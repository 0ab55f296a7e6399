#!/usr/bin/env python3
"""Local inference server CLI — the Azure endpoint contract without Azure.

Env contract (matching the other job CLIs):
  DCT_MODELS_DIR  — where checkpoints live (default data/models);
                    newest best ckpt is served, else last.ckpt
  DCT_CKPT        — serve a specific checkpoint file instead
  DCT_SERVE_HOST  — bind host (default 0.0.0.0)
  DCT_SERVE_PORT  — bind port (default 8901)

Throughput knobs (docs/SERVING.md; ServingConfig in dct_tpu/config.py):
  DCT_SERVE_PROCS           — SO_REUSEPORT serving processes (>1 forks
                              a ServerPool; this CLI forks EARLY, before
                              any threads, so it is the safe place)
  DCT_SERVE_WORKERS / DCT_SERVE_MAX_BATCH / DCT_SERVE_BATCH_WINDOW_MS
                            — per-process micro-batcher shape
  DCT_METRICS_DIR           — metrics-plane snapshot dir (this CLI arms
                              logs/metrics by default so a /metrics
                              scrape of any pool process reports fleet
                              totals; set empty to disable)

Endpoint mode — serve the LOCAL rollout endpoint instead of a raw
checkpoint (traffic-weighted blue/green routing + mirror shadowing over
the deploy DAG's persisted state):
  DCT_ENDPOINT_NAME         — endpoint to serve (enables this mode)
  DCT_LOCAL_ENDPOINT_STATE  — the rollout state JSON (same env the DAG
                              uses); stage transitions apply live

POST /score {"data": ...} -> {"probabilities": ...}; GET /healthz.
"""

from __future__ import annotations

import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def _serve_pool(build_server, what: str, serving, host: str,
                port: int) -> int:
    """Run a multi-process ServerPool until SIGTERM/SIGINT (clean exit
    0) or until its restart budget circuit-breaks (exit 1 — a pool
    that cannot hold capacity must not sit behind a healthy-looking
    banner). The dedicated entry point arms the resilience plane by
    default: child deaths are classified and respawned with backoff
    (``DCT_SERVE_MAX_RESTARTS`` budget), and ``DCT_SERVE_AUTOSCALE=1``
    runs the closed-loop proc autoscaler off the fleet queue-depth /
    SLO-burn / shed signals (docs/SERVING.md §elasticity)."""
    import signal

    from dct_tpu.resilience.supervisor import RestartPolicy
    from dct_tpu.serving.server import ServerPool
    from dct_tpu.utils.chip import refuse_shared_chip

    if serving.engine == "jax":
        # Every forked worker builds its own jitted scorer and would
        # claim the accelerator: refuse before the first fork.
        refuse_shared_chip(
            f"DCT_SERVE_PROCS={serving.processes} with DCT_SERVE_ENGINE=jax"
        )
    pool = ServerPool(
        build_server, processes=serving.processes, host=host, port=port,
        restart_policy=RestartPolicy(max_restarts=serving.max_restarts),
    )
    autoscaler = None
    publisher = None
    history_monitor = None
    if serving.autoscale:
        from dct_tpu.config import ObservabilityConfig

        obs = ObservabilityConfig.from_env()
        if not obs.metrics_dir:
            # A proc autoscaler without the metrics plane is BLIND: it
            # would read "queue 0" forever and drain a loaded pool to
            # the floor. Refuse loudly — no controller thread, no
            # unpublished gauge registry, the process state matches
            # this message.
            print(
                "[serving] DCT_SERVE_AUTOSCALE=1 needs DCT_METRICS_DIR "
                "(the fleet queue/shed signals) — autoscaler disabled",
                file=sys.stderr, flush=True,
            )
    if serving.autoscale and obs.metrics_dir:
        from dct_tpu.observability.metrics import MetricsRegistry
        from dct_tpu.serving import autoscale as _autoscale

        registry = MetricsRegistry()
        publisher = _autoscale.controller_publisher(registry)
        slo_monitor = None
        if obs.slo_spec:
            from dct_tpu.observability.slo import (
                SLOSpecError,
                SLOMonitor,
                parse_slo_spec,
            )

            try:
                specs = parse_slo_spec(obs.slo_spec)
                if specs:
                    # Alerting stays the scrape side's job: the
                    # controller only READS burn state as a signal.
                    slo_monitor = SLOMonitor(
                        specs,
                        fast_window_s=obs.slo_fast_window_s,
                        slow_window_s=obs.slo_slow_window_s,
                        burn_threshold=obs.slo_burn_threshold,
                    )
            except SLOSpecError:
                pass  # the serving children already report it loudly
        # Telemetry history (ISSUE 17): when DCT_TS_DIR arms the store
        # the pool parent runs the fleet-wide anomaly/incident monitor
        # (children each see 1/N of traffic; the parent reads it all),
        # and the autoscaler's queue/shed windows come from the same
        # on-disk history instead of between-poll deltas.
        from dct_tpu.observability import detect as _detect

        history_monitor = _detect.arm_from_env(
            registry=registry, emit=_autoscale.emit_default,
        )
        autoscaler = _autoscale.Autoscaler.from_config(
            _autoscale.PoolScaleTarget(pool), serving,
            signal_fn=_autoscale.pool_signal_fn(
                obs.metrics_dir, stale_s=obs.metrics_stale_s,
                slo_monitor=slo_monitor,
                history=(
                    history_monitor.reader
                    if history_monitor is not None else None
                ),
            ),
            emit=_autoscale.emit_default,
            registry=registry,
        ).start()

    def _term(signum, frame):
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, _term)
    print(
        f"serving {what} with {serving.processes} processes on "
        f"http://{host}:{pool.port} (POST /score, GET /healthz)",
        flush=True,
    )
    try:
        rc = pool.wait()
        if rc:
            print(
                "serving pool: worker deaths exhausted the restart "
                "budget — shutting down",
                file=sys.stderr, flush=True,
            )
        return rc
    finally:
        if autoscaler is not None:
            autoscaler.close()
        if history_monitor is not None:
            history_monitor.close()
        if publisher is not None:
            publisher.close()
        pool.close()


def main() -> int:
    from dct_tpu.config import ServingConfig

    # Persistent compile cache for the jax serving engine: configured
    # BEFORE any compile (the scorer compiles lazily on the first jax
    # flush), so endpoint spin-up disk-hits programs an earlier worker
    # — or the packaging-time warm-up — already compiled. No-op unless
    # DCT_COMPILE_CACHE arms it.
    from dct_tpu import compilecache

    compilecache.enable_from_env()

    host = os.environ.get("DCT_SERVE_HOST", "0.0.0.0")
    port = int(os.environ.get("DCT_SERVE_PORT", "8901"))
    # The dedicated serving entry point ARMS the metrics plane by
    # default (docs/OBSERVABILITY.md "Metrics plane"): every process of
    # a DCT_SERVE_PROCS pool publishes snapshots under this dir, so one
    # /metrics scrape of ANY process reports fleet totals. Library-built
    # servers stay local-only unless DCT_METRICS_DIR opts in; "" (set
    # but empty) disables explicitly.
    os.environ.setdefault("DCT_METRICS_DIR", "logs/metrics")
    serving = ServingConfig.from_env()

    endpoint = os.environ.get("DCT_ENDPOINT_NAME")
    if endpoint:
        from dct_tpu.serving.server import make_endpoint_server

        if serving.processes > 1:
            return _serve_pool(
                lambda h, p, reuse_port: make_endpoint_server(
                    endpoint, host=h, port=p, serving=serving,
                    reuse_port=reuse_port,
                ),
                f"rollout endpoint {endpoint!r}", serving, host, port,
            )
        server = make_endpoint_server(
            endpoint, host=host, port=port, serving=serving
        )
        print(
            f"serving rollout endpoint {endpoint!r} (state: "
            f"{server.state_path}) on http://{host}:{port} "
            "(POST /score, GET /healthz)",
            flush=True,
        )
        server.serve_forever()
        return 0

    from jobs.predict import _find_checkpoint
    from dct_tpu.serving.server import serve_forever

    models_dir = os.environ.get("DCT_MODELS_DIR", "data/models")
    ckpt = _find_checkpoint(models_dir)
    if serving.processes > 1:
        from dct_tpu.serving.server import make_server

        return _serve_pool(
            lambda h, p, reuse_port: make_server(
                ckpt, host=h, port=p, serving=serving,
                reuse_port=reuse_port,
            ),
            ckpt, serving, host, port,
        )
    serve_forever(ckpt, host=host, port=port)
    return 0


if __name__ == "__main__":
    sys.exit(main())

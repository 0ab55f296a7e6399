#!/usr/bin/env python3
"""Multi-tenant scheduler chaos smoke (the ``scheduler`` CI job /
ISSUE 12 acceptance).

A short but REAL 2-tenant session on CPU, training in ``supervised``
mode under ``jobs/scheduler.py``:

1. tenant A (weight 1) is fault-injected — ``crash@rank0:epoch1`` —
   and must be HEALED by its own round's PR 3 supervisor
   (``restart.relaunch`` on A's log, then further clean rounds);
2. tenant B (weight 2) must promote mid-run through gate + rollout
   (``loop.promoted`` on B's log) with zero errors — A's crash and
   healing never touch B's supervisor;
3. over the weighted run, each tenant's granted chip time must land
   within 20% of its configured share — asserted from the per-tenant
   ledger (``dct_tenant_chip_seconds_total``) on ONE aggregated
   ``/metrics`` scrape of ``DCT_METRICS_DIR``;
4. SIGTERM must drain BOTH tenants cleanly: exit code 0, ``sched.stop``
   on the scheduler log, ``tenant.stop`` for both, NO ``tenant.parked``.

Exit 0 on success; 1 with a diagnostic (+ log tails) on any gate
failing.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

WAIT_S = float(os.environ.get("DCT_SCHED_SMOKE_WAIT_S", "600"))
#: Fair shares under test: A weight 1, B weight 2.
WEIGHTS = {"alpha": 1.0, "beta": 2.0}
QUOTA_TOL = 0.20
MIN_RELEASES = 14


def _events(path: str, *names: str) -> list[dict]:
    out = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except ValueError:
                    continue
                if r.get("event") in names:
                    out.append(r)
    except OSError:
        pass
    return out


def _quota_shares_from_scrape(metrics_dir: str) -> dict[str, float] | None:
    """ONE aggregated scrape -> per-tenant granted chip-time shares."""
    from dct_tpu.observability.aggregate import aggregate_text

    _body, merged = aggregate_text(metrics_dir, stale_s=0)
    m = merged.metrics.get("dct_tenant_chip_seconds_total")
    if not m:
        return None
    by_tenant: dict[str, float] = {}
    for key, val in m["totals"].items():
        labels = dict(key)
        if "tenant" in labels:
            by_tenant[labels["tenant"]] = float(val)
    total = sum(by_tenant.values())
    if total <= 0:
        return None
    return {k: v / total for k, v in by_tenant.items()}


def main() -> int:
    from dct_tpu.data.synthetic import generate_weather_csv

    work = tempfile.mkdtemp(prefix="sched_smoke_")
    raw = os.path.join(work, "raw", "weather.csv")
    generate_weather_csv(raw, rows=400, seed=7)
    sched_events = os.path.join(work, "events", "events.jsonl")
    metrics_dir = os.path.join(work, "metrics")
    tenants_root = os.path.join(work, "tenants")

    tenants = [
        # The chaos tenant: a deterministic rank-0 crash its round's
        # supervisor must heal (two restarts budgeted, fast backoff).
        {"name": "alpha", "weight": WEIGHTS["alpha"], "env": {
            "DCT_FAULT_SPEC": "crash@rank0:epoch1",
            "DCT_MAX_RESTARTS": "2",
            "DCT_RESTART_BACKOFF_S": "0.5",
        }},
        {"name": "beta", "weight": WEIGHTS["beta"]},
    ]
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        DCT_TENANTS=json.dumps(tenants),
        DCT_SCHED_ROOT=tenants_root,
        DCT_SCHED_POLL_S="0.3",
        DCT_SCHED_MAX_WALL_S=str(int(WAIT_S)),
        DCT_RAW_CSV=raw,
        DCT_EVENTS_DIR=os.path.join(work, "events"),
        DCT_HEARTBEAT_DIR=os.path.join(work, "hb"),
        DCT_TRACKING_DIR=os.path.join(work, "mlruns"),
        DCT_METRICS_DIR=metrics_dir,
        DCT_METRICS_PUBLISH_S="0.5",
        # The contract under test: rounds under the PR 3 supervisor.
        DCT_LOOP_TRAIN_MODE="supervised",
        DCT_LOOP_EPOCHS_PER_ROUND="1",
        DCT_LOOP_SOAK_S="0.1",
        DCT_LOOP_POLL_S="0.3",
        DCT_LOOP_EVAL_POLL_S="0.3",
    )

    # Child output to a FILE (an undrained pipe would block the session
    # it measures — the continuous-loop smoke's lesson).
    sched_log = os.path.join(work, "scheduler.log")
    log_f = open(sched_log, "w")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO_ROOT, "jobs", "scheduler.py")],
        env=env, cwd=REPO_ROOT,
        stdout=log_f, stderr=subprocess.STDOUT,
    )

    def tenant_events(name: str, *evs: str) -> list[dict]:
        return _events(
            os.path.join(tenants_root, name, "events", "events.jsonl"),
            *evs,
        )

    failures: list[str] = []
    try:
        deadline = time.time() + WAIT_S
        while time.time() < deadline:
            if proc.poll() is not None:
                failures.append(
                    f"scheduler exited early with code {proc.returncode}"
                )
                break
            healed = bool(tenant_events("alpha", "restart.relaunch"))
            alpha_rounds = tenant_events("alpha", "loop.round")
            beta_promos = tenant_events("beta", "loop.promoted")
            releases = _events(sched_events, "sched.release")
            # Heal must be PROVEN recovered: a clean alpha round after
            # the healed one (restarts==0 on a later round record).
            healed_rounds = [r for r in alpha_rounds if r.get("restarts")]
            healed_then_clean = healed and bool(healed_rounds) and any(
                r.get("round", 0) > healed_rounds[0].get("round", 0)
                and not r.get("restarts")
                for r in alpha_rounds
            )
            if (
                healed_then_clean
                and beta_promos
                and len(releases) >= MIN_RELEASES
            ):
                break
            time.sleep(1.0)
        else:
            failures.append(
                f"timed out after {WAIT_S:.0f}s waiting for heal + "
                f"promotion + {MIN_RELEASES} releases"
            )

        if proc.poll() is None:
            print("[smoke] SIGTERM -> drain-all", flush=True)
            proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=180)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            failures.append("scheduler did not drain within 180s of SIGTERM")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        log_f.close()

    # ---- assertions over the artifacts --------------------------------
    if proc.returncode != 0 and not failures:
        failures.append(f"drain exit code {proc.returncode} != 0")

    # Fault isolation: alpha crashed AND healed...
    faults = tenant_events("alpha", "fault.injected")
    relaunches = tenant_events("alpha", "restart.relaunch")
    if not faults:
        failures.append("alpha never fired its injected fault")
    if not relaunches:
        failures.append("alpha's crash was never healed (no relaunch)")
    alpha_rounds = tenant_events("alpha", "loop.round")
    healed_rounds = [r for r in alpha_rounds if r.get("restarts")]
    if healed_rounds:
        after = [
            r for r in alpha_rounds
            if r.get("round", 0) > healed_rounds[0].get("round", 0)
            and not r.get("restarts")
        ]
        if not after:
            failures.append("no clean alpha round after the healed one")
    # ...while beta trained and promoted uninterrupted.
    beta_promos = tenant_events("beta", "loop.promoted")
    beta_errors = tenant_events("beta", "loop.error")
    beta_stops = tenant_events("beta", "loop.stop")
    if not beta_promos:
        failures.append("beta never promoted mid-run")
    if beta_errors:
        failures.append(f"beta saw loop.error: {beta_errors[0]}")
    if beta_stops and beta_stops[-1].get("error"):
        failures.append(f"beta stopped on error: {beta_stops[-1]['error']}")
    parked = _events(sched_events, "tenant.parked")
    if parked:
        failures.append(f"tenant parked during the session: {parked}")
    stops = _events(sched_events, "tenant.stop")
    if len(stops) < 2:
        failures.append(f"{len(stops)} tenant.stop record(s) < 2")
    if not _events(sched_events, "sched.stop"):
        failures.append("no sched.stop record — the drain was not clean")

    # Quota: ONE aggregated scrape of the metrics plane.
    shares = _quota_shares_from_scrape(metrics_dir)
    if not shares:
        failures.append("no dct_tenant_chip_seconds_total on the scrape")
    else:
        total_w = sum(WEIGHTS.values())
        for name, w in WEIGHTS.items():
            fair = w / total_w
            got = shares.get(name, 0.0)
            rel = abs(got - fair) / fair
            print(
                f"[smoke] quota {name}: granted_share={got:.3f} "
                f"fair={fair:.3f} rel_err={rel:.2%}",
                flush=True,
            )
            if rel > QUOTA_TOL:
                failures.append(
                    f"{name} granted share {got:.3f} is {rel:.0%} from "
                    f"its {fair:.3f} quota (> {QUOTA_TOL:.0%})"
                )

    print(
        f"[smoke] faults={len(faults)} relaunches={len(relaunches)} "
        f"alpha_rounds={len(alpha_rounds)} beta_promos={len(beta_promos)} "
        f"rc={proc.returncode}",
        flush=True,
    )
    if failures:
        print("[smoke] FAIL:", "; ".join(failures), flush=True)
        for label, path in (
            ("scheduler stdout", sched_log),
            ("scheduler events", sched_events),
            ("alpha events", os.path.join(
                tenants_root, "alpha", "events", "events.jsonl")),
            ("beta events", os.path.join(
                tenants_root, "beta", "events", "events.jsonl")),
        ):
            print(f"---- {label} tail ----")
            try:
                with open(path) as f:
                    print("".join(f.readlines()[-20:]))
            except OSError:
                pass
        return 1
    print(
        "[smoke] PASS: alpha crash healed in-lease, beta promoted "
        "uninterrupted, quota within 20% on one scrape, clean drain-all",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

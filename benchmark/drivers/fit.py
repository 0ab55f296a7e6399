"""Driver ``fit``: one ``Trainer.fit`` run, entered as ``jobs/train_tpu.py``
enters it, stopped at the stamp that closes the window.

The traffic file gives the job's shape (sequence length, sequences per
chip and step, steps per epoch, validation batches); the configuration
file gives the model's environment. From the program the driver takes the
system under test and its counters (tracker steps, event log, spans); the
clock, the window and the arithmetic are the benchmark's own.

How the window is cut without a change to the program: the driver hands
``Trainer`` a tracker that wraps the real one and stamps the benchmark's
clock whenever the trainer logs an epoch's metrics (the call right before
its ``epoch_end`` event). The first epoch is set-up: the compile or the
load of the epoch program and its first run. The window starts at its
stamp and ends at the stamp nearest to ``--seconds`` (the first with
elapsed >= seconds - half an epoch, and never before one whole epoch).
That stamp ends the run: the tracker call raises the trainer's own
``PreemptedError``, and ``fit`` leaves the way its health halt leaves, with
the successor epoch in flight and abandoned. Asking the trainer's
preemption guard instead (ISSUE 23's plan) costs one more epoch drained and
checkpointed after the last stamp, 22 s to 60 s a run that no metric reads.
Only whole epochs between the first and last stamp count, so nothing
depends on what a stopped ``fit`` returns.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import time

#: bf16 operands, f32 accumulation, against an f32 "highest" reference:
#: PR 21 measured 1.3e-2 relative for the flash kernel alone against
#: blockwise in bf16, and three blocks of bf16 GEMMs add to it. 2^-5
#: relative to the largest reference logit is 8 bf16 ulps: a step computed
#: in fp8 (2^-3 per operand) or a dropped term (mask, window, bias, RoPE)
#: moves the logits by far more, and f32-vs-bf16 differences stay inside.
LOGIT_TOL = 2.0 ** -5
#: The loss is a mean over thousands of positions, so roundings average
#: out; 2^-7 relative is still loose for bf16 and fails a wrong mask.
LOSS_TOL = 2.0 ** -7


class Plan:
    """Sizes the trainer will see, derived from traffic and device count.

    The causal family trains on stride-1 windows and splits them by time
    with a ``seq_len`` gap, so rows and the validation fraction follow
    from the steps wanted: ``train = steps * G`` windows, ``val =
    val_batches * G`` windows, ``n_w = train + seq_len + val``."""

    def __init__(self, config: dict, traffic: dict, n_devices: int):
        mesh = traffic.get("mesh", {})
        self.data_parallel = int(mesh.get("data", n_devices))
        if self.data_parallel != n_devices:
            raise SystemExit(
                f"traffic wants data={self.data_parallel}, "
                f"found {n_devices} devices"
            )
        self.seq_len = int(traffic["seq_len"])
        self.batch_per_chip = int(traffic["batch_per_chip"])
        self.steps = int(traffic["steps_per_epoch"])
        self.val_batches = int(traffic["val_batches"])
        self.global_batch = self.batch_per_chip * self.data_parallel
        train = self.steps * self.global_batch
        val = self.val_batches * self.global_batch
        n_w = train + self.seq_len + val
        self.rows = n_w + self.seq_len
        # int((1 - vf) * n_w) must be exactly `train`: aim at train + 0.5.
        self.val_fraction = 1.0 - (train + 0.5) / n_w
        self.tokens_per_epoch = train * self.seq_len
        self.input_dim = int(config.get("input_dim", 5))
        self.model_env = dict(config["program"]["env"])

    def env(self, work: str, processed: str, cache_dir: str) -> dict:
        return {
            **self.model_env,
            "DCT_SEQ_LEN": self.seq_len,
            "DCT_BATCH_SIZE": self.batch_per_chip,
            "DCT_VAL_FRACTION": repr(self.val_fraction),
            # Stopped by the window, never by the budget.
            "DCT_EPOCHS": 100000,
            "DCT_PROCESSED_DIR": processed,
            "DCT_MODELS_DIR": os.path.join(work, "models"),
            "DCT_TRACKING_DIR": os.path.join(work, "mlruns"),
            "DCT_EVENTS_DIR": os.path.join(work, "events"),
            "DCT_HEARTBEAT_DIR": os.path.join(work, "heartbeats"),
            "DCT_COMPILE_CACHE": "on",
            "DCT_COMPILE_CACHE_AOT_DIR": os.path.join(cache_dir, "aot"),
        }


class StampingTracker:
    """The real tracker, plus the benchmark's clock at every epoch's
    metrics. Runs on the trainer's thread: no polling, no second thread.
    The stamp that closes the window ends ``fit`` from here."""

    def __init__(self, inner, on_epoch):
        self._inner = inner
        self._on_epoch = on_epoch

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def log_metrics(self, metrics, step=None):
        from dct_tpu.resilience.preempt import PreemptedError

        closed = "val_loss" in metrics and self._on_epoch(metrics, step)
        out = self._inner.log_metrics(metrics, step=step)
        if closed:
            raise PreemptedError("benchmark: the window is closed")
        return out


class Window:
    """Stamps, the stop rule, and the profiler's start and stop."""

    def __init__(self, plan: Plan, seconds: float, *, trace_dir, age_fn):
        self.plan, self.seconds = plan, seconds
        self.trace_dir = trace_dir
        self.age_fn = age_fn
        self.stamps: list[float] = []
        self.steps: list[int] = []
        self.losses: list[float] = []
        #: The program's own goodput_fraction, as it logs it each epoch.
        self.goodput: list[float | None] = []
        self.start: float | None = None
        self.start_wall: float | None = None
        self.setup_s: float | None = None
        self.tracing = False
        self.closed = False

    def on_epoch(self, metrics, step) -> bool:
        """Stamp one epoch; True once this stamp closes the window."""
        import jax

        now = time.perf_counter()
        self.stamps.append(now)
        self.steps.append(int(step))
        self.losses.append(float(metrics["train_loss_epoch"]))
        self.goodput.append(metrics.get("goodput_fraction"))
        n = len(self.stamps)
        if n == 1:  # the warm-up epoch's stamp opens the window
            self.setup_s = self.age_fn()
            self.start, self.start_wall = now, time.time()
            if self.trace_dir:
                # Host spans (TraceAnnotation) on, the Python call tracer
                # off: it would time every call of the trainer's thread.
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(
                    self.trace_dir, profiler_options=opts)
                self.tracing = True
                with jax.profiler.TraceAnnotation("bench.trace_begin"):
                    pass
            return False
        # A marker on the profiler's clock at every boundary the harness
        # sees: the reduction attributes idle gaps with them.
        with jax.profiler.TraceAnnotation(f"bench.epoch_end.{n - 1}"):
            pass
        elapsed = now - self.start
        if elapsed >= self.seconds - 0.5 * (now - self.stamps[-2]):
            self.close()
            self.closed = True
        return self.closed

    def close(self) -> None:
        import jax

        if self.tracing:
            with jax.profiler.TraceAnnotation("bench.trace_end"):
                pass
            jax.profiler.stop_trace()
            self.tracing = False


@contextlib.contextmanager
def env_overlay(env: dict):
    """Set environment variables for one run and put the old values back."""
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update({k: str(v) for k, v in env.items()})
    try:
        yield
    finally:
        for k, old in saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old


def _etl(work: str, rows: int, seed: int) -> str:
    from dct_tpu.data.synthetic import generate_weather_csv
    from dct_tpu.etl.preprocess import preprocess_csv_to_parquet

    csv = os.path.join(work, "raw", "weather.csv")
    processed = os.path.join(work, "processed")
    generate_weather_csv(csv, rows=rows, seed=seed)
    preprocess_csv_to_parquet(csv, processed)
    return processed


def _reference_check(cfg, trainer, plan: Plan, config: dict) -> dict:
    """The system's forward and loss on one seeded batch, from the
    trainer's own seeded initial parameters, against the plain float32
    reference. Runs before ``fit`` and frees what it made."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.manifest import BENCH_DIR, load_module
    from dct_tpu.data.dataset import load_processed_dataset
    from dct_tpu.data.windows import make_windows
    from dct_tpu.models.registry import get_model
    from dct_tpu.ops.losses import masked_cross_entropy
    from dct_tpu.parallel.mesh import make_global_batch

    ref = load_module(
        os.path.join(BENCH_DIR, "reference", config["reference"] + ".py"),
        "bench_reference",
    )
    data = make_windows(
        load_processed_dataset(cfg.data.processed_dir), cfg.model.seq_len,
        per_position_labels=True, horizon=1,
    )
    g = plan.global_batch
    idx = np.arange(g)
    x = np.ascontiguousarray(data.take(idx), np.float32)
    y = np.asarray(data.labels[idx], np.int32)
    w = np.ones((g,), np.float32)
    compute = jnp.bfloat16 if cfg.train.bf16_compute else jnp.float32
    model = get_model(
        cfg.model, input_dim=data.input_dim, compute_dtype=compute,
        mesh=trainer.mesh,
    )
    # The trainer's own seeding (train/state.py create_train_state).
    init_key, _ = jax.random.split(jax.random.PRNGKey(cfg.train.seed))
    params = jax.jit(model.init)(
        init_key, jnp.zeros((1, cfg.model.seq_len, data.input_dim))
    )
    params = {"params": params["params"]}
    gx, gy, gw = make_global_batch(trainer.mesh, x, y, w)

    @jax.jit
    def system(p, bx, by, bw):
        logits = model.apply(p, bx, train=False)
        wpos = jnp.broadcast_to(bw[:, None], by.shape)
        s, c = masked_cross_entropy(logits, by, wpos)
        return logits, s / c

    logits, loss = system(params, gx, gy, gw)
    # The first sequence: what the f32 reference holds at 4k positions.
    got = np.asarray(jax.device_get(logits), np.float32)[:1]
    host_params = jax.device_get(params)
    del params, logits
    want, want_loss = ref.forward_and_loss(
        host_params["params"], x[:1], y[:1], config
    )
    # The system's loss over the same sequences the reference saw.
    sys_loss = ref.cross_entropy(got, y[:1])
    scale = float(np.abs(want).max())
    logit_err = float(np.abs(got - want).max() / max(scale, 1e-6))
    loss_err = abs(sys_loss - want_loss) / max(abs(want_loss), 1e-6)
    return {
        "ok": bool(
            np.isfinite(got).all() and logit_err <= LOGIT_TOL
            and loss_err <= LOSS_TOL
        ),
        "logit_rel_err": logit_err, "loss_rel_err": loss_err,
        "system_loss": sys_loss, "reference_loss": want_loss,
        "full_batch_loss": float(loss),
    }


def _read_jsonl(path: str) -> list:
    out = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    out.append(json.loads(line))
    return out


def run(cell: dict, config: dict, traffic: dict, *, seed: int,
        seconds: float, trace: bool, work: str, cache_dir: str,
        age_fn) -> dict:
    """One run of one fit cell. Returns the artefacts the end-to-end
    arithmetic and the per-layer readers work from."""
    import jax

    from dct_tpu.config import RunConfig
    from dct_tpu.parallel.distributed import initialize_from_env
    from dct_tpu.resilience.preempt import PreemptedError
    from dct_tpu.tracking import get_tracker
    from dct_tpu.train.trainer import Trainer

    plan = Plan(config, traffic, len(jax.devices()))
    if os.path.isdir(work):
        shutil.rmtree(work)
    os.makedirs(work)
    compiles: list[float] = []

    def on_duration(name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    marks = {"driver_start": age_fn()}
    processed = _etl(work, plan.rows, seed)
    marks["etl_done"] = age_fn()
    env = plan.env(work, processed, cache_dir)
    env["DCT_SEED"] = seed
    trace_dir = os.path.join(work, "trace") if trace else None
    window = Window(plan, seconds, trace_dir=trace_dir, age_fn=age_fn)
    prev_cwd = os.getcwd()
    os.chdir(work)  # relative defaults (logs/, mlruns_local) land here
    try:
        with env_overlay(env):
            cfg = RunConfig.from_env()
            initialize_from_env(cfg.dist)
            tracker = StampingTracker(
                get_tracker(
                    tracking_uri=cfg.tracking.tracking_uri,
                    experiment=cfg.tracking.experiment,
                ),
                window.on_epoch,
            )
            trainer = Trainer(cfg, tracker=tracker)
            reference = _reference_check(cfg, trainer, plan, config)
            marks["reference_done"] = age_fn()
            mesh = {k: int(v) for k, v in trainer.mesh.shape.items()}
            try:
                trainer.fit()
                stopped = "budget"
            except PreemptedError:
                stopped = "window" if window.closed else "preempted"
            finally:
                window.close()
    finally:
        os.chdir(prev_cwd)
    # Both checkpoint tiers of the run, 9 GB at these sizes: nothing reads
    # them again, and a check keeps a directory a cell and side.
    shutil.rmtree(env["DCT_MODELS_DIR"], ignore_errors=True)
    events = _read_jsonl(os.path.join(env["DCT_EVENTS_DIR"], "events.jsonl"))
    spans = []
    spans_dir = os.path.join(env["DCT_EVENTS_DIR"], "spans")
    if os.path.isdir(spans_dir):
        for name in sorted(os.listdir(spans_dir)):
            spans += _read_jsonl(os.path.join(spans_dir, name))
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    )
    return {
        "plan": plan, "window": window, "stopped": stopped, "mesh": mesh,
        "reference": reference, "events": events, "spans": spans,
        "compiles": compiles, "setup_marks": marks, "trace_dir": trace_dir,
        "memory_peak_bytes": int(peak),
        "attention_path": _attention_path(cfg),
    }


def _attention_path(cfg) -> dict:
    from dct_tpu.ops.attention import (
        flash_interpret_mode,
        select_attention_path,
    )

    return {
        "path": select_attention_path(cfg.model.seq_len),
        "interpret": flash_interpret_mode(),
    }


def end_to_end(art: dict) -> dict:
    """The end-to-end arithmetic, from the benchmark's own stamps."""
    win, plan = art["window"], art["plan"]
    stamps = win.stamps
    n_epochs = len(stamps) - 1
    out: dict = {
        "setup_s": win.setup_s, "epochs": max(n_epochs, 0),
        "epoch_seconds": [b - a for a, b in zip(stamps, stamps[1:])],
        "compiles_after_first_stamp": [
            t - stamps[0] for t in art["compiles"]
            if stamps and t > stamps[0]],
    }
    if n_epochs >= 1:
        out["fit_tokens_per_s"] = (
            n_epochs * plan.tokens_per_epoch / (stamps[-1] - stamps[0])
        )
    return out


def verdict(art: dict) -> dict:
    """``correct``, ``attempted``, ``failed`` and the reasons."""
    win, plan = art["window"], art["plan"]
    why: list[str] = []
    if not art["reference"]["ok"]:
        why.append(f"reference: {art['reference']}")
    losses = win.losses
    bad = [i for i, v in enumerate(losses) if not math.isfinite(v)]
    if bad:
        why.append(f"non-finite loss in epochs {bad}")
    if len(losses) >= 2 and not losses[-1] < losses[0]:
        why.append(f"loss did not fall: {losses[0]} -> {losses[-1]}")
    step_deltas = {b - a for a, b in zip(win.steps, win.steps[1:])}
    if step_deltas - {plan.steps}:
        why.append(f"steps per epoch {step_deltas}, planned {plan.steps}")
    # The closing stamp ends fit before that epoch's event is written.
    ends = [e for e in art["events"] if e.get("event") == "epoch_end"]
    if len(ends) != len(win.stamps) - 1:
        why.append(f"{len(ends)} epoch_end events, {len(win.stamps)} stamps")
    late = [t for t in art["compiles"] if win.start and t > win.start]
    if late:
        why.append(f"{len(late)} XLA compile(s) inside the window")
    loud = [
        e for e in art["events"]
        if e.get("event") in ("compile.cache_miss", "compile.window")
        and win.start_wall and e.get("ts", 0) > win.start_wall
    ]
    if loud:
        why.append(f"compile events inside the window: {loud[:2]}")
    if art["stopped"] != "window":
        why.append(f"fit ended by {art['stopped']}, not by the window")
    # What the configuration expects, unless the traffic says otherwise
    # (a length the policy sends down another attention path).
    expect = {**art["config"].get("expect", {}),
              **art["traffic"].get("expect", {})}
    got = art["attention_path"]
    if expect and (got["path"], got["interpret"]) != (
        expect["attention_path"], expect["flash_interpret"]
    ):
        why.append(f"attention path {got}, configuration expects {expect}")
    attempted = max(len(win.stamps) - 1, 0)
    failed = sum(1 for i in bad if i > 0)
    if attempted < 1:
        why.append("no whole epoch inside the window")
    return {
        "correct": not why, "attempted": attempted, "failed": failed,
        "why": why,
    }

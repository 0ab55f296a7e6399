"""Driver ``fit_routed``: driver ``fit`` for a family with routed experts.

The run is ``fit``'s: one ``Trainer.fit`` entered as ``jobs/train_tpu.py``
enters it, the window cut by the benchmark's own stamps. Two things differ.

The reference check. ``fit`` takes the largest distance between the system's
bf16 logits and a free-running float32 reference. A 64-way top-4 cannot be
held to that: bf16 activations move a router score a little, ranks 4 and 5
of 64 lie closer than that for some (position, layer) pairs, and each swap
exchanges a quarter of that token's expert output. So the comparison is in
two parts, on the first sequence at the timed length, from the trainer's
seeded initial parameters with the expert bias set to seeded values (so the
bias path is exercised):

(i) everything but the discrete choice: the system's logits against the
    reference run with the SYSTEM's chosen experts (read from the model's
    ``intermediates`` collection, in this check only) and the reference's
    own weights for them, under ``fit``'s ``LOGIT_TOL`` and ``LOSS_TOL``;
(ii) the choice: in that same run the reference, whose input to every
    router is then the one the system's router saw up to rounding, makes
    its OWN choice. Every (position, layer) pair where the two sets differ
    must be a near-tie of the reference (margin between its 4th and 5th
    selection score under ``TIE_WIDTH``), and at most ``MAX_DISAGREE`` of
    the pairs may differ. (A free-running reference would be compared on
    inputs that already differ by an earlier swap; teacher-forcing the
    earlier layers holds each router to its own decision.)

A sigmoid swapped for another function of another order, a bias left out of
the selection or added to the weights, a missing normalisation or a top-3
fails (i) or (ii) by far.

The counters. The trainer logs the model's routed-row counters with each
epoch's metrics; the window keeps them, the verdict wants zero rows past the
grouped products' bound in every epoch, and the per-layer readers take the
load from them. Traced, the driver also saves the optimized HLO text of the
epoch program that ran, beside the trace: the v5e's trace does not carry
``op_name``, and ``benchmark/reduce/scopes.py`` joins the two by name.
"""

from __future__ import annotations

import os
import shutil
import time

from benchmark.drivers.fit import (  # noqa: F401  (run.py calls end_to_end)
    LOGIT_TOL,
    LOSS_TOL,
    Plan,
    StampingTracker,
    Window,
    _attention_path,
    _etl,
    _read_jsonl,
    end_to_end,
    env_overlay,
)
from benchmark.drivers.fit import verdict as _fit_verdict

#: Width of a near-tie between the 4th and 5th selection score. The router
#: runs in float32 at full precision on a bf16 hidden state. That state
#: carries one bf16 ulp (2^-8) of relative error per block before it, the
#: input projection included: 6 x 2^-8 at the last of five blocks. The logit
#: is a 2,048-term product with it, whose terms err independently, so it
#: moves by that share of the logits' own spread (0.58 at this
#: initialisation: unit-RMS inputs, weights uniform in +-1/sqrt(2048)): 0.014.
#: A sigmoid's slope is at most 1/4 and two scores move apart: 0.0068 for a
#: typical pair. The largest of 32,768 pairs lies 4 to 5 times further out:
#: 2^-5 = 0.031. The selection scores spread over +-0.4, so a bias left out,
#: or a wrong function, disagrees at margins ten times this.
TIE_WIDTH = 2.0 ** -5
#: Disagreeing pairs, at most (ISSUE 27): a near-tie flips about half the
#: time, and a few percent of the pairs are that close.
MAX_DISAGREE = 0.10
#: Standard deviation of the seeded expert bias: the spread of the scores
#: themselves at initialisation (a sigmoid of logits of spread 0.58).
BIAS_SCALE = 0.14


class RoutedWindow(Window):
    """``fit``'s window, which also keeps the model's counters of every
    stamped epoch (the ``moe_*`` keys of the trainer's epoch metrics)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.counters: list[dict] = []

    def on_epoch(self, metrics, step) -> bool:
        self.counters.append(
            {k: float(v) for k, v in metrics.items() if k.startswith("moe_")})
        return super().on_epoch(metrics, step)


def _reference_check(cfg, trainer, plan: Plan, config: dict) -> dict:
    """Parts (i) and (ii) of the module docstring. Runs before ``fit`` and
    frees what it made."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.manifest import BENCH_DIR, load_module
    from dct_tpu.data.dataset import load_processed_dataset
    from dct_tpu.data.windows import make_windows
    from dct_tpu.models.registry import get_model

    ref = load_module(
        os.path.join(BENCH_DIR, "reference", config["reference"] + ".py"),
        "bench_reference",
    )
    data = make_windows(
        load_processed_dataset(cfg.data.processed_dir), cfg.model.seq_len,
        per_position_labels=True, horizon=1,
    )
    x = np.ascontiguousarray(data.take(np.arange(1)), np.float32)
    y = np.asarray(data.labels[:1], np.int32)
    compute = jnp.bfloat16 if cfg.train.bf16_compute else jnp.float32
    model = get_model(
        cfg.model, input_dim=data.input_dim, compute_dtype=compute,
        mesh=trainer.mesh,
    )
    # The trainer's own seeding (train/state.py create_train_state).
    init_key, _ = jax.random.split(jax.random.PRNGKey(cfg.train.seed))
    params = jax.device_get(jax.jit(model.init)(
        init_key, jnp.zeros((1, cfg.model.seq_len, data.input_dim))
    )["params"])
    rng = np.random.default_rng(cfg.train.seed)
    for name in sorted(params):
        if "moe" in params[name]:
            bias = params[name]["moe"]["expert_bias"]
            params[name]["moe"]["expert_bias"] = (
                BIAS_SCALE * rng.standard_normal(bias.shape)
            ).astype(np.float32)

    @jax.jit
    def system(p, bx):
        return model.apply(
            {"params": p}, bx, train=False, mutable=["intermediates"])

    logits, sown = system(params, jnp.asarray(x))
    got = np.asarray(jax.device_get(logits), np.float32)
    sown = jax.device_get(sown)["intermediates"]
    # [1, layers with experts, T, k], in layer order.
    chosen = np.stack([
        np.asarray(sown[name]["moe"]["topk"][0])
        for name in sorted(sown, key=lambda n: int(n.rsplit("_", 1)[1]))
    ])[None]
    del logits
    out = ref.forward(params, x, config, routing=chosen)
    want = out["logits"]
    want_loss = ref.cross_entropy(want, y)
    sys_loss = ref.cross_entropy(got, y)
    scale = float(np.abs(want).max())
    logit_err = float(np.abs(got - want).max() / max(scale, 1e-6))
    loss_err = abs(sys_loss - want_loss) / max(abs(want_loss), 1e-6)
    differs = (
        np.sort(chosen, -1) != np.sort(out["topk"], -1)).any(-1)
    share = float(differs.mean())
    widest = float(out["margin"][differs].max()) if differs.any() else 0.0
    return {
        "ok": bool(
            np.isfinite(got).all() and logit_err <= LOGIT_TOL
            and loss_err <= LOSS_TOL and widest < TIE_WIDTH
            and share <= MAX_DISAGREE
        ),
        "logit_rel_err": logit_err, "loss_rel_err": loss_err,
        "system_loss": sys_loss, "reference_loss": want_loss,
        "routing_disagree_share": share,
        "routing_disagree_by_layer": differs.mean(axis=(0, 2)).tolist(),
        "routing_widest_disagreeing_margin": widest,
        "routing_pairs": int(differs.size),
    }


def run(cell: dict, config: dict, traffic: dict, *, seed: int,
        seconds: float, trace: bool, work: str, cache_dir: str,
        age_fn) -> dict:
    """One run of one cell: ``fit.run`` with this file's window and
    reference check, and the epoch program's HLO text beside the trace."""
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
    window = RoutedWindow(plan, seconds, trace_dir=trace_dir, age_fn=age_fn)
    hlo_text = None
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
            if trace:
                # The executable the epochs ran (loaded from the store or
                # compiled): its text names every instruction's scope.
                program = trainer.aot_store.executables.get("scan_k1")
                if program is not None:
                    hlo_text = program.as_text()
                    with open(os.path.join(
                            trace_dir, "epoch_program.hlo.txt"), "w") as f:
                        f.write(hlo_text)
    finally:
        os.chdir(prev_cwd)
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
        "hlo_text": hlo_text,
    }


def verdict(art: dict) -> dict:
    """``fit``'s verdict, and no routed row past the grouped products'
    bound in any epoch the window stamped."""
    out = _fit_verdict(art)
    counters = art["window"].counters
    if not counters or any("moe_rows_overflowed" not in c for c in counters):
        out["why"].append("an epoch logged no routed-row counters")
    else:
        over = [c["moe_rows_overflowed"] for c in counters]
        if any(over):
            out["why"].append(f"routed rows dropped, by epoch: {over}")
    out["correct"] = not out["why"]
    return out

#!/usr/bin/env python3
"""Benchmark: parity-config throughput + scaled-config MFU, honest both ways.

Three stories in one JSON line:

1. **Parity config** (the reference's exact training configuration — MLP
   5->64->2, dropout 0.2, Adam lr 0.01, batch 4 per rank, seed 42;
   reference jobs/train_lightning_ddp.py:14,57-61,88,122), two numbers:
   - ``value`` — the fused scan-path number (all timed epochs stacked into
     one AOT dispatch): the framework's best case at the tiny parity batch,
     where per-dispatch latency otherwise dominates;
   - ``trainer_loop_samples_per_sec_per_chip`` — the REAL ``Trainer.fit()``
     loop at the same config, paying eval, checkpointing, resume-state
     saves, and per-epoch dispatch. This is what the product delivers.
   Baseline: the reference's compute stack (torch CPU loop with identical
   model/optimizer/batch semantics) measured live on this host.

2. **Scaled config** — a transformer at MXU-relevant sizes (d_model 512,
   seq 1024, bf16) with ``mfu`` = analytic matmul FLOPs/step / step time /
   chip peak bf16 FLOPs (peak from the device kind; override with
   DCT_PEAK_TFLOPS). The parity MLP cannot utilize an MXU (~1e-6 MFU);
   this is the number that says how well the framework maps to the
   hardware. Includes Pallas-flash vs XLA-blockwise attention step times.

3. **Scaled MoE** — sorted/segment dispatch vs one-hot einsum dispatch
   step times at a capacity where the [N,E,C] einsum tensors dominate.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "samples/sec/chip", "vs_baseline": N,
   "trainer_loop_samples_per_sec_per_chip": N, "scaled": {...},
   "moe": {...}, ...}
"""

from __future__ import annotations

import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

ROWS = int(os.environ.get("DCT_BENCH_ROWS", "20000"))
BATCH = 4  # per-rank parity batch (jobs/train_lightning_ddp.py:122)
WARMUP_EPOCHS = 1
TIMED_EPOCHS = max(1, int(os.environ.get("DCT_BENCH_EPOCHS", "3")))


def _prepare_data(tmp: str):
    from dct_tpu.data.dataset import load_processed_dataset
    from dct_tpu.data.synthetic import generate_weather_csv
    from dct_tpu.etl.preprocess import preprocess_csv_to_parquet

    csv = os.path.join(tmp, "raw", "weather.csv")
    generate_weather_csv(csv, rows=ROWS, seed=0)
    processed = os.path.join(tmp, "processed")
    preprocess_csv_to_parquet(csv, processed)
    return load_processed_dataset(processed)


def bench_tpu(data) -> tuple[float, float]:
    """Returns (samples_per_sec_per_chip, final_train_loss)."""
    import jax

    from dct_tpu.config import MeshConfig, ModelConfig
    from dct_tpu.data.pipeline import BatchLoader, train_val_split
    from dct_tpu.models.registry import get_model
    from dct_tpu.parallel.mesh import make_global_epoch, make_mesh, shard_state
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import make_epoch_train_step
    from dct_tpu.train.trainer import Trainer

    mesh = make_mesh(MeshConfig())
    n_chips = mesh.size
    global_batch = BATCH * mesh.shape["data"]

    train_idx, _ = train_val_split(len(data), val_fraction=0.2, seed=42)
    loader = BatchLoader(data, train_idx, global_batch=global_batch, shuffle=True, seed=42)

    import jax.numpy as jnp

    model = get_model(
        ModelConfig(), input_dim=data.input_dim, compute_dtype=jnp.bfloat16
    )
    state = create_train_state(model, input_dim=data.input_dim, lr=0.01, seed=42)
    state = shard_state(state, mesh)
    epoch_train = make_epoch_train_step()

    # The timed region includes everything the real trainer does per epoch
    # — host batch assembly, H2D transfer, and compute — matching what the
    # torch baseline's timed DataLoader loop includes.
    #
    # Epoch fusion (DCT_BENCH_FUSE=0 to disable): all timed epochs are
    # stacked host-side into ONE [E*S, B, ...] scan — a single H2D staging
    # and a single dispatch for the whole timed region. Identical update
    # sequence to per-epoch dispatch (each epoch keeps its own shuffle);
    # on a real chip behind a slow control plane, per-dispatch latency at
    # the tiny parity batch otherwise dominates the measurement.
    import numpy as np

    fuse = os.environ.get("DCT_BENCH_FUSE", "1").strip().lower() not in (
        "0", "false", "no"
    )
    # One warm epoch in BOTH modes: the timed region then starts from the
    # identical model state / step counter, so the per-step update sequence
    # (incl. step-folded dropout keys) is the same fused or not.
    warm_g = make_global_epoch(mesh, *Trainer._stack_epoch(loader, 0))
    steps_per_epoch = warm_g[0].shape[0]
    state, losses = epoch_train(state, *warm_g)
    jax.block_until_ready(losses)

    if fuse:
        # AOT-compile the fused [E*S, ...] shape outside the timed region.
        fused_specs = tuple(
            jax.ShapeDtypeStruct(
                (TIMED_EPOCHS * steps_per_epoch, *g.shape[1:]),
                g.dtype,
                sharding=g.sharding,
            )
            for g in warm_g
        )
        fused_fn = epoch_train.lower(state, *fused_specs).compile()

    t0 = time.perf_counter()
    if fuse:
        stacks = [
            Trainer._stack_epoch(loader, e) for e in range(1, 1 + TIMED_EPOCHS)
        ]
        fused = tuple(
            np.concatenate(cols, axis=0) for cols in zip(*stacks)
        )
        state, losses = fused_fn(state, *make_global_epoch(mesh, *fused))
    else:
        for e in range(1, 1 + TIMED_EPOCHS):
            stack = Trainer._stack_epoch(loader, e)
            state, losses = epoch_train(state, *make_global_epoch(mesh, *stack))
    jax.block_until_ready(losses)
    dt = time.perf_counter() - t0

    samples = TIMED_EPOCHS * steps_per_epoch * global_batch
    return samples / dt / n_chips, float(jax.device_get(losses)[-1])


def _bench_prefetch_spans() -> int:
    """ONE parse of DCT_PREFETCH_SPANS for the bench: the trainer-loop
    legs build their TrainConfig with it and the trainer_gap stanza
    stamps the same value, so the recorded provenance can never diverge
    from the mode that was actually measured."""
    try:
        return int(os.environ.get("DCT_PREFETCH_SPANS", "1") or 1)
    except ValueError:
        return 1


def bench_trainer_loop(data, tmp: str, epoch_chunk: int = 1) -> float:
    """The PRODUCT number: Trainer.fit() at parity config — eval,
    best/last checkpointing, resume-state saves, logging, per-epoch
    dispatch all included. Returns samples/sec/chip.

    ``epoch_chunk`` > 1 exercises the multi-epoch-per-dispatch path
    (TrainConfig.epoch_chunk): on a slow control plane the per-epoch
    host round trip dominates this number, and the chunked leg
    quantifies how much of the gap to the fused bench_tpu figure that
    round trip explains."""
    from dct_tpu.config import (
        DataConfig, RunConfig, TrackingConfig, TrainConfig,
    )
    from dct_tpu.tracking.client import LocalTracking
    from dct_tpu.train.trainer import Trainer

    # Chunked leg: TWO uniform spans of K epochs — span 0 absorbs the
    # XLA compile, span 1 is the steady measurement. A remainder span
    # (K' < K) would compile a SECOND program inside the steady window
    # and measure compilation, not throughput.
    epochs = (1 + TIMED_EPOCHS) if epoch_chunk == 1 else 2 * epoch_chunk
    # Honor DCT_PREFETCH_SPANS here even though the config is built
    # directly (not from_env): the record's trainer_gap stanza stamps
    # this knob as the measured run's provenance, and an operator's
    # serial-vs-pipelined A/B must actually measure the mode it reports.
    prefetch = _bench_prefetch_spans()
    cfg = RunConfig(
        data=DataConfig(
            # The serving section reads bench_models/ (the chunk=1 leg's
            # artifacts); the chunked leg writes beside it.
            models_dir=os.path.join(
                tmp,
                "bench_models" if epoch_chunk == 1
                else f"bench_models_ec{epoch_chunk}",
            )
        ),
        train=TrainConfig(
            epochs=epochs, batch_size=BATCH, epoch_chunk=epoch_chunk,
            prefetch_spans=prefetch,
        ),
        tracking=TrackingConfig(experiment="bench"),
    )
    tracker = LocalTracking(
        root=os.path.join(
            tmp,
            "bench_runs" if epoch_chunk == 1
            else f"bench_runs_ec{epoch_chunk}",
        ),
        experiment="bench",
    )
    trainer = Trainer(cfg, tracker=tracker)
    result = trainer.fit(data)
    return result.steady_samples_per_sec_per_chip


# --- Scaled-config MFU ----------------------------------------------------
# Env-overridable so on-chip tuning sweeps need no edits:
#   DCT_SCALED_DMODEL/_DFF/_SEQ/_LAYERS/_HEADS/_BATCH

SCALED = dict(
    d_model=int(os.environ.get("DCT_SCALED_DMODEL", "512")),
    n_heads=int(os.environ.get("DCT_SCALED_HEADS", "8")),
    # 4 layers x batch 32 (was 2 x 16): amortizes per-step dispatch and
    # non-matmul overhead over more MXU work — measured 10.7% MFU at the
    # old size on v5e; the bigger config raises arithmetic intensity at
    # still-trivial HBM footprint.
    n_layers=int(os.environ.get("DCT_SCALED_LAYERS", "4")),
    d_ff=int(os.environ.get("DCT_SCALED_DFF", "2048")),
    seq_len=int(os.environ.get("DCT_SCALED_SEQ", "1024")),
)
SCALED_BATCH = int(os.environ.get("DCT_SCALED_BATCH", "32"))


def _chip_peak_tflops() -> float | None:
    """Peak bf16 TFLOPs per chip (dct_tpu.utils.profiling owns the table;
    override with DCT_PEAK_TFLOPS)."""
    from dct_tpu.utils.profiling import chip_peak_flops

    peak = chip_peak_flops()
    return peak / 1e12 if peak else None


# Shared by the flash-legs deadline gate and the variant-leg loop so the
# deadline_skipped bookkeeping cannot drift from the legs that exist.
# Order = execution priority; "gqa" last (it runs after the loop).
_VARIANT_LEG_NAMES = (
    "causal_flash", "causal_blockwise", "window_flash", "window_blockwise",
    "gqa",
)

# Share of DCT_BENCH_DEADLINE the optional variant legs may consume —
# the rest is reserved for the MoE/serving/dataplane sections behind
# them (one constant so the two gate sites cannot drift).
_VARIANT_LEG_BUDGET = 0.55

# Set by main(): sections stream per-leg values into the live record via
# _leg() the moment they are measured, so a failure LATER in a section
# cannot lose legs that already ran.
_LIVE_RECORD: dict | None = None


def _leg(key: str, value) -> None:
    print(f"[bench] leg {key}={value}", file=sys.stderr, flush=True)
    if _LIVE_RECORD is not None:
        _LIVE_RECORD.setdefault("scaled_legs", {})[key] = value
        _flush_partial(_LIVE_RECORD)


def _time_step(step_fn, state, args, *, n: int = 8) -> float:
    """Seconds per optimizer step, post-compilation."""
    import jax

    st = state
    for _ in range(2):  # warmup (compile + cache)
        st, _m = step_fn(st, *args)
    jax.block_until_ready(st.params)
    t0 = time.perf_counter()
    for _ in range(n):
        st, _m = step_fn(st, *args)
    jax.block_until_ready(st.params)
    return (time.perf_counter() - t0) / n


def _time_scanned_step(epoch_step, state, stacks, *, scan_len: int,
                       n: int = 4) -> float:
    """Seconds per optimizer step measured through a ``lax.scan`` of
    ``scan_len`` steps in ONE dispatch — how the trainer actually runs
    an epoch (train/steps.py:make_epoch_train_step). Per-dispatch timing
    includes the host's dispatch cost; this measures steady-state
    compute throughput."""
    import jax

    for _ in range(2):  # warmup (compile + cache)
        st, _losses = epoch_step(state, *stacks)
    jax.block_until_ready(st.params)
    t0 = time.perf_counter()
    for _ in range(n):
        st, _losses = epoch_step(state, *stacks)
    jax.block_until_ready(st.params)
    return (time.perf_counter() - t0) / (n * scan_len)


def bench_roofline() -> dict:
    """Cost-model MFU of a small train-scan (ISSUE 14).

    A small transformer train-scan is compiled on the backend JAX
    selected, its analytic FLOPs/bytes read from XLA's own cost model
    (``compiled.cost_analysis()``), its steady step time measured, and
    MFU = flops / seconds / peak computed against the device table's
    peak. Off the TPU there is no peak and no ``mfu`` key
    (``peak_source: not_measured``) — nothing stands in for it. This
    leg is the sentinel's `program_mfu` series; the record's headline
    ``mfu`` is the scaled stanza's, never this one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dct_tpu.config import ModelConfig
    from dct_tpu.models.registry import get_model
    from dct_tpu.observability import roofline as _rf
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import make_epoch_train_step

    shape = dict(
        d_model=128, n_heads=4, n_layers=2, d_ff=256, seq_len=64,
    )
    batch, scan_len, input_dim = 8, 4, 5
    cfg = ModelConfig(name="weather_transformer", **shape)
    model = get_model(
        cfg, input_dim=input_dim, compute_dtype=jnp.float32
    )
    state = create_train_state(
        model, input_dim=input_dim, lr=1e-3, seed=0,
        example_shape=(1, shape["seq_len"], input_dim),
    )
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.standard_normal(
        (scan_len, batch, shape["seq_len"], input_dim)
    ).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 2, (scan_len, batch)), jnp.int32)
    ws = jnp.ones((scan_len, batch), jnp.float32)

    epoch_step = make_epoch_train_step(donate=False)
    compiled = epoch_step.lower(state, xs, ys, ws).compile()
    cost = _rf.analyze_compiled(compiled) or {}
    st, losses = compiled(state, xs, ys, ws)
    jax.block_until_ready(losses)
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        st, losses = compiled(state, xs, ys, ws)
        jax.block_until_ready(losses)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)

    peak, peak_source = _rf.resolve_peak_flops()
    hbm = _rf.chip_hbm_bytes_per_sec()
    flops = cost.get("flops")
    ba = cost.get("bytes_accessed")
    out = {
        "config": {**shape, "batch": batch, "scan_len": scan_len},
        "step_time_ms": round(best / scan_len * 1e3, 3),
        "flops_per_dispatch": flops,
        "peak_source": peak_source,
    }
    if peak:
        out["peak_tflops"] = round(peak / 1e12, 3)
    if cost.get("hbm_peak_bytes") is not None:
        out["hbm_peak_bytes"] = cost["hbm_peak_bytes"]
    if flops and ba:
        intensity = flops / ba
        out["arithmetic_intensity"] = round(intensity, 2)
        out["bound"] = _rf.classify(
            intensity, (peak / hbm) if peak and hbm else None
        )
    if flops and peak and best:
        out["mfu"] = round(flops / best / peak, 6)
    return out


def bench_low_precision(tmp: str) -> dict:
    """Low-precision end-to-end (ISSUE 20): the int8/bf16 story as two
    tracked A/Bs plus the gate safety net, every round.

    - **Serving**: the int8 weight-quantized and bf16 numpy twins vs the
      f32 twin at serving width — single-row p50, batch-64 throughput,
      and the max-abs-prob delta. All three run through the micro-
      batcher's ``rows_mm`` row-invariant hook; the int8 path's
      integer-exact GEMM (runtime.QuantTensor) collapses the per-row
      loop into ONE quantized GEMM while keeping bit-identical rows,
      which is where the batched speedup comes from. The sentinel's
      ``quant_serving_speedup`` series is the batch-64 throughput ratio.
    - **Training**: one transformer train step, f32 vs
      ``DCT_DTYPE_RULES='.*=bf16'`` (f32 master weights, bf16 compute)
      at matched config — samples/s, cost-model bytes_accessed and MFU
      per variant. Bytes come from the LOWERED program (the roofline
      plane's pre-backend capture): the CPU rig's backend wraps every
      bf16 dot in f32 converts (no native bf16 FMA), so the compiled
      CPU cost model would charge bf16 MORE bytes — the lowered HLO is
      the dtype-honest accounting and matches what a native-bf16 chip
      executes. The sentinel's ``bf16_bytes_ratio`` series is
      bf16/f32 bytes (down = better).
    - **Gates**: a quantized challenger built from this run's own
      trained checkpoint walks the PR-4 promotion gate against its f32
      champion (clean -> promote), then again with a corrupted scale
      column (-> blocked) — the accuracy safety net proven on every
      record.
    """
    import numpy as np

    from dct_tpu.serving.quant import quantize_weights
    from dct_tpu.serving.runtime import (
        assemble_weights, forward_numpy, rows_mm, softmax_numpy,
    )

    out: dict = {}
    rng = np.random.default_rng(0)

    # --- serving twins: f32 vs int8 vs bf16 at serving width ---------
    # 1024-wide so the weight matrix (4 MB in f32) outruns L2: the f32
    # rows_mm loop re-reads it per row while the int8 GEMM streams it
    # once as int8 — the regime the quantized scorer is FOR. Fan-in
    # scaling keeps logits in a realistic range (saturated random
    # logits would understate the prob delta).
    input_dim, hidden, classes = 256, 1024, 2
    def _fan_in(n_in, n_out):
        w = rng.standard_normal((n_in, n_out)) / np.sqrt(n_in)
        return w.astype(np.float32)

    weights = {
        "w0": _fan_in(input_dim, hidden),
        "b0": np.zeros(hidden, np.float32),
        "w1": _fan_in(hidden, hidden),
        "b1": np.zeros(hidden, np.float32),
        "w2": _fan_in(hidden, classes),
        "b2": np.zeros(classes, np.float32),
    }
    meta = {"model": "weather_mlp", "input_dim": input_dim}
    variants = {"f32": weights}
    for dt in ("int8", "bf16"):
        flat, _qmeta = quantize_weights(weights, meta, dt)
        variants[dt] = assemble_weights(flat)

    x1 = rng.standard_normal((1, input_dim)).astype(np.float32)
    x64 = rng.standard_normal((64, input_dim)).astype(np.float32)
    ref64 = softmax_numpy(forward_numpy(weights, meta, x64, mm=rows_mm))
    serving: dict = {}
    for name, w in variants.items():
        for _ in range(5):  # warmup
            forward_numpy(w, meta, x64, mm=rows_mm)
        p50 = []
        for _ in range(50):
            t0 = time.perf_counter()
            forward_numpy(w, meta, x1, mm=rows_mm)
            p50.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        reps = 30
        for _ in range(reps):
            probs = softmax_numpy(forward_numpy(w, meta, x64, mm=rows_mm))
        dt_batch = (time.perf_counter() - t0) / reps
        serving[name] = {
            "p50_ms": round(float(np.median(p50)) * 1e3, 4),
            "batch64_rows_per_s": round(64 / dt_batch, 1),
            "max_abs_prob_delta": round(
                float(np.abs(probs - ref64).max()), 6
            ),
        }
    f32_rps = serving["f32"]["batch64_rows_per_s"]
    for name in ("int8", "bf16"):
        serving[name]["speedup_batch64"] = round(
            serving[name]["batch64_rows_per_s"] / f32_rps, 2
        )
    out["serving"] = serving
    out["quant_serving_speedup"] = serving["int8"]["speedup_batch64"]
    _leg("quant_serving_speedup", out["quant_serving_speedup"])

    # --- training A/B: f32 vs bf16 dtype rules at matched config -----
    out["train"] = _lowprec_train_ab()
    if out["train"].get("bf16_bytes_ratio") is not None:
        out["bf16_bytes_ratio"] = out["train"]["bf16_bytes_ratio"]
        _leg("bf16_bytes_ratio", out["bf16_bytes_ratio"])

    # --- gate parity: quantized challenger through the PR-4 gate -----
    try:
        out["gate"] = _lowprec_gate_parity(tmp)
    except Exception as e:  # noqa: BLE001 — the A/Bs above must land
        print(
            f"[bench] low_precision gate leg FAILED "
            f"({type(e).__name__}: {e})",
            file=sys.stderr, flush=True,
        )
        out["gate"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    return out


def _lowprec_train_ab() -> dict:
    """One transformer train step, f32 vs bf16 dtype rules, matched
    config: samples/s + lowered-cost-model bytes/flops/MFU per variant.
    FFN-dominated shape (d_ff=8*d_model, short seq): the attention
    softmax stays f32 by the numerics contract (ops/attention.py
    computes scores with preferred_element_type=f32), so an
    attention-dominated shape would understate the rules' effect."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dct_tpu.config import ModelConfig
    from dct_tpu.models.registry import get_model
    from dct_tpu.observability import roofline as _rf
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import make_train_step

    shape = dict(d_model=128, n_heads=4, n_layers=2, d_ff=1024, seq_len=64)
    batch, input_dim = 64, 5
    xrng = np.random.default_rng(0)
    x = jnp.asarray(xrng.standard_normal(
        (batch, shape["seq_len"], input_dim)
    ).astype(np.float32))
    y = jnp.asarray(xrng.integers(0, 2, (batch,)), jnp.int32)
    w = jnp.ones((batch,), jnp.float32)
    peak, peak_source = _rf.resolve_peak_flops()

    def run_variant(rules: str | None) -> dict:
        saved = os.environ.get("DCT_DTYPE_RULES")
        try:
            if rules is None:
                os.environ.pop("DCT_DTYPE_RULES", None)
            else:
                os.environ["DCT_DTYPE_RULES"] = rules
            cfg = ModelConfig(name="weather_transformer", **shape)
            model = get_model(
                cfg, input_dim=input_dim,
                compute_dtype=jnp.bfloat16 if rules else jnp.float32,
            )
            state = create_train_state(
                model, input_dim=input_dim, lr=1e-3, seed=0,
                example_shape=(1, shape["seq_len"], input_dim),
            )
            step = make_train_step(donate=False)
            # The rules are read at TRACE time (steps.py casts inside
            # the jitted body), so lower() must happen inside the env
            # window.
            lowered = step.lower(state, x, y, w)
            cost = _rf.analyze_lowered(lowered) or {}
            compiled = lowered.compile()
        finally:
            if saved is None:
                os.environ.pop("DCT_DTYPE_RULES", None)
            else:
                os.environ["DCT_DTYPE_RULES"] = saved
        st, metrics = compiled(state, x, y, w)
        jax.block_until_ready(metrics["train_loss"])
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            st, metrics = compiled(st, x, y, w)
            jax.block_until_ready(metrics["train_loss"])
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        # Master-weight contract, asserted where it is measured: the
        # bf16 variant's params and optimizer state stay dense f32.
        pd = {str(l.dtype) for l in jax.tree.leaves(st.params)}
        if pd != {"float32"}:
            raise RuntimeError(f"master weights leaked off f32: {pd}")
        flops = cost.get("flops")
        res = {
            "samples_per_s": round(batch / best, 1),
            "bytes_accessed": cost.get("bytes_accessed"),
            "flops": flops,
        }
        if flops and peak and best:
            res["mfu"] = round(flops / best / peak, 6)
        return res

    f32 = run_variant(None)
    bf16 = run_variant(".*=bf16")
    out = {
        "config": {**shape, "batch": batch},
        "peak_source": peak_source,
        "f32": f32,
        "bf16_rules": bf16,
    }
    if f32.get("bytes_accessed") and bf16.get("bytes_accessed"):
        out["bf16_bytes_ratio"] = round(
            bf16["bytes_accessed"] / f32["bytes_accessed"], 3
        )
        out["bytes_reduction_pct"] = round(
            100 * (1 - out["bf16_bytes_ratio"]), 1
        )
    if f32.get("samples_per_s") and bf16.get("samples_per_s"):
        out["bf16_sps_ratio"] = round(
            bf16["samples_per_s"] / f32["samples_per_s"], 2
        )
    if f32.get("mfu") and bf16.get("mfu"):
        out["bf16_mfu_delta"] = round(bf16["mfu"] - f32["mfu"], 6)
    return out


def _lowprec_gate_parity(tmp: str) -> dict:
    """The quantized challenger through the real promotion gate, twice:
    clean (must promote) and with one scale column corrupted (must be
    blocked). Uses this bench run's own trained checkpoint and
    processed split — the exact artifacts a production rollout would
    gate. The gate's regression tolerance is widened to the documented
    quant prob bound (SERVING.md: a quantized challenger trades <=
    prob_bound of per-example accuracy for the speedup; the gate's job
    here is catching BROKEN quantization, not the documented rounding)."""
    import numpy as np

    from dct_tpu.config import EvaluationConfig
    from dct_tpu.evaluation.gates import PromotionGate
    from dct_tpu.serving.quant import prob_bound, quantize_package
    from dct_tpu.serving.score_gen import generate_score_package

    ckpts = sorted(
        f for f in os.listdir(os.path.join(tmp, "bench_models"))
        if f.endswith(".ckpt")
    )
    champ = os.path.join(tmp, "lowprec_champion")
    chall = os.path.join(tmp, "lowprec_challenger")
    generate_score_package(
        os.path.join(tmp, "bench_models", ckpts[0]), champ
    )
    quantize_package(champ, chall, dtype="int8")

    cfg = EvaluationConfig.from_env()
    cfg.max_regression = max(cfg.max_regression, prob_bound())
    gate = PromotionGate(cfg, processed_dir=os.path.join(tmp, "processed"))
    clean = gate.evaluate(
        challenger_dir=chall, champion_dir=champ, stage="shadow"
    )

    # Corrupt ONE int8 scale column (x64): the challenger now scores
    # garbage on that output channel — the gate must block it.
    npz_path = os.path.join(chall, "model.npz")
    with np.load(npz_path) as z:
        flat = {k: z[k] for k in z.files}
    scale_key = next(k for k in sorted(flat) if k.endswith("::scale"))
    flat[scale_key] = flat[scale_key] * np.float32(64.0)
    np.savez(npz_path, **flat)
    # Bust the package-cached eval evidence: the corrupted npz must be
    # re-scored, not read from the clean run's cache.
    cache = os.path.join(chall, "eval_report.json")
    if os.path.exists(cache):
        os.remove(cache)
    corrupted = gate.evaluate(
        challenger_dir=chall, champion_dir=champ, stage="shadow"
    )
    return {
        "clean": clean.decision,
        "corrupted": corrupted.decision,
        "parity": bool(clean.promoted and not corrupted.promoted),
    }


def bench_scaled_transformer() -> dict:
    """MXU-relevant transformer: step time, MFU, flash vs blockwise.

    MFU is computed from the SCANNED step time (DCT_SCALED_SCAN steps per
    dispatch, default 16): the trainer's product path runs whole epochs as
    one dispatch, so steady-state compute throughput is the honest basis.
    The per-dispatch step time is also reported — the gap between the two
    is the host dispatch cost at this step size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dct_tpu.config import MeshConfig, ModelConfig
    from dct_tpu.models.registry import get_model
    from dct_tpu.ops.attention import (
        blockwise_attention, flash_interpret_mode,
    )
    from dct_tpu.parallel.mesh import (
        make_global_batch, make_global_epoch, make_mesh,
    )
    from dct_tpu.parallel.sharding_rules import shard_state_with_rules
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import make_epoch_train_step, make_train_step

    on_tpu = jax.default_backend() == "tpu"
    scaled = dict(SCALED)
    batch = SCALED_BATCH
    # 16 steps/dispatch: at the default config (~3.3 TFLOP/step) the
    # per-dispatch host cost is a small share of the timed region, so
    # mfu measures the MXU, not the host loop.
    scan_len = max(1, int(os.environ.get("DCT_SCALED_SCAN", "16")))
    if not on_tpu:  # CPU sanity runs: keep it minutes, not hours
        scaled.update(d_model=128, d_ff=256, seq_len=256, n_layers=2)
        batch = 4
        scan_len = min(scan_len, 2)

    mesh = make_mesh(MeshConfig())
    input_dim = 5
    # DCT_REMAT participates in the sweep: at large DCT_SCALED_SEQ/LAYERS
    # the non-remat step can exceed HBM, and the remat-vs-not step-time
    # delta on the same config quantifies the HBM-for-FLOPs trade.
    # Parsed by the config system's own bool parser so bench and trainer
    # can never disagree on what counts as "on".
    from dct_tpu.config import _env

    remat = _env("DCT_REMAT", False, bool)
    cfg = ModelConfig(name="weather_transformer", remat=remat, **scaled)

    def build(attn_fn):
        model = get_model(
            cfg, input_dim=input_dim, compute_dtype=jnp.bfloat16,
            attn_fn=attn_fn,
        )
        return model

    def blockwise_fn(q, k, v):
        return blockwise_attention(q, k, v, block_size=min(512, q.shape[-2]))

    model_bw = build(blockwise_fn)
    state = create_train_state(
        model_bw, input_dim=input_dim, lr=1e-3, seed=0,
        example_shape=(1, scaled["seq_len"], input_dim),
    )
    state = shard_state_with_rules(state, mesh)

    rng = np.random.default_rng(0)
    xs = rng.standard_normal(
        (scan_len, batch, scaled["seq_len"], input_dim)
    ).astype(np.float32)
    ys = rng.integers(0, 2, (scan_len, batch)).astype(np.int32)
    ws = np.ones((scan_len, batch), np.float32)
    stacks = make_global_epoch(mesh, xs, ys, ws)
    gx, gy, gw = make_global_batch(mesh, xs[0], ys[0], ws[0])

    epoch_step = make_epoch_train_step(donate=False)
    t_blockwise = _time_scanned_step(
        epoch_step, state, stacks, scan_len=scan_len
    )
    _leg("attn_blockwise_ms", round(t_blockwise * 1e3, 2))

    t_flash = None
    state_fl = None
    causal = {}
    t = scaled["seq_len"]
    # The kernels pick their tiles from the shape
    # (pallas_attention.flash_tiles), as the product path does.
    flash_fits = t % 128 == 0
    if flash_interpret_mode() is False and not flash_fits:
        # A sequence the kernel cannot tile must not kill the whole
        # bench record.
        print(
            f"[bench] SKIP flash legs: seq_len {t} is no multiple of 128",
            file=sys.stderr, flush=True,
        )
    run_flash = flash_interpret_mode() is False and flash_fits
    if run_flash and _over_deadline("scaled:flash_legs"):
        run_flash = False
        causal["deadline_skipped"] = ["flash"] + list(_VARIANT_LEG_NAMES)
    if run_flash:
        from dct_tpu.ops.pallas_attention import flash_attention

        def flash_fn(q, k, v):
            return flash_attention(q, k, v)

        # A Mosaic compile/runtime failure in a flash leg must degrade
        # to the blockwise-only record, not kill the section — the
        # driver's end-of-round run is this code's first time on the
        # chip, and `mfu` must land regardless.
        try:
            state_fl = state.replace(apply_fn=build(flash_fn).apply)
            t_flash = _time_scanned_step(
                epoch_step, state_fl, stacks, scan_len=scan_len
            )
            _leg("attn_flash_ms", round(t_flash * 1e3, 2))
        except Exception as e:  # noqa: BLE001
            state_fl = None
            causal["attn_flash_error"] = f"{type(e).__name__}: {e}"
            print(
                f"[bench] flash leg FAILED ({type(e).__name__}: {e}) — "
                "continuing with blockwise only",
                file=sys.stderr, flush=True,
            )

        # CAUSAL variants: the flash kernel skips above-diagonal tiles
        # (and elides their KV DMA) — roughly half the attention work —
        # while the XLA blockwise path computes every block and masks.
        def flash_causal(q, k, v):
            return flash_attention(q, k, v, causal=True)

        def blockwise_causal(q, k, v):
            return blockwise_attention(
                q, k, v, block_size=min(512, q.shape[-2]), causal=True
            )

        # WINDOWED variants (DCT_SCALED_WINDOW, default seq_len/4): the
        # in-kernel band skips every tile behind the window — compute AND
        # DMA — so flash-window vs flash-causal quantifies the
        # O(T*window)-vs-O(T^2/2) claim on hardware, and flash-window vs
        # blockwise-window shows the kernel's edge over the masked XLA
        # scan (which pays every block and masks).
        win = int(os.environ.get("DCT_SCALED_WINDOW", str(max(1, t // 4))))

        def flash_window(q, k, v):
            return flash_attention(q, k, v, causal=True, window=win)

        def blockwise_window(q, k, v):
            return blockwise_attention(
                q, k, v, block_size=min(512, q.shape[-2]), causal=True,
                window=win,
            )

        causal["attn_window"] = win
        # Per-leg deadline gates: each leg compiles its own programs,
        # and a slow one can run past DCT_BENCH_DEADLINE from INSIDE the
        # section, where the between-sections check can't see it. A
        # skipped leg is an ABSENT key, named in deadline_skipped
        # so absence can't read as a measurement bug; the streamed legs
        # above already secured everything measured so far.
        variant_legs = list(zip(
            _VARIANT_LEG_NAMES[:-1],
            (flash_causal, blockwise_causal, flash_window, blockwise_window),
        ))
        for i, (name, fn) in enumerate(variant_legs):
            # 55%: the causal/window variants are the first to yield —
            # they re-measure the same kernels the mandatory legs above
            # already timed, while MoE/serving behind them have no other
            # source in the record.
            if _over_deadline(f"scaled:{name}", frac=_VARIANT_LEG_BUDGET):
                causal["deadline_skipped"] = list(_VARIANT_LEG_NAMES[i:])
                break
            try:
                st = state.replace(apply_fn=build(fn).apply)
                causal[f"attn_{name}_ms"] = round(
                    _time_scanned_step(
                        epoch_step, st, stacks, scan_len=scan_len
                    ) * 1e3, 2,
                )
                _leg(f"attn_{name}_ms", causal[f"attn_{name}_ms"])
            except Exception as e:  # noqa: BLE001
                causal[f"attn_{name}_error"] = (
                    f"{type(e).__name__}: {e}"
                )
                print(
                    f"[bench] {name} leg FAILED "
                    f"({type(e).__name__}: {e})",
                    file=sys.stderr, flush=True,
                )

        # GQA op-level A/B at the scaled attention shape: grouped KV
        # (n_heads/4 kv heads) vs full MHA through the causal kernel —
        # quantifies the KV-HBM-read reduction the divided index maps
        # deliver; attention-only timing because GQA changes the param
        # tree (the train-step legs above share one state). Runs after
        # the causal/window legs: those carry the headline flash-vs-
        # blockwise claims, so under deadline pressure they go first.
        if _over_deadline("scaled:gqa", frac=_VARIANT_LEG_BUDGET):
            skipped = causal.setdefault("deadline_skipped", [])
            if "gqa" not in skipped:
                skipped.append("gqa")
        else:
            try:
                import jax as _jax

                heads = scaled["n_heads"]
                kvh = max(1, heads // 4)
                dh = scaled["d_model"] // heads
                rngk = np.random.default_rng(7)
                shp = lambda h_: (batch, h_, t, dh)
                qa = jnp.asarray(
                    rngk.standard_normal(shp(heads)), jnp.bfloat16
                )
                ka = jnp.asarray(
                    rngk.standard_normal(shp(kvh)), jnp.bfloat16
                )
                va = jnp.asarray(
                    rngk.standard_normal(shp(kvh)), jnp.bfloat16
                )
                kf = jnp.repeat(ka, heads // kvh, axis=1)
                vf = jnp.repeat(va, heads // kvh, axis=1)

                def _time_op(fn, *args, n=10):
                    out = fn(*args)
                    _jax.block_until_ready(out)
                    t0 = time.perf_counter()
                    for _ in range(n):
                        out = fn(*args)
                    _jax.block_until_ready(out)
                    return (time.perf_counter() - t0) / n

                fl = _jax.jit(
                    lambda q_, k_, v_: flash_attention(
                        q_, k_, v_, causal=True
                    )
                )
                t_mha = _time_op(fl, qa, kf, vf)
                t_gqa = _time_op(fl, qa, ka, va)
                causal["attn_gqa"] = {
                    "kv_heads": kvh,
                    "mha_ms": round(t_mha * 1e3, 3),
                    "gqa_ms": round(t_gqa * 1e3, 3),
                    "speedup": round(t_mha / t_gqa, 2),
                }
                _leg("attn_gqa", causal["attn_gqa"])
            except Exception as e:  # noqa: BLE001
                causal["attn_gqa"] = {"error": f"{type(e).__name__}: {e}"}

    from dct_tpu.utils.profiling import transformer_train_flops

    t_best = min(x for x in (t_blockwise, t_flash) if x is not None)
    # Per-dispatch step time with the SAME attention path that produced
    # t_best, so (step_time_dispatch_ms - step_time_ms) isolates the
    # control-plane dispatch cost rather than a kernel delta.
    best_state = (
        state_fl if (t_flash is not None and t_flash <= t_blockwise) else state
    )
    step = make_train_step(donate=False)
    try:
        t_dispatch = _time_step(step, best_state, (gx, gy, gw))
    except Exception as e:  # noqa: BLE001 — a failure here must not
        # discard the scanned legs above (they carry the MFU number)
        t_dispatch = None
        print(
            f"[bench] dispatch-timing leg FAILED ({type(e).__name__}: {e})",
            file=sys.stderr, flush=True,
        )
    flops = transformer_train_flops(
        batch=batch, input_dim=input_dim, **scaled
    )
    peak = _chip_peak_tflops() if on_tpu else None
    out = {
        "config": {
            **scaled, "batch": batch, "dtype": "bfloat16",
            "scan_len": scan_len, "remat": remat,
        },
        "step_time_ms": round(t_best * 1e3, 2),
        "step_time_dispatch_ms": (
            round(t_dispatch * 1e3, 2) if t_dispatch is not None else None
        ),
        "flops_per_step": flops,
        "tflops_per_sec": round(flops / t_best / 1e12, 2),
        "attn_blockwise_ms": round(t_blockwise * 1e3, 2),
        "attn_flash_ms": round(t_flash * 1e3, 2) if t_flash else None,
        "samples_per_sec_per_chip": round(batch / t_best / mesh.size, 1),
        **causal,
    }
    if peak:
        out["chip_peak_bf16_tflops"] = peak
        out["mfu"] = round(flops / t_best / (peak * 1e12), 4)
    return out


def bench_scaled_moe() -> dict:
    """Sorted/segment MoE dispatch vs the one-hot einsum engine at a size
    where the [N,E,C] dispatch tensors dominate the einsum path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dct_tpu.config import MeshConfig, ModelConfig
    from dct_tpu.models.registry import get_model
    from dct_tpu.parallel.mesh import make_global_batch, make_mesh
    from dct_tpu.parallel.sharding_rules import shard_state_with_rules
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import make_train_step

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        # E=32 puts the einsum engine's [N,E,C] dispatch tensors well past
        # the FFN cost (the regime the sorted engine exists for).
        size = dict(
            d_model=512, n_heads=8, n_layers=2, d_ff=1024, seq_len=512,
            n_experts=32,
        )
        batch = 8
    else:
        size = dict(
            d_model=64, n_heads=4, n_layers=1, d_ff=128, seq_len=64,
            n_experts=4,
        )
        batch = 4

    mesh = make_mesh(MeshConfig())
    input_dim = 5
    rng = np.random.default_rng(1)
    x = rng.standard_normal((batch, size["seq_len"], input_dim)).astype(
        np.float32
    )
    y = rng.integers(0, 2, batch).astype(np.int32)
    w = np.ones(batch, np.float32)
    gx, gy, gw = make_global_batch(mesh, x, y, w)
    step = make_train_step(donate=False)

    times = {}
    state_sorted = None
    skipped = []
    engines = ("sorted", "einsum")
    for i, engine in enumerate(engines):
        if _over_deadline(f"moe:{engine}"):
            skipped = list(engines[i:])
            break
        cfg = ModelConfig(name="weather_moe", moe_dispatch=engine, **size)
        model = get_model(
            cfg, input_dim=input_dim, compute_dtype=jnp.bfloat16, mesh=mesh
        )
        if state_sorted is None:
            state_sorted = create_train_state(
                model, input_dim=input_dim, lr=1e-3, seed=0,
                example_shape=(1, size["seq_len"], input_dim),
            )
            state_sorted = shard_state_with_rules(state_sorted, mesh)
        st = state_sorted.replace(apply_fn=model.apply)
        times[engine] = _time_step(step, st, (gx, gy, gw), n=5)
        _leg(f"moe_{engine}_ms", round(times[engine] * 1e3, 2))

    out = {"config": {**size, "batch": batch, "dtype": "bfloat16"}}
    for engine in times:
        out[f"{engine}_ms"] = round(times[engine] * 1e3, 2)
    if "sorted" in times and "einsum" in times:
        out["sorted_speedup"] = round(times["einsum"] / times["sorted"], 2)
    if skipped:
        out["deadline_skipped"] = skipped
    return out


def bench_host_dataplane() -> dict | None:
    """Native C++ data plane vs pure-numpy host gathers — the input
    pipeline work that runs on the prefetch thread (CPU-side regardless
    of accelerator). Returns None when the native library is absent
    (the numpy fallback is then the product path)."""
    import numpy as np

    from dct_tpu import native

    if not native.available():
        return None

    rng = np.random.default_rng(0)
    base = rng.standard_normal((200_000, 5)).astype(np.float32)
    idx = rng.integers(0, len(base), 65_536)
    starts = rng.integers(0, len(base) - 64, 8_192)

    def timeit(fn, n=20):
        fn()
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t0) / n

    t_rows_native = timeit(lambda: native.gather_rows(base, idx))
    t_rows_numpy = timeit(lambda: base[idx])
    t_win_native = timeit(lambda: native.gather_windows(base, starts, 64))
    t_win_numpy = timeit(
        lambda: np.stack([base[s : s + 64] for s in starts])
    )
    return {
        "rows_native_ms": round(t_rows_native * 1e3, 3),
        "rows_numpy_ms": round(t_rows_numpy * 1e3, 3),
        "rows_speedup": round(t_rows_numpy / t_rows_native, 2),
        "windows_native_ms": round(t_win_native * 1e3, 3),
        "windows_numpy_ms": round(t_win_numpy * 1e3, 3),
        "windows_speedup": round(t_win_numpy / t_win_native, 2),
    }


def bench_serving(tmp: str) -> dict:
    """Inference latency of the deployed scoring path vs the reference's.

    Our deploy package is framework-free numpy (serving/score_gen.py);
    the reference's generated score.py runs a torch CPU forward inside
    the Azure container (dags/azure_manual_deploy.py:116-124). Both are
    measured here on the same host, same weights-shape model, single-row
    (the endpoint request shape) and batch-64 payloads."""
    import numpy as np
    import torch

    from dct_tpu.serving.runtime import score_payload
    from dct_tpu.serving.score_gen import weights_from_checkpoint

    ckpts = [
        f for f in os.listdir(os.path.join(tmp, "bench_models"))
        if f.endswith(".ckpt")
    ]
    weights, meta = weights_from_checkpoint(
        os.path.join(tmp, "bench_models", sorted(ckpts)[0])
    )

    tmodel = torch.nn.Sequential(
        torch.nn.Linear(int(meta["input_dim"]), int(meta["hidden_dim"])),
        torch.nn.ReLU(),
        torch.nn.Dropout(0.2),
        torch.nn.Linear(int(meta["hidden_dim"]), int(meta["num_classes"])),
    )
    tmodel.eval()

    rng = np.random.default_rng(0)
    out = {}
    for label, bsz in (("single_row", 1), ("batch64", 64)):
        x = rng.standard_normal((bsz, int(meta["input_dim"])))
        payload = {"data": x.tolist()}

        # Both paths pay the per-request list->tensor conversion, exactly
        # like the serving containers do (ours: score_payload's asarray;
        # reference score.py: torch.tensor(data) per run() call).
        def t_ours():
            score_payload(weights, meta, payload["data"])

        def t_torch():
            with torch.no_grad():
                xt = torch.tensor(payload["data"], dtype=torch.float32)
                torch.softmax(tmodel(xt), dim=1).numpy()

        times = {}
        for name, fn in (("ours", t_ours), ("torch", t_torch)):
            for _ in range(20):
                fn()
            samples = []
            for _ in range(200):
                t0 = time.perf_counter()
                fn()
                samples.append(time.perf_counter() - t0)
            times[name] = float(np.median(samples) * 1e3)
        out[label] = {
            "numpy_p50_ms": round(times["ours"], 4),
            "torch_p50_ms": round(times["torch"], 4),
            "speedup": round(times["torch"] / times["ours"], 2),
        }
    return out


def bench_serving_load(tmp: str) -> dict:
    """The serving tier under traffic (ISSUE 7): a micro-batched HTTP
    server over the bench checkpoint, closed-loop load generation at the
    configured concurrency levels (>= 2), qps + p50/p99 per level, the
    saturation knee, and two throughput ratios:

    - ``batched_over_single`` — saturated endpoint qps over the
      concurrency-1 qps, HTTP transport included. Bounded by this
      host's cores (the loadgen client shares them with the server;
      ``processes`` reports the SO_REUSEPORT pool size used).
    - ``score_batched_over_single`` — rows/s of one merged micro-batch
      flush vs the same requests dispatched one by one through the same
      scorer: the compute-amortization factor batching buys, transport-
      independent and host-portable.

    ``parity`` asserts the tentpole's core invariant right in the
    record: a batched HTTP response is bit-identical to the sequential
    single-row reference."""
    import numpy as np

    from dct_tpu.config import ServingConfig
    from dct_tpu.serving import loadgen
    from dct_tpu.serving.batching import score_rows_invariant
    from dct_tpu.serving.runtime import score_payload
    from dct_tpu.serving.score_gen import weights_from_checkpoint
    from dct_tpu.serving.server import ServerPool, make_server_from_weights

    ckpts = [
        f for f in os.listdir(os.path.join(tmp, "bench_models"))
        if f.endswith(".ckpt")
    ]
    weights, meta = weights_from_checkpoint(
        os.path.join(tmp, "bench_models", sorted(ckpts)[0])
    )
    cfg = ServingConfig.from_env()
    rng = np.random.default_rng(0)
    row = rng.standard_normal((1, int(meta["input_dim"]))).round(4)
    body = json.dumps({"data": row.tolist()}).encode()

    pool = ServerPool(
        lambda h, p, reuse_port: make_server_from_weights(
            weights, meta, host=h, port=p, serving=cfg,
            reuse_port=reuse_port,
        ),
        processes=cfg.processes, host="127.0.0.1",
    )
    try:
        levels = sorted(set(cfg.concurrency_levels()) | {1})
        sweep = loadgen.sweep_closed_loop(
            "127.0.0.1", pool.port, body, levels=levels,
            requests_per_level=cfg.loadgen_requests, duration_s=30.0,
        )
        base = next(
            r for r in sweep["levels"] if r["concurrency"] == 1
        )
        out = {"processes": cfg.processes, **sweep}
        out["baseline_qps"] = base["qps"]
        out["batched_over_single"] = (
            round(sweep["saturated_qps"] / base["qps"], 2)
            if base["qps"] else None
        )
        _leg("serving_load_qps", out["saturated_qps"])
        if cfg.loadgen_qps > 0:
            out["open_loop"] = loadgen.run_open_loop(
                "127.0.0.1", pool.port, body, qps=cfg.loadgen_qps,
                duration_s=cfg.loadgen_duration_s,
            )

        # Parity, proven against the LIVE server: the batched response's
        # bits equal the sequential single-row reference while the sweep
        # traffic above has exercised real merging.
        client = loadgen._Client("127.0.0.1", pool.port)
        try:
            status, resp = client.post(body)
        finally:
            client.close()
        served = np.asarray(
            json.loads(resp)["probabilities"], np.float32
        )
        reference = np.asarray(
            score_payload(weights, meta, row.tolist())["probabilities"],
            np.float32,
        )
        out["parity"] = bool(
            status == 200
            and served.shape == reference.shape
            and (served == reference).all()
        )
    finally:
        pool.close()

    # Transport-free amortization: one merged flush of 64 single-row
    # requests vs the same 64 dispatched sequentially.
    arrays = [
        rng.standard_normal((1, int(meta["input_dim"])))
        .astype(np.float32)
        for _ in range(64)
    ]

    def _timeit(fn, n=50):
        fn()
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t0) / n

    t_batched = _timeit(lambda: score_rows_invariant(weights, meta, arrays))
    t_single = _timeit(
        lambda: [score_rows_invariant(weights, meta, [a]) for a in arrays],
        n=10,
    )
    out["score_batched_over_single"] = round(t_single / t_batched, 2)

    # Metrics-plane cost bound (ISSUE 8 acceptance): the hot-path price
    # of snapshot publishing, measured — same in-process server, same
    # closed-loop traffic, with the plane off vs armed at the DEFAULT
    # publish throttle (the shipped config: one clock read per request
    # inside the window, a snapshot write per DCT_METRICS_PUBLISH_S).
    def _p50_with_env(metrics_dir: str | None) -> float:
        saved = {"DCT_METRICS_DIR": os.environ.get("DCT_METRICS_DIR")}
        try:
            if metrics_dir is None:
                os.environ["DCT_METRICS_DIR"] = ""
            else:
                os.environ["DCT_METRICS_DIR"] = metrics_dir
            with ServerPool(
                lambda h, p, reuse_port: make_server_from_weights(
                    weights, meta, host=h, port=p, serving=cfg,
                    reuse_port=reuse_port,
                ),
                processes=1, host="127.0.0.1",
            ) as p1:
                return loadgen.run_closed_loop(
                    "127.0.0.1", p1.port, body, concurrency=1,
                    total_requests=200, duration_s=10.0,
                )["p50_ms"]
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    plain_p50 = _p50_with_env(None)
    publish_p50 = _p50_with_env(os.path.join(tmp, "bench_metrics"))
    out["snapshot_publish"] = {
        "plain_p50_ms": plain_p50,
        "publish_p50_ms": publish_p50,
    }
    # Flat copy for the stdout digest: the shrink ladder's serving_load
    # rungs keep scalars by name, and the overhead bound must survive
    # to the driver tail.
    out["publish_overhead_ms"] = round(publish_p50 - plain_p50, 4)
    return out


def bench_elastic_serving(tmp: str) -> dict:
    """Overload resilience A/B (ISSUE 15): the SAME diurnal+spike
    open-loop trace replayed against the serving tier twice — elasticity
    controls OFF (PR 7 semantics: everything queues) vs ON (admission
    control + the worker autoscaler) — so "overload degrades to bounded
    p99 instead of collapse" is a tracked number, not a slogan.

    The rig is deliberately deterministic: a synthetic MLP behind an
    in-process server whose per-flush cost is pinned by a
    ``slow_score:msN`` fault clause (``max_batch=1`` so batching cannot
    absorb the overload), base arrivals at ~50% of capacity, then a 4x
    spike. Controls OFF, the spike's excess arrivals queue without
    bound — admitted p99 grows with the spike length. Controls ON, low
    classes shed fast (429 + Retry-After) while the autoscaler raises
    the scoring-worker pool, so the p99 of ADMITTED traffic stays a
    function of the queue budget. The record carries both spike p99s,
    their ratios over the pre-spike baseline, the shed fraction, and
    the scale-event count; the sentinel tracks ``overload_p99_s`` and
    ``shed_fraction`` (observability/report.py)."""
    import numpy as np

    from dct_tpu.config import ServingConfig
    from dct_tpu.resilience import faults
    from dct_tpu.serving import loadgen
    from dct_tpu.serving.server import make_server_from_weights

    # Capacity = 1000/service_ms rows/s per worker (max_batch=1): base
    # arrivals sit at ~50% of one worker, the 4x spike at ~2x — a real
    # overload, not a grazing one.
    service_ms = 8.0
    base_qps, spike_qps = 60.0, 240.0
    base_s, spike_s = 1.5, 2.5
    weights, meta = loadgen.synthetic_mlp()
    rng = np.random.default_rng(0)
    body = json.dumps({
        "data": rng.standard_normal((1, meta["input_dim"])).round(4)
        .tolist()
    }).encode()

    def _replay(controls_on: bool) -> dict:
        import threading

        serving = ServingConfig(
            max_batch=1, workers=1, batch_window_ms=0.0,
            admit=controls_on, admit_max_queue=8, admit_wait_ms=40.0,
            retry_after_s=0.05,
            autoscale=controls_on, scale_min=1, scale_max=4,
            scale_up_queue=4.0, scale_down_queue=1.0,
            scale_poll_s=0.15, scale_hysteresis=2, scale_cooldown_s=0.4,
        )
        # Deterministic capacity: every flush costs service_ms — the
        # knee sits where the trace wants it, on any host.
        faults.set_default(
            faults.FaultPlan.parse(f"slow_score:ms{int(service_ms)}")
        )
        server = make_server_from_weights(weights, meta, serving=serving)
        host, port = server.server_address[:2]
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            phases = {}
            for phase, qps, dur in (
                ("base", base_qps, base_s),
                ("spike", spike_qps, spike_s),
                ("recover", base_qps, base_s),
            ):
                phases[phase] = loadgen.run_open_loop(
                    host, port, body, qps=qps, duration_s=dur,
                    max_inflight=400,
                    headers={"x-dct-priority": "low"},
                )
            return {
                "phases": phases,
                "scale_events": (
                    server.autoscaler.events
                    if server.autoscaler is not None else 0
                ),
            }
        finally:
            faults.set_default(None)
            server.shutdown()
            server.server_close()

    off = _replay(False)
    on = _replay(True)

    def _p99(replay, phase):
        return replay["phases"][phase].get("p99_ms")

    # Each replay's ratio uses ITS OWN base phase as the denominator —
    # the OFF comparison must not inherit noise from the ON run's
    # warm-up (worker scaling, admission bookkeeping) and vice versa.
    pre = _p99(on, "base")
    pre_off = _p99(off, "base")
    spike_off, spike_on = _p99(off, "spike"), _p99(on, "spike")
    sheds = sum(
        p.get("shed", 0) for p in on["phases"].values()
    )
    admitted = sum(p["requests"] for p in on["phases"].values())
    out = {
        "trace": {
            "base_qps": base_qps, "spike_qps": spike_qps,
            "base_s": base_s, "spike_s": spike_s,
            "service_ms": service_ms,
        },
        "off": off["phases"], "on": on["phases"],
        "pre_spike_p99_ms": pre,
        "pre_spike_p99_off_ms": pre_off,
        "spike_p99_off_ms": spike_off,
        "spike_p99_on_ms": spike_on,
        "p99_ratio_off": (
            round(spike_off / pre_off, 2)
            if pre_off and spike_off else None
        ),
        "p99_ratio_on": (
            round(spike_on / pre, 2) if pre and spike_on else None
        ),
        "overload_p99_s": (
            round(spike_on / 1e3, 4) if spike_on else None
        ),
        "shed": sheds,
        "admitted": admitted,
        "shed_fraction": round(sheds / max(1, sheds + admitted), 4),
        "admitted_errors": sum(
            p["errors"] for p in on["phases"].values()
        ),
        "scale_events": on["scale_events"],
    }
    out["bounded"] = bool(
        out["p99_ratio_on"] is not None and out["p99_ratio_on"] <= 3.0
    )
    _leg("elastic_overload_p99_s", out["overload_p99_s"])
    return out


def bench_telemetry_history(tmp: str) -> dict:
    """Telemetry history plane (ISSUE 17), two bounds per round:

    - **publish overhead** — p50 of ``SnapshotPublisher.publish()``
      plain vs with the history store teeing every snapshot
      (``timeseries.HistoryWriter`` at default flush settings). The
      store's whole design contract is "appends are memory pushes,
      disk only every flush window"; ``publish_overhead_ms`` is that
      contract as a tracked number (the sentinel gates it like a
      latency).
    - **detection latency** — the real serving chain (metrics plane +
      history store + anomaly monitor armed off env), baseline load to
      warm the EWMA, then a planted ``slow_score`` fault overloads the
      queue: seconds from planting to the ``queue_depth`` watch firing
      FROM THE ON-DISK HISTORY — the store→flush→read→detect pipeline
      end to end (``detect_latency_s`` on the sentinel)."""
    import statistics
    import threading

    import numpy as np

    from dct_tpu.observability.aggregate import SnapshotPublisher
    from dct_tpu.observability.metrics import MetricsRegistry
    from dct_tpu.observability.timeseries import HistoryWriter

    # -- publish overhead: armed vs plain ------------------------------
    def _registry() -> MetricsRegistry:
        """A representative live registry: a labelled counter, a busy
        histogram and a gauge — the shape a serving worker snapshots."""
        reg = MetricsRegistry()
        c = reg.counter("dct_requests_total", "bench")
        h = reg.histogram(
            "dct_serve_queue_depth", "bench",
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0),
        )
        g = reg.gauge("dct_train_goodput_fraction", "bench", agg="last")
        for i in range(64):
            c.inc(1, {"slot": "serving"})
            h.observe(float(i % 9))
        g.set(0.7)
        return reg

    def _publish_pair() -> tuple[float, float]:
        """p50 publish latency (plain, armed), measured INTERLEAVED —
        alternating one plain and one armed publish per iteration so
        ambient drift (page-cache state, CPU frequency, a noisy
        neighbour) lands on both medians equally instead of biasing
        whichever ran second."""
        pubs = {}
        for label, history in (
            ("plain", None),
            ("armed", HistoryWriter(
                os.path.join(tmp, "th_store"), proc="bench",
            )),
        ):
            pubs[label] = SnapshotPublisher(
                _registry(), os.path.join(tmp, f"th_metrics_{label}"),
                proc="bench", interval_s=1e9, start_timer=False,
                history=history,
            )
        times = {"plain": [], "armed": []}
        try:
            for _ in range(160):
                for label, pub in pubs.items():
                    t0 = time.perf_counter()
                    pub.publish()
                    times[label].append(time.perf_counter() - t0)
                # Pace the loop: real publishers fire on a seconds-scale
                # timer, so the history flusher thread's segment writes
                # happen BETWEEN publishes. Back-to-back publishes with
                # no gap would instead measure a GIL duel with that
                # thread — a workload the publish path never sees.
                time.sleep(0.001)
        finally:
            for pub in pubs.values():
                pub.close(final=False)
        return (
            statistics.median(times["plain"]) * 1e3,
            statistics.median(times["armed"]) * 1e3,
        )

    plain_ms, armed_ms = _publish_pair()

    # -- detection latency through the real serving chain --------------
    from dct_tpu.config import ServingConfig
    from dct_tpu.resilience import faults
    from dct_tpu.serving import loadgen
    from dct_tpu.serving.server import make_server_from_weights

    service_ms, fault_ms = 2.0, 30.0
    base_qps, spike_qps = 40.0, 80.0
    baseline_s, budget_s = 1.6, 12.0
    knobs = {
        "DCT_METRICS_DIR": os.path.join(tmp, "th_e2e_metrics"),
        "DCT_TS_DIR": os.path.join(tmp, "th_e2e_ts"),
        "DCT_EVENTS_DIR": os.path.join(tmp, "th_e2e_events"),
        "DCT_METRICS_PUBLISH_S": "0.1",
        "DCT_TS_FLUSH_S": "0.15",
        "DCT_ANOMALY_POLL_S": "0.1",
        "DCT_ANOMALY_MIN_POINTS": "5",
        "DCT_ANOMALY_WINDOW_S": "8",
        "DCT_ANOMALY_Z": "3.5",
        # No bundle assembly inside the timing loop — the latency being
        # measured is detection, not evidence collection.
        "DCT_INCIDENT": "0",
        "DCT_SLO_SPEC": "",
    }
    saved = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)
    weights, meta = loadgen.synthetic_mlp()
    rng = np.random.default_rng(0)
    body = json.dumps({
        "data": rng.standard_normal((1, meta["input_dim"])).round(4)
        .tolist()
    }).encode()
    detect_latency = None
    try:
        serving = ServingConfig(
            max_batch=1, workers=1, batch_window_ms=0.0,
        )
        faults.set_default(
            faults.FaultPlan.parse(f"slow_score:ms{int(service_ms)}")
        )
        server = make_server_from_weights(weights, meta, serving=serving)
        monitor = getattr(server, "history_monitor", None)
        host, port = server.server_address[:2]
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            if monitor is None:
                raise RuntimeError(
                    "history monitor did not arm (DCT_TS_DIR path)"
                )
            # Warm the EWMA baseline under healthy load.
            loadgen.run_open_loop(
                host, port, body, qps=base_qps, duration_s=baseline_s,
                max_inflight=64,
            )
            # Plant the fault: every flush now costs fault_ms, the
            # spike load overloads the single worker, queue depth grows.
            faults.set_default(
                faults.FaultPlan.parse(f"slow_score:ms{int(fault_ms)}")
            )
            spike = threading.Thread(
                target=loadgen.run_open_loop,
                args=(host, port, body),
                kwargs={
                    "qps": spike_qps, "duration_s": budget_s,
                    "max_inflight": 400,
                },
                daemon=True,
            )
            t_plant = time.perf_counter()
            spike.start()
            while time.perf_counter() - t_plant < budget_s:
                if any(
                    a.get("signal") == "queue_depth"
                    for a in monitor.detector.active()
                ):
                    detect_latency = time.perf_counter() - t_plant
                    break
                time.sleep(0.02)
            spike.join(timeout=budget_s)
        finally:
            faults.set_default(None)
            server.shutdown()
            server.server_close()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    out = {
        "plain_publish_p50_ms": round(plain_ms, 4),
        "armed_publish_p50_ms": round(armed_ms, 4),
        "publish_overhead_ms": round(max(0.0, armed_ms - plain_ms), 4),
        "overhead_frac": (
            round(max(0.0, armed_ms / plain_ms - 1.0), 4)
            if plain_ms > 0 else None
        ),
        "detected": detect_latency is not None,
        "detect_latency_s": (
            round(detect_latency, 3) if detect_latency is not None
            else None
        ),
        "rig": {
            "service_ms": service_ms, "fault_ms": fault_ms,
            "base_qps": base_qps, "spike_qps": spike_qps,
            "baseline_s": baseline_s, "budget_s": budget_s,
        },
    }
    _leg("telemetry_detect_latency_s", out["detect_latency_s"])
    return out


#: restart_spinup leg model: a transformer whose fused-epoch program
#: makes XLA compile the dominant cold-relaunch cost on the CPU rig
#: (the regime the cache exists for). Serial span consume pins ONE
#: program identity across the crash drill and the healed relaunch
#: (an armed fault plan forces serial anyway — compilecache docstring).
_SPINUP_MODEL_ENV = {
    "DCT_MODEL": "weather_transformer",
    "DCT_N_LAYERS": "4",
    "DCT_D_MODEL": "96",
    "DCT_N_HEADS": "4",
    "DCT_D_FF": "384",
    "DCT_SEQ_LEN": "16",
    "DCT_PREFETCH_SPANS": "0",
}


def bench_restart_spinup(tmp: str) -> dict:
    """Restart/spin-up debt, cold vs warm (ROADMAP item 5 / ISSUE 9):

    - **time-from-SIGKILL-to-first-step** through the REAL supervisor
      relaunch path (``python -m dct_tpu.resilience.supervise`` over
      ``jobs/train_tpu.py`` with a ``crash@rank0:step1`` hard kill),
      with the compile cache off (cold control) vs armed (the healed
      attempt deserializes the fused epoch program);
    - **time-to-first-score** of a fresh endpoint worker over a
      deployed package (single-row probe + max-batch flush), cold vs a
      package that carries its pre-compiled scorer (the packaging-time
      ``DCT_COMPILE_CACHE_WARM_SIZES`` warm-up).

    Wall-clock ratios land on the record every round so cold-start
    regressions are a tracked series (observability/report.py gates
    the warm numbers at the >25% latency threshold). The subprocess
    worlds inherit CPU pinning from the measurement env (spinup
    defaults JAX_PLATFORMS=cpu): a relaunch drill must never claim a
    live chip mid-bench, and the CPU numbers are the tracked series."""
    from dct_tpu.compilecache import spinup
    from dct_tpu.serving.score_gen import generate_score_package

    work = os.path.join(tmp, "restart_spinup")
    spinup.prepare_processed(work, rows=600)
    cold = spinup.measure_relaunch(
        work, cache_on=False, model_env=_SPINUP_MODEL_ENV
    )
    warm = spinup.measure_relaunch(
        work, cache_on=True, model_env=_SPINUP_MODEL_ENV
    )
    out = {
        # *_step_s = time-from-SIGKILL-to-first-step through the real
        # supervisor relaunch; *_score_s = endpoint worker
        # time-to-first-score; short names keep the stdout digest
        # inside the driver tail.
        "cold_step_s": cold["sigkill_to_first_step_s"],
        "warm_step_s": warm["sigkill_to_first_step_s"],
        "cold_compile_s": cold["relaunch_compile_s"],
        "warm_compile_s": warm["relaunch_compile_s"],
        "warm_cache": warm["relaunch_cache"],
    }
    if cold["sigkill_to_first_step_s"] and warm["sigkill_to_first_step_s"]:
        out["step_speedup"] = round(
            cold["sigkill_to_first_step_s"]
            / warm["sigkill_to_first_step_s"], 2,
        )
        _leg("restart_step_speedup", out["step_speedup"])

    # Endpoint spin-up over the warm run's own best checkpoint: the
    # package is built with the packaging-time scorer warm-up armed,
    # so the warm worker measures exactly what a deployed package
    # ships with.
    ckpts = sorted(
        f
        for f in os.listdir(os.path.join(work, "models_warm"))
        if f.endswith(".ckpt")
    )
    if ckpts:
        pkg = os.path.join(work, "package")
        saved = {
            k: os.environ.get(k)
            for k in ("DCT_COMPILE_CACHE", "DCT_COMPILE_CACHE_WARM_SIZES")
        }
        try:
            os.environ["DCT_COMPILE_CACHE"] = "on"
            os.environ["DCT_COMPILE_CACHE_WARM_SIZES"] = ",".join(
                str(s) for s in spinup.FIRST_SCORE_SIZES
            )
            generate_score_package(
                os.path.join(work, "models_warm", ckpts[0]), pkg
            )
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        cold_score = spinup.measure_first_score(pkg, cache_on=False)
        warm_score = spinup.measure_first_score(pkg, cache_on=True)
        out["cold_score_s"] = cold_score
        out["warm_score_s"] = warm_score
        if cold_score and warm_score:
            out["score_speedup"] = round(cold_score / warm_score, 2)
            _leg("restart_score_speedup", out["score_speedup"])
    return out


#: model_sharded leg shape: the SAME small transformer config measured
#: twice on a 4-virtual-CPU-device mesh in ISOLATED subprocesses (each
#: variant's peak host RSS is per-process, and XLA_FLAGS must be set
#: before the child's first jax import): pure DP (data=4, everything
#: replicated per device) vs partition-rule sharded (data=2/model=2 TP
#: + ZeRO-1 optimizer sharding). On the CPU rig "device" memory IS host
#: memory, so the replicated run materializes one state copy per
#: device while the sharded run holds one copy split across them — the
#: peak-RSS delta is the memory story, the samples/sec ratio the
#: throughput story (sharded_sps_ratio, tracked by report.py).
_SHARDED_DEVICES = 4
_SHARDED_CFG = dict(seq_len=16, d_model=64, n_heads=2, n_layers=2, d_ff=128)
_SHARDED_BATCH = 32
_SHARDED_SCAN = 8


def _model_sharded_child():
    """Subprocess body (``python -c "import bench; bench._model_sharded_
    child()" '<spec json>'``): build the mesh/layout the spec asks for,
    time the fused scanned step, report throughput + peak host RSS as
    one JSON line on stdout."""
    import resource

    import jax.numpy as jnp
    import numpy as np

    spec = json.loads(sys.argv[-1])

    from dct_tpu.config import MeshConfig, ModelConfig
    from dct_tpu.models.registry import get_model
    from dct_tpu.ops.attention import make_attention_fn
    from dct_tpu.parallel.mesh import make_global_epoch, make_mesh
    from dct_tpu.parallel.sharding_rules import shard_state_with_rules
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import make_epoch_train_step

    mesh = make_mesh(MeshConfig(**spec["mesh"]))
    cfg = ModelConfig(name="weather_transformer", **_SHARDED_CFG)
    input_dim = 5
    model = get_model(
        cfg, input_dim=input_dim, compute_dtype=jnp.float32,
        attn_fn=make_attention_fn(mesh), mesh=mesh,
    )
    state = create_train_state(
        model, input_dim=input_dim, lr=1e-3, seed=0,
        example_shape=(1, cfg.seq_len, input_dim),
    )
    state = shard_state_with_rules(
        state, mesh,
        shard_opt=spec["shard_opt"], shard_params=spec["shard_params"],
        family="weather_transformer",
    )
    rng = np.random.default_rng(0)
    scan_len, batch = _SHARDED_SCAN, _SHARDED_BATCH
    xs = rng.standard_normal(
        (scan_len, batch, cfg.seq_len, input_dim)
    ).astype(np.float32)
    ys = rng.integers(0, 2, (scan_len, batch)).astype(np.int32)
    ws = np.ones((scan_len, batch), np.float32)
    stacks = make_global_epoch(mesh, xs, ys, ws)
    epoch_step = make_epoch_train_step(donate=False)
    t_step = _time_scanned_step(
        epoch_step, state, stacks, scan_len=scan_len
    )
    # One fresh trajectory for the parity sanity number (the timed
    # states above advanced through warmup reps).
    import jax as _jax

    _st, losses = epoch_step(state, *stacks)
    _jax.block_until_ready(_st.params)
    peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    print(json.dumps({
        "samples_per_sec": round(batch / t_step, 1),
        "step_ms": round(t_step * 1e3, 3),
        "peak_host_rss_mb": round(peak_mb, 1),
        "first_epoch_loss": float(np.asarray(losses).mean()),
    }))


def bench_model_sharded() -> dict:
    """Partition-rule sharded vs pure-DP continuous training at matched
    config on the CPU mesh (ISSUE 11): throughput ratio + peak host
    memory per variant, each measured in an isolated subprocess world
    so RSS and device layout cannot bleed between them. The loss of the
    first fused epoch rides along as a cross-variant sanity pin (layout
    is not math: the two must agree to float tolerance)."""
    import subprocess

    # The parent has touched JAX and may hold the chip: the children are
    # pinned to CPU, explicitly (a host-side layout A/B by design).
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={_SHARDED_DEVICES}",
    )
    # The A/B must compare THIS tree's layouts, not an operator's
    # override experiment.
    env.pop("DCT_SHARD_RULES", None)

    def run(tag: str, mesh: dict, *, shard_opt: bool, shard_params: bool):
        spec = {
            "mesh": mesh, "shard_opt": shard_opt,
            "shard_params": shard_params,
        }
        out = subprocess.run(
            [
                sys.executable, "-c",
                "import bench; bench._model_sharded_child()",
                json.dumps(spec),
            ],
            env=env, cwd=_REPO_ROOT, capture_output=True, text=True,
            timeout=600,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"model_sharded {tag} child failed: {out.stderr[-400:]}"
            )
        return json.loads(out.stdout.strip().splitlines()[-1])

    dp = run(
        "dp", {"data": _SHARDED_DEVICES, "model": 1},
        shard_opt=False, shard_params=False,
    )
    sh = run(
        "sharded", {"data": _SHARDED_DEVICES // 2, "model": 2},
        shard_opt=True, shard_params=False,
    )
    out = {
        "devices": _SHARDED_DEVICES,
        "config": dict(_SHARDED_CFG, batch=_SHARDED_BATCH,
                       scan_len=_SHARDED_SCAN),
        "dp_sps": dp["samples_per_sec"],
        "sharded_sps": sh["samples_per_sec"],
        "dp_peak_rss_mb": dp["peak_host_rss_mb"],
        "sharded_peak_rss_mb": sh["peak_host_rss_mb"],
        # Layout is not math: the two first-epoch losses must agree to
        # float tolerance (different meshes reduce in different orders,
        # so bitwise is not promised HERE; the trainer-level pins live
        # in tests/test_sharded_loop.py).
        "loss_delta": round(
            abs(dp["first_epoch_loss"] - sh["first_epoch_loss"]), 8
        ),
    }
    if dp["samples_per_sec"]:
        out["sharded_sps_ratio"] = round(
            sh["samples_per_sec"] / dp["samples_per_sec"], 3
        )
    if sh["peak_host_rss_mb"]:
        out["peak_rss_ratio"] = round(
            dp["peak_host_rss_mb"] / sh["peak_host_rss_mb"], 3
        )
    return out


#: mpmd_pipeline leg shape (ISSUE 13): MPMD-1F1B (distinct per-stage
#: programs on disjoint device slices, explicit transfers) vs
#: SPMD-GPipe (the single lockstep tick program) at MATCHED stages /
#: microbatches / model config, each in an isolated 2-device subprocess
#: world. Bubble contract (docs/PARALLELISM.md §MPMD): the SPMD GPipe
#: program's bubble is ``(P-1)/(M+P-1)`` BY CONSTRUCTION of its
#: lockstep schedule (every device computes every tick, ramp ticks
#: compute garbage — tier-1 pins the tick model against a slope
#: measurement); the MPMD side's bubbles are MEASURED from per-stage
#: busy/idle windows — the whole-step bubble for an apples-to-apples
#: number, and the steady-state bubble (the always-on trainer's
#: operating point, where 1F1B keeps every stage saturated) for the
#: headline. Sizes tuned so per-op compute dominates the thread/queue
#: overhead on the CPU rig.
_MPMD_CFG = {
    "seq_len": 32, "d_model": 128, "n_heads": 4, "n_layers": 2,
    "d_ff": 512,
}
_MPMD_STAGES = 2
_MPMD_MICROBATCHES = 8
_MPMD_MB_ROWS = 32
_MPMD_REPS = 3


def _mpmd_bench_batch(m: int):
    import numpy as np

    rng = np.random.default_rng(0)
    b = _MPMD_MB_ROWS * m
    return (
        rng.standard_normal(
            (b, _MPMD_CFG["seq_len"], 5)
        ).astype(np.float32),
        rng.integers(0, 2, b).astype(np.int32),
        np.ones(b, np.float32),
    )


def _mpmd_child():
    """Subprocess body (``python -c "import bench; bench._mpmd_child()"
    '<spec json>'``): run one side of the A/B in its own 2-device world
    and report one JSON line."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    spec_in = json.loads(sys.argv[-1])
    side = spec_in["side"]
    m = int(spec_in["microbatches"])
    input_dim = 5

    from dct_tpu.config import ModelConfig, MpmdConfig

    mc_kwargs = dict(
        name="weather_transformer_pp", dropout=0.0,
        n_stages=_MPMD_STAGES, **_MPMD_CFG,
    )
    x, y, w = _mpmd_bench_batch(m)
    b = x.shape[0]

    if side == "mpmd":
        from dct_tpu.config import RunConfig
        from dct_tpu.parallel import mpmd
        from dct_tpu.train import mpmd_trainer as mt

        cfg = RunConfig()
        cfg.model = ModelConfig(**mc_kwargs)
        cfg.train.bf16_compute = False
        cfg.mpmd = MpmdConfig(
            stages=",".join(["1"] * _MPMD_STAGES), microbatches=m,
            schedule=spec_in.get("schedule", "1f1b"),
        )
        spec = cfg.mpmd.to_spec(n_devices=jax.device_count())
        meshes = mpmd.carve_stage_meshes(spec.device_counts, model=1)
        full = mt.build_full_state(cfg, input_dim, compute_dtype=jnp.float32)
        stage_states = [
            mt.shard_stage_state(
                mpmd.split_state(full, k, _MPMD_STAGES), meshes[k]
            )
            for k in range(_MPMD_STAGES)
        ]
        fns = mt.build_stage_fns(
            cfg.model, input_dim, compute_dtype=jnp.float32
        )
        progs = [
            mpmd.make_stage_programs(k, _MPMD_STAGES, fns)
            for k in range(_MPMD_STAGES)
        ]
        runner = mpmd.MpmdRunner(spec, stage_states, progs, meshes)
        # The compile+warm call's loss is the INIT-state loss — the
        # cross-schedule parity pin (the gpipe child re-steps its init
        # state every rep; the runner's states advance).
        loss, _ = runner.train_step(x, y, w)
        best, bub = None, None
        for _ in range(_MPMD_REPS):
            _loss_rep, wall = runner.train_step(x, y, w)
            if best is None or wall < best:
                best = wall
                bub = runner.step_bubble(wall)
        print(json.dumps({
            "wall_s": round(best, 4),
            "samples_per_sec_per_chip": round(b / (best * _MPMD_STAGES), 1),
            "step_bubble": bub["step_bubble"],
            "steady_bubble": bub["steady_bubble"],
            "transfer_wait_s": round(
                sum(s["transfer_wait_s"] for s in bub["stages"]), 4
            ),
            "loss": round(float(loss), 6),
        }))
        return

    # SPMD GPipe side: the registry PP model on a pipe=P mesh — ONE
    # jitted lockstep tick program (gpipe_tick_apply under GSPMD).
    from dct_tpu.config import MeshConfig
    from dct_tpu.models.registry import get_model
    from dct_tpu.parallel.mesh import make_mesh
    from dct_tpu.parallel.sharding_rules import shard_state_with_rules
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import _train_body

    mesh = make_mesh(
        MeshConfig(data=1, model=1, seq=1, pipe=_MPMD_STAGES)
    )
    cfg = ModelConfig(**mc_kwargs, n_microbatches=m)
    model = get_model(
        cfg, input_dim=input_dim, compute_dtype=jnp.float32, mesh=mesh
    )
    st = create_train_state(
        model, input_dim=input_dim, lr=0.01, seed=42,
        example_shape=(1, cfg.seq_len, input_dim),
    )
    st = shard_state_with_rules(st, mesh, family=cfg.name)
    step = jax.jit(_train_body)
    out = step(st, x, y, w)
    jax.block_until_ready(out[0].params)
    best, loss = None, None
    for _ in range(_MPMD_REPS):
        t0 = _time.perf_counter()
        out = step(st, x, y, w)
        jax.block_until_ready(out[0].params)
        wall = _time.perf_counter() - t0
        if best is None or wall < best:
            best = wall
        loss = float(out[1])
    print(json.dumps({
        "wall_s": round(best, 4),
        "samples_per_sec_per_chip": round(b / (best * _MPMD_STAGES), 1),
        "loss": round(loss, 6),
    }))


def bench_mpmd_pipeline() -> dict:
    """MPMD-1F1B vs SPMD-GPipe at matched P=2/M=8 (ISSUE 13 headline):
    bubble fraction for both schedules + samples/s/chip, each side in
    an isolated 2-device subprocess world. The acceptance bar — the
    MPMD steady-state bubble at least 15% below the SPMD-GPipe bubble
    — rides the record as ``bubble_reduction``; the slope-method bubble
    at a doubled microbatch count rides along as the cross-check that
    the MPMD step wall really is affine in M."""
    import subprocess

    from dct_tpu.parallel.mpmd import analytic_bubble, measured_bubble

    # Same pin as model_sharded: the parent may hold the chip, so the
    # children run on CPU, explicitly.
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(
            f"--xla_force_host_platform_device_count={_MPMD_STAGES}"
        ),
    )
    env.pop("DCT_SHARD_RULES", None)
    env.pop("DCT_MPMD_STAGES", None)

    def run(side: str, m: int) -> dict:
        out = subprocess.run(
            [
                sys.executable, "-c",
                "import bench; bench._mpmd_child()",
                json.dumps({"side": side, "microbatches": m}),
            ],
            env=env, cwd=_REPO_ROOT, capture_output=True, text=True,
            timeout=900,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"mpmd_pipeline {side}/M={m} child failed: "
                f"{out.stderr[-400:]}"
            )
        return json.loads(out.stdout.strip().splitlines()[-1])

    m = _MPMD_MICROBATCHES
    gp = run("gpipe", m)
    mp = run("mpmd", m)
    mp2 = run("mpmd", 2 * m)
    gpipe_bubble = analytic_bubble(_MPMD_STAGES, m)
    out = {
        "stages": _MPMD_STAGES,
        "microbatches": m,
        "config": dict(_MPMD_CFG, mb_rows=_MPMD_MB_ROWS),
        # The SPMD lockstep program's bubble is its tick count (the
        # tier-1 gpipe measured-vs-analytic test pins the tick model).
        "gpipe_bubble_fraction": round(gpipe_bubble, 4),
        "mpmd_steady_bubble": mp["steady_bubble"],
        "mpmd_step_bubble": mp["step_bubble"],
        "mpmd_slope_bubble": round(
            measured_bubble(mp["wall_s"], mp2["wall_s"], m, 2 * m), 4
        ),
        "mpmd_transfer_wait_s": mp["transfer_wait_s"],
        # Transfer-wait as a fraction of total stage-seconds per step
        # (wall x stages): the sentinel's inter-stage comms series.
        "mpmd_transfer_wait_frac": (
            round(
                mp["transfer_wait_s"] / (mp["wall_s"] * _MPMD_STAGES), 4
            )
            if mp.get("wall_s") else None
        ),
        "gpipe_sps": gp["samples_per_sec_per_chip"],
        "mpmd_sps": mp["samples_per_sec_per_chip"],
        # Cross-schedule parity pin: layout is not math (same init,
        # same batch, different reduction orders — float tolerance).
        "loss_delta": round(abs(gp["loss"] - mp["loss"]), 8),
        "bubble_reduction": round(
            1.0 - mp["steady_bubble"] / gpipe_bubble, 4
        ),
    }
    if gp["samples_per_sec_per_chip"]:
        out["mpmd_sps_ratio"] = round(
            mp["samples_per_sec_per_chip"]
            / gp["samples_per_sec_per_chip"], 3
        )
    return out


#: cycle_freshness leg shape: two SCORED generations arriving while the
#: system is busy, after a bootstrap generation that pays XLA compile
#: and the first deploy for BOTH runners. The serial side's train
#: quantum is the episodic cycle's epoch budget; the loop's is its
#: round — equal per-step semantics (same trainer), different
#: architecture. Soak dwell is identical on both sides (the rollout's
#: shadow/canary windows are inherent promotion latency either way).
_FRESHNESS_GENS = 2
_FRESHNESS_ROWS = 1200
_FRESHNESS_APPEND_ROWS = 300
#: The episodic cycle's per-trigger train budget. Sized so the train
#: stage DOMINATES the serial cycle (roughly 3:1 over the gate+deploy
#: tail on the CPU rig) — the regime the episodic architecture
#: actually lives in (a daily DAG trains the day's budget per cycle,
#: hours of training against minutes of deploy); a toy budget would
#: measure two promotion paths, not two architectures. The loop trains
#: the IDENTICAL per-step program continuously in
#: _FRESHNESS_LOOP_ROUND_EPOCHS-sized rounds — small enough that fresh
#: data waits under a round for its first gradient, large enough to
#: amortize the per-fit fixed costs.
_FRESHNESS_EPOCHS_PER_GEN = 200
_FRESHNESS_LOOP_ROUND_EPOCHS = 8
_FRESHNESS_SOAK_S = 0.35
_FRESHNESS_MAX_CYCLES_PER_GEN = 4
_FRESHNESS_LOOP_WALL_CAP_S = 150.0


def _freshness_append(raw_csv: str, seed: int) -> float:
    """Append one generation of rows and return the arrival timestamp
    (the file's mtime — what the ETL stamps)."""
    from dct_tpu.data.synthetic import append_weather_rows

    append_weather_rows(raw_csv, rows=_FRESHNESS_APPEND_ROWS, seed=seed)
    return os.path.getmtime(raw_csv)


def _freshness_cfg(work: str, side: str, epochs_per_round: int):
    from dct_tpu.config import (
        DataConfig, LoopConfig, ObservabilityConfig, RunConfig,
    )

    base = os.path.join(work, side)
    return RunConfig(
        data=DataConfig(
            processed_dir=os.path.join(base, "processed"),
            raw_csv=os.path.join(base, "raw", "weather.csv"),
            models_dir=os.path.join(base, "models"),
        ),
        obs=ObservabilityConfig(
            events_dir=os.path.join(base, "events"),
            heartbeat_dir=os.path.join(base, "hb"),
        ),
        loop=LoopConfig(
            poll_s=0.1, eval_poll_s=0.1,
            epochs_per_round=epochs_per_round,
            train_mode="inline", soak_s=_FRESHNESS_SOAK_S,
            packages_dir=os.path.join(base, "packages"),
            max_wall_s=_FRESHNESS_LOOP_WALL_CAP_S,
        ),
    )


def _freshness_serial(work: str) -> dict:
    """The episodic baseline: back-to-back serial cycles (ETL -> train
    -> gate -> deploy) with each scored generation arriving MID-cycle —
    the steady state of a schedule-triggered DAG."""
    import threading

    from dct_tpu.continuous import PromotionEvaluator, run_episodic_cycle
    from dct_tpu.data.synthetic import generate_weather_csv
    from dct_tpu.deploy.local import LocalEndpointClient

    cfg = _freshness_cfg(work, "serial", _FRESHNESS_EPOCHS_PER_GEN)
    generate_weather_csv(cfg.data.raw_csv, rows=_FRESHNESS_ROWS, seed=11)
    client = LocalEndpointClient()
    ev = PromotionEvaluator(
        cfg.data.models_dir, cfg.loop.packages_dir,
        client=client, endpoint="bench-fresh",
        processed_dir=cfg.data.processed_dir,
        soak_s=_FRESHNESS_SOAK_S, poll_s=0.0,
    )
    t0 = time.perf_counter()
    boot = run_episodic_cycle(cfg, client=client, evaluator=ev)
    cycle_s = boot["cycle_s"]
    fresh: list[float] = []
    cycles: list[dict] = []
    for g in range(_FRESHNESS_GENS):
        target_gen = g + 2  # bootstrap published generation 1
        arrival_box: dict = {}
        timer = threading.Timer(
            max(0.05, 0.4 * cycle_s),
            lambda: arrival_box.setdefault(
                "ts", _freshness_append(cfg.data.raw_csv, seed=100 + g)
            ),
        )
        timer.start()
        # The cycle the arrival lands inside (the episodic trigger was
        # already committed to the OLD data), then cycles until a
        # promoted model has trained on the new generation — a gate
        # hold honestly delays freshness by another full cycle.
        for _ in range(1 + _FRESHNESS_MAX_CYCLES_PER_GEN):
            rec = run_episodic_cycle(cfg, client=client, evaluator=ev)
            cycles.append(rec)
            promoted_gen = (
                ev.promotions[-1].get("generation") or 0
            ) if ev.promotions else 0
            if "ts" in arrival_box and promoted_gen >= target_gen:
                fresh.append(
                    ev.promotions[-1]["ts"] - arrival_box["ts"]
                )
                break
        timer.cancel()
    wall = time.perf_counter() - t0
    train_step = sum(c["train_step_wall_s"] for c in cycles) + boot[
        "train_step_wall_s"
    ]
    sps = [
        c["train_samples_per_sec_per_chip"]
        for c in cycles + [boot]
        if c["train_samples_per_sec_per_chip"]
    ]
    return {
        "freshness_s": [round(f, 3) for f in fresh],
        "mean_freshness_s": (
            round(sum(fresh) / len(fresh), 3) if fresh else None
        ),
        "cycle_s": round(
            sum(c["cycle_s"] for c in cycles) / len(cycles), 3
        ) if cycles else None,
        "cycles": len(cycles) + 1,
        "promotions": len(ev.promotions),
        "held": len(ev.held),
        "goodput": round(train_step / wall, 4) if wall > 0 else None,
        "train_samples_per_sec_per_chip": (
            round(sum(sps) / len(sps), 1) if sps else None
        ),
        "wall_s": round(wall, 3),
    }


def _freshness_loop(work: str) -> dict:
    """The overlapped loop on the SAME workload: short rounds, ingest
    and promotion concurrent, arrivals landing mid-round."""
    import threading

    from dct_tpu.continuous import AlwaysOnLoop
    from dct_tpu.data.synthetic import generate_weather_csv

    cfg = _freshness_cfg(work, "loop", _FRESHNESS_LOOP_ROUND_EPOCHS)
    generate_weather_csv(cfg.data.raw_csv, rows=_FRESHNESS_ROWS, seed=11)
    arrivals: dict[int, float] = {}
    fresh: dict[int, float] = {}
    state = {"next": 2, "loop": None}
    lock = threading.Lock()

    def _arrive_later(gen: int, delay: float) -> None:
        def _go():
            arrivals[gen] = _freshness_append(
                cfg.data.raw_csv, seed=100 + (gen - 2)
            )
        threading.Timer(delay, _go).start()

    def on_promotion(rec: dict) -> None:
        gen = rec.get("generation") or 0
        with lock:
            for g, ats in list(arrivals.items()):
                if ats is not None and g not in fresh and gen >= g:
                    fresh[g] = rec["ts"] - ats
            if gen >= 1 and state["next"] == 2 and 2 not in arrivals:
                # Bootstrap deployed: first scored generation arrives
                # mid-round, like the serial side's mid-cycle arrival.
                arrivals[2] = None  # reserve
                _arrive_later(2, 0.2)
                state["next"] = 3
            elif (
                state["next"] <= _FRESHNESS_GENS + 1
                and (state["next"] - 1) in fresh
            ):
                g = state["next"]
                arrivals[g] = None
                _arrive_later(g, 0.2)
                state["next"] = g + 1
            if len(fresh) >= _FRESHNESS_GENS and state["loop"] is not None:
                state["loop"].request_stop("freshness_measured")

    loop = AlwaysOnLoop(cfg, on_promotion=on_promotion)
    state["loop"] = loop
    summary = loop.run()
    scored = [v for v in fresh.values() if v is not None]
    return {
        "freshness_s": [round(f, 3) for f in sorted(scored)],
        "mean_freshness_s": (
            round(sum(scored) / len(scored), 3) if scored else None
        ),
        "rounds": summary["rounds"],
        "promotions": summary["promotions"],
        "held": summary["held"],
        "goodput": summary["goodput"],
        "train_samples_per_sec_per_chip":
            summary["train_samples_per_sec_per_chip"],
        "wall_s": summary["wall_s"],
        "stop_reason": summary["reason"],
    }


def bench_cycle_freshness(tmp: str) -> dict:
    """Data-arrival -> deployed-model latency, serial episodic cycle vs
    the always-on overlapped loop (ISSUE 10 / ROADMAP item 3), same
    workload and same promotion machinery on both sides. The headline
    is ``freshness_speedup`` (serial mean / loop mean; the acceptance
    bar is >= 2x at equal per-step training semantics) plus platform
    goodput (train-step wall / runner wall) for both architectures."""
    work = os.path.join(tmp, "cycle_freshness")
    saved = {
        k: os.environ.get(k)
        for k in ("DCT_TRACKING_DIR", "DCT_COMPILE_CACHE")
    }
    try:
        # Tracker files under the leg's own tree; AOT executable store
        # armed so rounds/cycles past the bootstrap load their fused
        # programs instead of recompiling (both sides benefit equally —
        # the steady-state configuration the loop lives in, PR 9).
        os.environ["DCT_TRACKING_DIR"] = os.path.join(work, "mlruns")
        os.environ["DCT_COMPILE_CACHE"] = "on"
        serial = _section("cycle_freshness.serial", _freshness_serial, work)
        loop = _section("cycle_freshness.loop", _freshness_loop, work)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    out: dict = {
        "generations": _FRESHNESS_GENS,
        "epochs_per_gen_serial": _FRESHNESS_EPOCHS_PER_GEN,
        "loop_round_epochs": _FRESHNESS_LOOP_ROUND_EPOCHS,
        "soak_s": _FRESHNESS_SOAK_S,
        "serial": serial,
        "loop": loop,
        # Flat copies: the stdout digest + the report.py sentinel series
        # dig these without descending into the side stanzas.
        "serial_mean_freshness_s": serial["mean_freshness_s"],
        "loop_mean_freshness_s": loop["mean_freshness_s"],
        "goodput_serial": serial["goodput"],
        "goodput_loop": loop["goodput"],
    }
    if serial["mean_freshness_s"] and loop["mean_freshness_s"]:
        out["freshness_speedup"] = round(
            serial["mean_freshness_s"] / loop["mean_freshness_s"], 2
        )
        _leg("cycle_freshness_speedup", out["freshness_speedup"])
    if (
        serial["train_samples_per_sec_per_chip"]
        and loop["train_samples_per_sec_per_chip"]
    ):
        out["train_throughput_ratio"] = round(
            loop["train_samples_per_sec_per_chip"]
            / serial["train_samples_per_sec_per_chip"], 2,
        )
    return out


#: stream_ingest leg shape: a timed arrival process (bursts on a fixed
#: schedule) driven through BOTH deployed watchers — the stream-mode
#: watcher at its DCT_STREAM_POLL_S cadence vs the CSV polling watcher
#: at the loop's DCT_LOOP_POLL_S default. Freshness is the product
#: claim, so the sentinel is IN-BOUND throughput: events made trainable
#: within the arrival→trainable bound, per second of wall.
_STREAM_BENCH_EVENTS = 4000
_STREAM_BENCH_BURST = 50
_STREAM_BENCH_BURST_EVERY_S = 0.05
#: The configured arrival→trainable bound (seconds). Deliberately under
#: the CSV watcher's 2 s poll cadence: sub-cadence freshness is exactly
#: what the streaming plane exists to buy (docs/STREAMING.md).
_STREAM_BENCH_LAG_BOUND_S = 0.25
#: The CSV comparator's cadence = the loop's production default
#: (config.LoopConfig.poll_s); pinned here so a drifting loop default
#: silently changing the bench comparator would show up in review.
_STREAM_BENCH_CSV_POLL_S = 2.0


def bench_stream_ingest(tmp: str) -> dict:
    """Streaming ingest data plane (ISSUE 19): sustained events/s at
    bounded arrival→trainable lag, stream mode vs the polling watcher.

    The same timed arrival process (bursts of rows on a fixed schedule)
    feeds both DEPLOYED watchers: the stream side produces each burst
    onto the partitioned event log and :class:`StreamIngestWatcher`
    runs the exactly-once offset-range ETL at its ``DCT_STREAM_POLL_S``
    cadence; the CSV side appends each burst to the staging file and
    ``IngestWatcher`` runs the PR 10 incremental re-digest at the
    loop's default ``DCT_LOOP_POLL_S`` cadence. Per-event
    arrival→trainable lag = (the pass that covered it completing) −
    (its burst's arrival wall). The sentinels:

    - ``stream_events_per_s`` (up) — events made trainable WITHIN the
      configured bound, per second of wall. The CSV watcher's cadence
      floors its lag near ``poll_s``, so most of its events miss a
      sub-cadence bound — the acceptance bar is the stream side
      sustaining >= 5x the poller's in-bound rate.
    - ``stream_lag_p99_s`` (down) — the stream side's lag p99, which
      must itself stay under the bound.

    A backpressure sub-phase runs a producer with a tiny lag budget and
    NO consumer: the shed counter must engage and end-of-phase lag must
    stay at or under budget — the "never unbounded" acceptance bit."""
    import threading

    import numpy as np

    from dct_tpu.config import StreamConfig
    from dct_tpu.continuous.ingest import IngestWatcher, StreamIngestWatcher
    from dct_tpu.etl.preprocess import DEFAULT_FEATURES
    from dct_tpu.stream.log import PartitionedEventLog, StreamProducer

    n_events = _STREAM_BENCH_EVENTS
    burst, every = _STREAM_BENCH_BURST, _STREAM_BENCH_BURST_EVERY_S
    bound = _STREAM_BENCH_LAG_BOUND_S
    rng = np.random.default_rng(19)

    def _rows(n: int) -> list[dict]:
        vals = {
            "Temperature": rng.uniform(-5, 40, n),
            "Humidity": rng.uniform(10, 100, n),
            "Wind_Speed": rng.uniform(0, 30, n),
            "Cloud_Cover": rng.uniform(0, 100, n),
            "Pressure": rng.uniform(980, 1040, n),
        }
        rain = rng.random(n) < 0.3
        return [
            {
                **{k: round(float(vals[k][i]), 2) for k in DEFAULT_FEATURES},
                "Rain": "rain" if rain[i] else "no rain",
            }
            for i in range(n)
        ]

    bursts = [_rows(burst) for _ in range(n_events // burst)]

    def _drive(watcher, deliver, *, warm_rows: int = 0) -> dict:
        """Run ``watcher`` (its deployed ``run`` thread) against the
        timed arrival schedule; ``deliver(rows, ts)`` lands one burst.
        A warm-up burst (outside the clock, the bench-wide idiom — cold
        numpy/pyarrow import and the first full-basis publish are
        one-time costs, not the sustained path) precedes the schedule
        when ``warm_rows`` is 0. Returns per-event lags + in-bound
        throughput."""
        stop = threading.Event()
        marks: list[tuple[float, int]] = []  # (trainable wall, rows)
        check_once = watcher.check_once

        def _instrumented():
            state = check_once()
            if state is not None:
                marks.append((time.time(), int(state.get("rows") or 0)))
            return state

        watcher.check_once = _instrumented
        thread = threading.Thread(
            target=watcher.run, args=(stop,), daemon=True
        )
        thread.start()
        if warm_rows == 0:
            deliver(_rows(burst), time.time())
            deadline = time.time() + 3.0 * max(
                getattr(watcher, "poll_s", 1.0), 1.0
            )
            while time.time() < deadline and not marks:
                time.sleep(0.02)
            warm_rows = marks[-1][1] if marks else 0
        t_start = time.time()
        arrivals: list[float] = []
        for rows in bursts:
            t_arr = time.time()
            deliver(rows, t_arr)
            arrivals.extend([t_arr] * len(rows))
            time.sleep(every)
        # Drain: give the slower cadence two more fires to catch up.
        deadline = time.time() + 2.5 * max(
            getattr(watcher, "poll_s", 1.0), 1.0
        )
        target = warm_rows + n_events
        while time.time() < deadline:
            if marks and marks[-1][1] >= target:
                break
            time.sleep(0.05)
        stop.set()
        thread.join(timeout=10.0)
        lags: list[float] = []
        covered = 0
        for t_mark, rows_total in marks:
            done = min(rows_total - warm_rows, n_events)
            for i in range(covered, max(covered, done)):
                lags.append(t_mark - arrivals[i])
            covered = max(covered, done)
        wall = (marks[-1][0] - t_start) if marks else (time.time() - t_start)
        in_bound = sum(1 for x in lags if x <= bound)
        return {
            "trainable": len(lags),
            "in_bound": in_bound,
            "in_bound_events_per_s": round(in_bound / max(wall, 1e-9), 1),
            "lag_p99_s": (
                round(float(np.percentile(lags, 99)), 4) if lags else None
            ),
            "wall_s": round(wall, 2),
        }

    # -- stream side: producer bursts + deployed stream watcher --------
    sdir = os.path.join(tmp, "si_stream")
    scfg = StreamConfig()
    scfg.mode, scfg.dir, scfg.topic = "stream", sdir, "bench"
    log = PartitionedEventLog(sdir, "bench", partitions=2)
    prod = StreamProducer(
        log, groups=(scfg.group,), backpressure="block",
        lag_budget=max(n_events, 1), batch_records=burst,
    )
    s_watch = StreamIngestWatcher(
        scfg, os.path.join(tmp, "si_stream_out"),
        poll_s=scfg.poll_s, prefetch=True,
    )

    def _deliver_stream(rows: list[dict], ts: float) -> None:
        for r in rows:
            prod.produce(dict(r), ts=ts)
        prod.flush()

    stream = _drive(s_watch, _deliver_stream)
    prod.close()
    s_watch.close()

    # -- CSV side: staged appends + deployed polling watcher -----------
    csv = os.path.join(tmp, "si_poll.csv")
    cols = DEFAULT_FEATURES + ["Rain"]
    with open(csv, "w") as f:
        f.write(",".join(cols) + "\n")
    p_watch = IngestWatcher(
        csv, os.path.join(tmp, "si_poll_out"),
        poll_s=_STREAM_BENCH_CSV_POLL_S,
    )

    def _deliver_csv(rows: list[dict], ts: float) -> None:
        with open(csv, "a") as f:
            for r in rows:
                f.write(",".join(str(r[c]) for c in cols) + "\n")

    poll = _drive(p_watch, _deliver_csv)

    # -- backpressure: tiny budget, dead consumer ----------------------
    bp_log = PartitionedEventLog(os.path.join(tmp, "si_bp"), "bp",
                                 partitions=1)
    bp = StreamProducer(
        bp_log, groups=("etl",), backpressure="shed",
        lag_budget=64, batch_records=32,
    )
    for r in _rows(512):
        bp.produce(r)
    bp.flush()
    bp_lag = bp.lag_records()
    bp.close()

    out: dict = {
        "n_events": n_events,
        "burst": burst,
        "burst_every_s": every,
        "lag_bound_s": bound,
        "stream_poll_s": scfg.poll_s,
        "csv_poll_s": _STREAM_BENCH_CSV_POLL_S,
        "stream_events_per_s": stream["in_bound_events_per_s"],
        "poll_events_per_s": poll["in_bound_events_per_s"],
        "stream_lag_p99_s": stream["lag_p99_s"],
        "poll_lag_p99_s": poll["lag_p99_s"],
        "stream": stream,
        "poll": poll,
        "backpressure": {
            "lag_budget": 64,
            "produced": bp.produced,
            "shed": bp.shed,
            "end_lag_records": bp_lag,
            "bounded": bp.shed > 0 and bp_lag <= 64,
        },
    }
    if poll["in_bound_events_per_s"] > 0:
        out["events_per_s_speedup"] = round(
            stream["in_bound_events_per_s"] / poll["in_bound_events_per_s"],
            2,
        )
    if out["stream_lag_p99_s"] is not None:
        out["lag_bounded"] = out["stream_lag_p99_s"] <= bound
    _leg("stream_events_per_s", out["stream_events_per_s"])
    _leg("stream_lag_p99_s", out["stream_lag_p99_s"])
    return out


#: multi_tenant leg shape: two same-family always-on tenants at 1:2
#: quota weights time-sharing the rig through round leases (ISSUE 12).
#: Rounds are small so the deficit scheduler gets enough boundaries to
#: converge the chip-time shares inside the leg's budget; the shared
#: AOT store amortizes the second tenant's compile exactly as in
#: production (docs/SCHEDULER.md).
_TENANT_BENCH_ROWS = 1200
#: Enough boundaries for the deficit scheduler to absorb the first
#: round's one-off XLA-compile skew (~10 warm rounds' worth) and then
#: demonstrably converge the 1:2 shares.
_TENANT_BENCH_ROUNDS = 20
_TENANT_BENCH_ROUND_EPOCHS = 4
_TENANT_BENCH_WALL_CAP_S = 120.0


def bench_multi_tenant(tmp: str) -> dict:
    """Per-tenant goodput fraction, round-lease wait, and quota
    convergence over a short REAL 2-tenant scheduler session. The
    sentinel series are ``min_goodput_fraction`` (the worst tenant's
    useful-seconds share of its granted leases) and
    ``mean_round_wait_s`` (how long tenants queue for chips);
    ``quota_max_rel_err`` tracks how far granted chip time landed from
    the configured 1:2 shares."""
    import json as _json

    from dct_tpu.config import (
        ObservabilityConfig, RunConfig, SchedulerConfig,
    )
    from dct_tpu.data.synthetic import generate_weather_csv
    from dct_tpu.scheduler import WorkloadScheduler, parse_tenants

    work = os.path.join(tmp, "multi_tenant")
    raw = os.path.join(work, "raw", "weather.csv")
    generate_weather_csv(raw, rows=_TENANT_BENCH_ROWS, seed=13)
    saved = {k: os.environ.get(k) for k in ("DCT_TRACKING_DIR",)}
    os.environ["DCT_TRACKING_DIR"] = os.path.join(work, "mlruns")
    try:
        cfg = RunConfig(
            obs=ObservabilityConfig(
                events_dir=os.path.join(work, "events"),
                heartbeat_dir=os.path.join(work, "hb"),
            ),
            sched=SchedulerConfig(
                root=os.path.join(work, "tenants"),
                poll_s=0.2,
                max_rounds=_TENANT_BENCH_ROUNDS,
                max_wall_s=_TENANT_BENCH_WALL_CAP_S,
            ),
        )
        tenants = parse_tenants(_json.dumps([
            {"name": "light", "weight": 1.0},
            {"name": "heavy", "weight": 2.0},
        ]))
        sched = WorkloadScheduler(cfg, tenants=tenants, base_env={
            "DCT_RAW_CSV": raw,
            "DCT_LOOP_TRAIN_MODE": "inline",
            "DCT_LOOP_EPOCHS_PER_ROUND": str(_TENANT_BENCH_ROUND_EPOCHS),
            "DCT_LOOP_SOAK_S": "0.05",
            "DCT_LOOP_POLL_S": "0.2",
            "DCT_LOOP_EVAL_POLL_S": "0.2",
        })
        summary = sched.run()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    per_tenant = summary["tenants"]
    fracs = [
        t["goodput_fraction"] for t in per_tenant.values()
        if t.get("goodput_fraction") is not None
    ]
    waits = [
        t["mean_wait_s"] for t in per_tenant.values()
        if t.get("mean_wait_s") is not None
    ]
    errs = [
        abs(t["granted_share"] - t["fair_share"]) / t["fair_share"]
        for t in per_tenant.values()
        if t.get("granted_share") is not None and t.get("fair_share")
    ]
    return {
        "tenants": len(per_tenant),
        "rounds": summary["total_rounds"],
        "preempts": summary["preempts"],
        "wall_s": summary["wall_s"],
        "min_goodput_fraction": round(min(fracs), 4) if fracs else None,
        "mean_round_wait_s": (
            round(sum(waits) / len(waits), 3) if waits else None
        ),
        "quota_max_rel_err": round(max(errs), 3) if errs else None,
        # The full per-tenant ledger stays in the partial; stdout keeps
        # the flat series above (_stdout_record digests this away).
        "per_tenant": per_tenant,
    }


def _torch_reference_setup(data):
    """The reference's exact seed/data/model/optimizer
    (jobs/train_lightning_ddp.py:14,45-46,57-61,88): seed 42, float
    features / long labels, MLP input->64(ReLU, dropout 0.2)->2, Adam
    lr 0.01. ONE definition shared by the throughput baseline and the
    val-parity leg, so the protocol cannot drift between them."""
    import numpy as np
    import torch

    torch.manual_seed(42)
    feats = torch.from_numpy(np.ascontiguousarray(data.features))
    labels = torch.from_numpy(np.ascontiguousarray(data.labels)).long()
    model = torch.nn.Sequential(
        torch.nn.Linear(data.input_dim, 64),
        torch.nn.ReLU(),
        torch.nn.Dropout(0.2),
        torch.nn.Linear(64, 2),
    )
    opt = torch.optim.Adam(model.parameters(), lr=0.01)
    return feats, labels, model, opt


def bench_torch_reference(data) -> float:
    """The reference's per-rank training loop, measured on this host's CPU."""
    import torch.nn.functional as F
    from torch.utils.data import DataLoader, TensorDataset

    feats, labels, model, opt = _torch_reference_setup(data)
    n_train = int(0.8 * len(feats))
    ds = TensorDataset(feats[:n_train], labels[:n_train])
    loader = DataLoader(ds, batch_size=BATCH, shuffle=True, num_workers=0)
    model.train()

    # Warm up one pass over a few hundred steps, then time full epochs.
    it = iter(loader)
    for _ in range(min(200, len(loader))):
        x, y = next(it)
        opt.zero_grad()
        F.cross_entropy(model(x), y).backward()
        opt.step()

    timed = max(1, int(os.environ.get("DCT_BENCH_TORCH_EPOCHS", "1")))
    t0 = time.perf_counter()
    steps = 0
    for _ in range(timed):
        for x, y in loader:
            opt.zero_grad()
            F.cross_entropy(model(x), y).backward()
            opt.step()
            steps += 1
    dt = time.perf_counter() - t0
    return steps * BATCH / dt


def bench_val_parity(data, tmp: str) -> dict:
    """The north-star number (BASELINE.md protocol row 1): run the
    reference's EXACT end-to-end config in torch — 10 epochs, batch 4,
    seeded 80/20 random split, Adam lr 0.01, MLP 5->64(ReLU, dropout
    0.2)->2 (reference jobs/train_lightning_ddp.py:14,57-61,88,117,122,
    132) — and the product ``Trainer.fit()`` at its reference-parity
    defaults, on the SAME parquet, and report both final val_losses
    side by side. RNG streams differ across frameworks by construction
    (shuffle order, dropout masks, split permutation); the parity claim
    is the converged val_loss band, not bitwise trajectory (that is
    tests/test_train_step.py's job).
    """
    import torch
    import torch.nn.functional as F
    from torch.utils.data import DataLoader, TensorDataset, random_split

    feats, labels, model, opt = _torch_reference_setup(data)
    ds = TensorDataset(feats, labels)
    n_train = int(0.8 * len(ds))  # train_lightning_ddp.py:117
    train_set, val_set = random_split(
        ds, [n_train, len(ds) - n_train],
        generator=torch.Generator().manual_seed(42),
    )
    train_loader = DataLoader(
        train_set, batch_size=BATCH, shuffle=True, num_workers=0
    )
    val_loader = DataLoader(
        val_set, batch_size=BATCH, shuffle=False, num_workers=0
    )
    epochs = int(os.environ.get("DCT_VAL_PARITY_EPOCHS", "10"))
    for _ in range(epochs):  # max_epochs=10 (train_lightning_ddp.py:132)
        model.train()
        for x, y in train_loader:
            opt.zero_grad()
            F.cross_entropy(model(x), y).backward()
            opt.step()
    model.eval()
    loss_sum = acc_sum = count = 0.0
    with torch.no_grad():
        for x, y in val_loader:
            logits = model(x)
            loss_sum += float(
                F.cross_entropy(logits, y, reduction="sum")
            )
            acc_sum += float((logits.argmax(1) == y).sum())
            count += len(y)
    torch_vl = loss_sum / count
    torch_va = acc_sum / count
    # Stream the torch side NOW: if the jax side below fails, the
    # host-CPU torch numbers must not be lost with it.
    _leg(
        "val_parity_torch",
        {"torch_val_loss": round(torch_vl, 5),
         "torch_val_acc": round(torch_va, 5)},
    )

    # Ours: the product Trainer.fit() at its defaults — which ARE the
    # reference config (config.py TrainConfig: epochs 10, batch 4,
    # lr 0.01, seed 42, val_fraction 0.2). Same parquet-loaded arrays.
    from dct_tpu.config import (
        DataConfig, RunConfig, TrackingConfig, TrainConfig,
    )
    from dct_tpu.tracking.client import LocalTracking
    from dct_tpu.train.trainer import Trainer

    cfg = RunConfig(
        data=DataConfig(models_dir=os.path.join(tmp, "parity_models")),
        train=TrainConfig(epochs=epochs, batch_size=BATCH),
        tracking=TrackingConfig(experiment="val_parity"),
    )
    tracker = LocalTracking(
        root=os.path.join(tmp, "parity_runs"), experiment="val_parity"
    )
    result = Trainer(cfg, tracker=tracker).fit(data)

    out = {
        "protocol": (
            f"{epochs} epochs, batch {BATCH}, Adam lr 0.01, seeded 80/20 "
            "split, seed 42 (train_lightning_ddp.py:14,88,117,122,132)"
        ),
        "torch_val_loss": round(torch_vl, 5),
        "torch_val_acc": round(torch_va, 5),
        "jax_val_loss": round(float(result.val_loss), 5),
        "jax_val_acc": round(float(result.val_acc), 5),
        "abs_diff": round(abs(float(result.val_loss) - torch_vl), 5),
    }
    _leg("val_parity", out)
    return out


_BENCH_T0 = time.perf_counter()
# Soft wall-clock budget: optional sections are skipped once exceeded so
# the bench ALWAYS prints its JSON line instead of being timeout-killed
# mid-run (which loses the record).
_DEADLINE = float(os.environ.get("DCT_BENCH_DEADLINE", "1500"))

def _over_deadline(name: str, frac: float = 1.0) -> bool:
    """``frac`` < 1 carves out budget for the sections BEHIND this one:
    the scaled section's optional variant legs each compile their own
    programs, and at frac=1 they starve the MoE/serving sections the
    record also needs (the E>=16 sorted_speedup is a driver-record
    deliverable, not a nice-to-have)."""
    elapsed = time.perf_counter() - _BENCH_T0
    budget = _DEADLINE * frac
    if _DEADLINE > 0 and elapsed > budget:
        print(
            f"[bench] SKIP {name}: {elapsed:.0f}s elapsed > "
            f"{budget:.0f}s ({frac:.0%} of "
            f"DCT_BENCH_DEADLINE={_DEADLINE:.0f}s)",
            file=sys.stderr, flush=True,
        )
        return True
    return False


def _section(name: str, fn, *args):
    """Run one bench section with a wall-time line on stderr —
    knowing where the minutes went is the difference between tuning
    compute and tuning dispatch."""
    t0 = time.perf_counter()
    out = fn(*args)
    print(
        f"[bench] {name}: {time.perf_counter() - t0:.1f}s",
        file=sys.stderr, flush=True,
    )
    return out


# Partial-record checkpointing: every completed section is flushed to this
# file (and echoed on stderr), so a mid-run wedge/timeout-kill still leaves
# all numbers measured so far on disk.
_PARTIAL_PATH = os.environ.get(
    "DCT_BENCH_PARTIAL", os.path.join(_REPO_ROOT, "BENCH_PARTIAL.json")
)


def _json_default(o):
    """Serialization fallback for the partial record: a numpy scalar (or
    anything else json chokes on) leaking into a leg value must degrade
    to a representable form, never raise — a TypeError thrown FROM the
    evidence hedge would kill the section it exists to protect."""
    try:
        return float(o)
    except (TypeError, ValueError):
        return str(o)


def _flush_partial(record: dict) -> None:
    # Serialize once, crash-proof (see _json_default), then atomic
    # replace: a SIGKILL mid-write must not corrupt the previous flush —
    # that is the record this file exists to preserve.
    payload = json.dumps(record, default=_json_default)
    tmp_path = _PARTIAL_PATH + ".tmp"
    try:
        with open(tmp_path, "w") as f:
            f.write(payload + "\n")
        os.replace(tmp_path, _PARTIAL_PATH)
    except OSError as e:  # read-only rigs: stderr echo still lands
        print(f"[bench] partial write failed: {e}", file=sys.stderr)
        try:
            os.remove(tmp_path)
        except OSError:
            pass
    print(f"[bench] partial: {payload}", file=sys.stderr, flush=True)


def _stdout_record(record: dict) -> dict:
    """The driver machine-parses the final JSON line from a 2,000-byte
    stdout tail; a line past it ships ``parsed: null``. This builds the
    PRINTED record: the verbatim record stays on disk
    (``BENCH_PARTIAL.json``) while stdout gets a val_parity with the
    ~140 B protocol prose reduced to its BASELINE.md pointer and the
    digests below. Everything else passes through unchanged.
    tests/test_bench_record.py pins the worst-case fully-populated line
    at <= 1,800 B."""
    out = dict(record)
    vp = out.get("val_parity")
    if isinstance(vp, dict) and "protocol" in vp:
        vp = dict(vp)
        vp["protocol"] = "BASELINE.md row 1"
        out["val_parity"] = vp
    tg = out.get("trainer_gap")
    if isinstance(tg, dict):
        # fused/fit duplicate the top-level value / trainer_loop keys
        # byte for byte; stdout keeps the ratio + the mode knob only.
        out["trainer_gap"] = {
            k: tg.get(k) for k in ("fused_over_fit", "prefetch_spans")
        }
    # Derivable duplicate: trainer_loop / baseline, both already on the
    # line byte for byte (the partial keeps the computed field).
    out.pop("trainer_loop_vs_baseline", None)
    # The unit is a constant of the metric name ("samples/sec/chip",
    # verbatim in the partial) — bytes reclaimed to fund the
    # telemetry_history sentinel series.
    out.pop("unit", None)
    rs = out.get("restart_spinup")
    if isinstance(rs, dict):
        # Stdout carries the warm numbers (the sentinel's tracked
        # series) + both ratios; the cold controls are derivable
        # (warm x speedup) and the compile-seconds/cache-label detail
        # stays in the partial.
        digest = {
            k: rs[k]
            for k in (
                "warm_step_s", "step_speedup",
                "warm_score_s", "score_speedup",
            )
            if k in rs
        }
        if digest:
            out["restart_spinup"] = digest
    ms = out.get("model_sharded")
    if isinstance(ms, dict) and "error" not in ms:
        # Stdout carries the two ratios + the parity delta (the
        # sentinel's series + the memory story as one number); the
        # per-variant sps/RSS detail and the config dict stay in the
        # partial (env-reconstructible constants).
        out["model_sharded"] = {
            k: ms[k]
            for k in ("sharded_sps_ratio", "peak_rss_ratio", "loss_delta")
            if k in ms
        }
    cf = out.get("cycle_freshness")
    if isinstance(cf, dict) and "error" not in cf:
        # Stdout carries the architecture comparison (speedup, the loop
        # mean, both goodputs); the serial mean is derivable
        # (loop_mean x speedup — bytes reclaimed to fund the
        # mpmd_pipeline sentinel series), and the throughput-parity
        # ratio, generation count and per-side stanzas with freshness
        # series, cycle walls and stop reasons stay in the partial.
        out["cycle_freshness"] = {
            k: cf[k]
            for k in (
                "freshness_speedup", "loop_mean_freshness_s",
                "goodput_serial", "goodput_loop",
            )
            if k in cf
        }
    mt = out.get("multi_tenant")
    if isinstance(mt, dict) and "error" not in mt:
        # Stdout carries ONLY the sentinel series + the quota error —
        # the stdout line had ~17 B of typical-round headroom left, so
        # the counts (tenants/rounds/preempts/wall) and the per-tenant
        # ledger stay in the partial.
        out["multi_tenant"] = {
            k: mt[k]
            for k in (
                "min_goodput_fraction", "mean_round_wait_s",
                "quota_max_rel_err",
            )
            if k in mt
        }
    mpp = out.get("mpmd_pipeline")
    if isinstance(mpp, dict) and "error" not in mpp:
        # Stdout carries the two sentinel series + the gpipe comparator
        # bubble (bubble_reduction = 1 - steady/gpipe is derivable);
        # the config dict, slope cross-check, transfer-wait and
        # absolute sps detail stay in the partial.
        out["mpmd_pipeline"] = {
            k: mpp[k]
            for k in (
                "mpmd_steady_bubble", "gpipe_bubble_fraction",
                "mpmd_sps_ratio", "mpmd_transfer_wait_frac",
            )
            if k in mpp
        }
    rf = out.get("roofline")
    if isinstance(rf, dict) and "error" not in rf:
        # Stdout carries the sentinel series + the roofline placement;
        # the size config, step time, flops and peak detail stay in the
        # partial (the mfu itself is duplicated at top level — that key
        # is the record's headline and predates this stanza).
        out["roofline"] = {
            k: rf[k]
            for k in (
                "mfu", "arithmetic_intensity", "bound", "peak_source",
            )
            if k in rf
        }
    srv = out.get("serving")
    if isinstance(srv, dict) and "error" not in srv:
        # torch_p50_ms is derivable on stdout (numpy_p50_ms x speedup)
        # and verbatim in the partial — bytes reclaimed to fund the
        # multi_tenant sentinel series.
        out["serving"] = {
            label: (
                {k: v for k, v in leg.items() if k != "torch_p50_ms"}
                if isinstance(leg, dict) else leg
            )
            for label, leg in srv.items()
        }
    sl = out.get("serving_load")
    if isinstance(sl, dict) and isinstance(sl.get("levels"), list):
        # Columnar digest of the sweep: every measured number still on
        # stdout at ~half the bytes of the per-level dict list (which
        # stays verbatim in the partial). Derivables (knee qps = qps at
        # the knee level, saturated concurrency, a processes=1 default,
        # all-zero error columns) stay on disk only.
        sl = dict(sl)
        lv = [r for r in sl["levels"] if isinstance(r, dict)]
        sl["levels"] = {
            "concurrency": [r.get("concurrency") for r in lv],
            "qps": [r.get("qps") for r in lv],
            "p50_ms": [r.get("p50_ms") for r in lv],
            "p99_ms": [r.get("p99_ms") for r in lv],
        }
        if any(r.get("errors") for r in lv):  # all-zero = noise
            sl["levels"]["errors"] = [r.get("errors") for r in lv]
        sl.pop("knee_qps", None)
        sl.pop("saturated_concurrency", None)
        # The per-variant p50 pair stays in the partial; stdout carries
        # the flat publish_overhead_ms bound only.
        sl.pop("snapshot_publish", None)
        # baseline_qps is derivable (saturated_qps / batched_over_single)
        # and verbatim in the partial — bytes reclaimed to fund the
        # elastic_serving sentinel series.
        sl.pop("baseline_qps", None)
        if sl.get("processes") == 1:
            sl.pop("processes")
        out["serving_load"] = sl
    es = out.get("elastic_serving")
    if isinstance(es, dict) and "error" not in es:
        # Stdout carries the sentinel series + the A/B ratios + the
        # acceptance bit; the per-phase replay dicts, the trace shape
        # and the derivables (pre_spike p99 = spike_on / ratio_on, shed
        # counts behind the fraction) stay in the partial.
        out["elastic_serving"] = {
            k: es[k]
            for k in (
                "overload_p99_s", "shed_fraction", "p99_ratio_on",
                "p99_ratio_off", "bounded",
            )
            if k in es
        }
    th = out.get("telemetry_history")
    if isinstance(th, dict) and "error" not in th:
        # Stdout carries ONLY the two sentinel series — the stdout line
        # is near its budget, so the plain/armed p50 pair behind the
        # overhead and the rig knobs stay in the partial (the overhead
        # carries the A/B story in one number).
        out["telemetry_history"] = {
            k: th[k]
            for k in ("detect_latency_s", "publish_overhead_ms")
            if k in th
        }
    si = out.get("stream_ingest")
    if isinstance(si, dict) and "error" not in si:
        # Stdout carries the two sentinel series, the vs-polling
        # speedup and the two acceptance bits; the polling comparator's
        # raw numbers, the chunk shape and the backpressure counter
        # detail stay in the partial (bounded is the story in one bit).
        digest = {
            k: si[k]
            for k in (
                "stream_events_per_s", "stream_lag_p99_s",
                "events_per_s_speedup", "lag_bounded",
            )
            if k in si
        }
        bp = si.get("backpressure")
        if isinstance(bp, dict):
            digest["backpressure_bounded"] = bp.get("bounded")
        out["stream_ingest"] = digest
    lp = out.get("low_precision")
    if isinstance(lp, dict) and "error" not in lp:
        # Stdout carries the two sentinel series + the accuracy bound
        # evidence + the gate parity bit ONLY — the train A/B ratios
        # are derivable (reduction_pct = 100 x (1 - bytes_ratio)) or
        # verbatim in the partial (sps ratio), and the per-variant
        # p50/throughput/bytes detail and the size config stay there
        # too (the line has no typical-round headroom left for more).
        digest = {
            k: lp[k]
            for k in ("quant_serving_speedup", "bf16_bytes_ratio")
            if k in lp
        }
        sv = lp.get("serving")
        if isinstance(sv, dict) and isinstance(sv.get("int8"), dict):
            digest["int8_prob_delta"] = sv["int8"].get(
                "max_abs_prob_delta"
            )
        gt = lp.get("gate")
        if isinstance(gt, dict) and "error" not in gt:
            digest["gate_parity"] = gt.get("parity")
        out["low_precision"] = digest
    hd = out.get("host_dataplane")
    if isinstance(hd, dict) and "error" not in hd:
        # The native timings are derivable (numpy_ms / speedup) and
        # verbatim in the partial — more elastic_serving funding.
        out["host_dataplane"] = {
            k: v for k, v in hd.items() if not k.endswith("_native_ms")
        }
    legs = out.get("scaled_legs")
    if isinstance(legs, dict):
        # The streamed crash hedges survive when their section FAILED
        # (a scaled failure leaves scaled_legs in the record). The
        # val_parity hedge carries the ~140 B protocol
        # prose; same pointer treatment as the section stanza.
        legs = dict(legs)
        for k in ("val_parity", "val_parity_torch"):
            if isinstance(legs.get(k), dict) and "protocol" in legs[k]:
                legs[k] = dict(legs[k], protocol="BASELINE.md row 1")
        out["scaled_legs"] = legs

    def _cfg_digest(cfg: dict) -> str:
        """One short provenance string for a size config dict (the full
        dict stays in the partial; the knobs are env-reconstructible)."""
        short = {"d_model": "d", "n_heads": "h", "n_layers": "L",
                 "d_ff": "ff", "seq_len": "T", "n_experts": "E",
                 "batch": "b", "scan_len": "scan"}
        parts = [f"{short[k]}{cfg[k]}" for k in short if k in cfg]
        parts += [
            (k if cfg[k] else f"no-{k}") if isinstance(cfg[k], bool)
            else f"{k}={cfg[k]}"
            for k in cfg
            if k not in short and not isinstance(cfg[k], (dict, list))
        ]
        return " ".join(parts)

    for key in ("scaled", "moe"):
        sec = out.get(key)
        if isinstance(sec, dict) and isinstance(sec.get("config"), dict):
            sec = dict(sec)
            sec["config"] = _cfg_digest(sec["config"])
            out[key] = sec
    # The torch baseline is derivable on stdout (value / vs_baseline)
    # and verbatim in the partial — bytes reclaimed to fund the
    # multi_tenant sentinel series.
    if out.get("value") and out.get("vs_baseline"):
        out.pop("baseline_torch_cpu_samples_per_sec", None)
    return _shrink_to_budget(out)


#: Printed-line budget, with headroom under the driver's 2,000-byte
#: stdout tail (the line must parse even if a stray warning shares the
#: tail). test_bench_record.py asserts the worst case stays <= 1,800.
_STDOUT_BUDGET = 1750


def _shrink_to_budget(out: dict) -> dict:
    """Guarantee the printed line fits the driver tail: collapse the
    least-headline stanzas to their core numbers, one at a time, until
    the encoded record is under :data:`_STDOUT_BUDGET`. In a typical
    round nothing here fires — the provenance digests alone fit; this
    ladder exists so a maximally-populated record (every section AND
    skip markers at once) can never push the line past the tail. The
    verbatim record
    always survives in ``BENCH_PARTIAL.json``."""
    def fits() -> bool:
        return (
            len(json.dumps(out, default=_json_default).encode())
            <= _STDOUT_BUDGET
        )

    if fits():
        return out

    def _keep(key: str, fields: tuple) -> None:
        sec = out.get(key)
        if isinstance(sec, dict):
            kept = {k: sec[k] for k in fields if k in sec}
            if len(kept) < len(sec):
                # ONE top-level pointer for every collapsed stanza: a
                # per-stanza "more" marker cost 28 B per fired rung —
                # at the bottom of the ladder that waste alone was
                # collapsing the next stanza in line.
                out["more"] = "BENCH_PARTIAL.json"
            out[key] = kept

    # Least headline first; each rung re-checks the budget. Every
    # top-level stanza the bench can emit has a rung here: a stanza
    # the ladder cannot reach is a stanza that can push the line past
    # the driver tail.
    ladder = (
        ("host_dataplane", ("rows_speedup", "windows_speedup")),
        ("serving", ()),
        # The protocol pointer is a constant ("BASELINE.md row 1" —
        # recoverable from the partial); under squeeze the three parity
        # NUMBERS are what must ride.
        ("val_parity", ("torch_val_loss", "jax_val_loss", "abs_diff")),
        ("scaled_legs", ("attn_blockwise_ms", "attn_flash_ms",
                         "moe_sorted_ms", "moe_einsum_ms",
                         "serving_load_qps")),
        ("moe", ("config", "sorted_ms", "einsum_ms", "sorted_speedup",
                 "deadline_skipped")),
        # chip_peak_bf16_tflops is the platform table's constant and
        # tflops_per_sec = mfu x peak — both derivable, both in the
        # partial (bytes reclaimed for the multi_tenant series).
        ("scaled", ("config", "step_time_ms", "step_time_dispatch_ms",
                    "attn_blockwise_ms", "attn_flash_ms", "mfu",
                    "deadline_skipped")),
        # Reachability guard (usually a no-op: _stdout_record already
        # digested the stanza to exactly these four); the cold
        # controls, compile seconds and cache labels live on in the
        # partial.
        ("restart_spinup", ("warm_step_s", "step_speedup",
                            "warm_score_s", "score_speedup")),
        # Same guard for the freshness digest: the speedup + the loop
        # mean + both goodputs survive every tier-1 squeeze (the
        # serial mean is derivable: loop_mean x speedup).
        ("cycle_freshness", ("freshness_speedup",
                             "loop_mean_freshness_s",
                             "goodput_serial", "goodput_loop")),
        # Sharded-vs-DP: the sentinel's tracked throughput ratio
        # survives tier 1; the memory-story ratio and parity delta
        # yield to the partial under squeeze.
        ("model_sharded", ("sharded_sps_ratio",)),
        # Multi-tenant: the two sentinel series + the quota error
        # survive tier 1; counts yield to the partial.
        ("multi_tenant", ("min_goodput_fraction", "mean_round_wait_s",
                          "quota_max_rel_err")),
        # MPMD pipeline: reachability guard (the digest already keeps
        # these — both sentinel series, the comparator, and the
        # transfer-wait fraction; the frac yields first under squeeze).
        ("mpmd_pipeline", ("mpmd_steady_bubble", "gpipe_bubble_fraction",
                           "mpmd_sps_ratio")),
        # Roofline: the sentinel's program_mfu series + the placement
        # survive tier 1; intensity/peak-source yield to the partial.
        ("roofline", ("mfu", "bound")),
        # Elastic serving: both sentinel series + the A/B ratio pair
        # survive tier 1 (the bounded flag and scale-event count yield
        # to the partial under squeeze).
        ("elastic_serving", ("overload_p99_s", "shed_fraction",
                             "p99_ratio_on", "p99_ratio_off")),
        # Telemetry history: reachability guard (the digest already
        # keeps exactly these two sentinel series).
        ("telemetry_history", ("detect_latency_s",
                               "publish_overhead_ms")),
        # Stream ingest: reachability guard (the digest already keeps
        # the sentinels + speedup + acceptance bits; the speedup and
        # bits yield to the partial under squeeze, the series last).
        ("stream_ingest", ("stream_events_per_s", "stream_lag_p99_s")),
        # Low precision: reachability guard (the digest already keeps
        # exactly these four — both sentinel series, the accuracy
        # bound and the gate bit; the train A/B ratios never ride
        # stdout, they are derivable/verbatim in the partial).
        ("low_precision", ("quant_serving_speedup", "bf16_bytes_ratio",
                           "int8_prob_delta", "gate_parity")),
        # Late config squeeze: the scaled/moe size-config digest
        # strings are env-reconstructible constants (and verbatim in
        # the partial) — they yield before the serving_load level
        # columns do.
        ("moe", ("sorted_ms", "einsum_ms", "sorted_speedup",
                 "deadline_skipped")),
        ("scaled", ("step_time_ms", "step_time_dispatch_ms",
                    "attn_blockwise_ms", "attn_flash_ms", "mfu",
                    "deadline_skipped")),
        # Late non-sentinel squeezes funding the elastic_serving series:
        # the quota error and the windows-path speedup yield (verbatim
        # in the partial) before the serving_load level columns do.
        ("multi_tenant", ("min_goodput_fraction", "mean_round_wait_s")),
        ("host_dataplane", ("rows_speedup",)),
        # Late squeeze funding the telemetry_history sentinel series:
        # the elastic A/B ratio pair yields (verbatim in the partial)
        # before the serving_load level columns do — the two elastic
        # sentinel series always survive tier 1.
        ("elastic_serving", ("overload_p99_s", "shed_fraction")),
        # Late squeeze funding the stream_ingest sentinel series: the
        # freshness goodput pair and the gpipe bubble comparator yield
        # (verbatim in the partial — and bubble_reduction/goodput live
        # on there) before the serving_load level columns do; both
        # stanzas' sentinel series always survive tier 1.
        ("cycle_freshness", ("freshness_speedup",
                             "loop_mean_freshness_s")),
        # Late squeeze funding the low_precision sentinel series: the
        # prefetch knob, the moe deadline marker + sorted wall
        # (einsum_ms / sorted_speedup recovers it), the tenant wait
        # and the load knee (the argmax of the qps column) yield — all
        # verbatim in the partial — before the gpipe comparator does.
        ("trainer_gap", ("fused_over_fit",)),
        ("moe", ("einsum_ms", "sorted_speedup")),
        ("multi_tenant", ("min_goodput_fraction",)),
        ("serving_load", ("processes", "levels", "saturated_qps",
                          "batched_over_single",
                          "score_batched_over_single", "parity",
                          "publish_overhead_ms")),
        ("mpmd_pipeline", ("mpmd_steady_bubble", "mpmd_sps_ratio")),
        # The serving tier's headline stanza goes LAST in tier 1: its
        # per-level qps/p50/p99 columns outlive every other stanza's
        # detail (the acceptance contract wants >= 2 levels on the
        # driver record), collapsing to the ratios only when even the
        # scaled/carry-forward digests were not enough.
        ("serving_load", ("processes", "baseline_qps", "saturated_qps",
                          "knee_concurrency", "batched_over_single",
                          "score_batched_over_single", "parity",
                          "publish_overhead_ms")),
    )
    for key, fields in ladder:
        if key == "serving":
            srv = out.get("serving")
            if isinstance(srv, dict) and "error" not in srv:
                out["serving"] = {
                    label: leg.get("speedup")
                    for label, leg in srv.items()
                    if isinstance(leg, dict)
                }
        else:
            _keep(key, fields)
        if fits():
            return out

    # Tier 2: a maximally-populated record (every stanza AND failure
    # leftovers at once) can exceed the budget even with every tier-1
    # rung fired. Each stanza collapses to its headline number(s); the
    # partial keeps all.
    for key, fields in (
        ("host_dataplane", ("rows_speedup",)),
        ("serving", ()),
        ("scaled_legs", ("attn_blockwise_ms", "attn_flash_ms")),
        ("serving_load", ("saturated_qps", "batched_over_single",
                          "score_batched_over_single", "parity")),
        ("val_parity", ("abs_diff",)),
        ("restart_spinup", ("step_speedup", "score_speedup")),
        ("cycle_freshness", ("freshness_speedup",)),
        ("model_sharded", ("sharded_sps_ratio",)),
        ("multi_tenant", ("min_goodput_fraction",)),
        ("mpmd_pipeline", ("mpmd_steady_bubble",)),
        ("roofline", ("mfu",)),
        ("elastic_serving", ("overload_p99_s", "shed_fraction")),
        ("telemetry_history", ("detect_latency_s",)),
        ("stream_ingest", ("stream_events_per_s", "stream_lag_p99_s")),
        ("low_precision", ("quant_serving_speedup", "bf16_bytes_ratio")),
        ("moe", ("sorted_speedup",)),
        ("trainer_gap", ("fused_over_fit", "prefetch_spans")),
        ("scaled", ("step_time_ms", "attn_blockwise_ms",
                    "attn_flash_ms", "mfu")),
    ):
        if key == "serving":
            if isinstance(out.get("serving"), dict):
                out["serving"] = {"more": "BENCH_PARTIAL.json"}
        else:
            _keep(key, fields)
        if fits():
            return out

    # Last rung: no stanza may carry a multi-KB string — error text from
    # XLA/Mosaic (attn_*_error, a section-level {"error": ...}) can run
    # to kilobytes and none of the field-keep rungs above touch string
    # values. Progressively harder truncation until the line fits;
    # stderr and the partial keep the full text. Recurses LISTS too —
    # the stanzas carry dict lists (loadgen levels, deadline_skipped) a
    # dict-only walk would sail past.
    def _truncate(obj, limit):
        if isinstance(obj, dict):
            return {k: _truncate(v, limit) for k, v in obj.items()}
        if isinstance(obj, list):
            return [_truncate(v, limit) for v in obj]
        if isinstance(obj, str) and len(obj) > limit:
            return obj[:limit]
        return obj

    for limit in (200, 100, 48):
        for key in list(out):
            out[key] = _truncate(out[key], limit)
        if fits():
            return out
    return out


def main():
    import tempfile

    record = {
        "metric": "weather_parity_train_samples_per_sec_per_chip",
        "unit": "samples/sec/chip",
        "mfu": None,
        "generated_utc": time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
        ),
    }
    # Join the bench to the provenance plane: the run-correlation ID ties
    # it to the event log, the ledger head pins WHICH lineage graph state
    # the numbers were measured against (both None-safe when disabled).
    try:
        from dct_tpu.observability import events as _events
        from dct_tpu.observability import lineage as _lineage

        record["run_id"] = _events.current_run_id()
        record["lineage_head"] = _lineage.head_hash()
    except Exception:
        record["run_id"] = None
        record["lineage_head"] = None
    global _LIVE_RECORD
    _LIVE_RECORD = record
    # Overwrite any stale partial from a previous run BEFORE the first
    # section: an early crash must leave this run's (empty) record, not a
    # prior run's numbers masquerading as this run's partials.
    _flush_partial(record)

    skip_scaled = os.environ.get("DCT_BENCH_SCALED", "1").strip().lower() in (
        "0", "false", "no"
    )

    def _gate(name: str, frac: float = 1.0) -> bool:
        """Deadline gate that leaves a trace: every skipped leg names
        itself in the record's top-level ``deadline_skipped`` list, not
        on stderr alone."""
        if _over_deadline(name, frac=frac):
            skipped = record.setdefault("deadline_skipped", [])
            if name not in skipped:
                skipped.append(name)
            _flush_partial(record)
            return True
        return False

    with tempfile.TemporaryDirectory() as tmp:
        data = _section("prepare_data", _prepare_data, tmp)
        baseline = _section("torch_baseline", bench_torch_reference, data)
        record["baseline_torch_cpu_samples_per_sec"] = round(baseline, 1)
        _flush_partial(record)

        ours, last_loss = _section("parity_fused", bench_tpu, data)
        import jax

        record.update(
            value=round(ours, 1),
            vs_baseline=round(ours / baseline, 2),
            final_train_loss=round(last_loss, 4),
            platform=jax.default_backend(),
        )
        _flush_partial(record)

        trainer_loop = _section(
            "trainer_loop", bench_trainer_loop, data, tmp
        )
        record["trainer_loop_samples_per_sec_per_chip"] = round(
            trainer_loop, 1
        )
        record["trainer_loop_vs_baseline"] = round(trainer_loop / baseline, 2)
        # The dispatch-gap tracker (ISSUE 5 tentpole): fused-epoch vs
        # the production Trainer.fit() loop on the IDENTICAL config,
        # data, and host, as a ratio recorded EVERY round, so the gap
        # the host loop leaves on the table is tracked. fit()
        # additionally pays the per-epoch validation pass, both
        # checkpoint tiers, and telemetry; the ratio is the price of
        # being the product, and driving it toward 1.0 is the trainer's
        # standing perf objective.
        record["trainer_gap"] = {
            # Units: samples/sec/chip (the record's headline unit).
            "fused": record["value"],
            "fit": round(trainer_loop, 1),
            "fused_over_fit": (
                round(ours / trainer_loop, 2) if trainer_loop else None
            ),
            "prefetch_spans": _bench_prefetch_spans(),
        }
        _flush_partial(record)

        def _optional(name: str, fn, *args):
            """Optional sections degrade to an error marker instead of
            killing the sections after them — the driver's end-of-round
            run must always reach the final JSON line. The record's
            error string is truncated: XLA/Mosaic messages run to
            multiple KB, and one of them riding the record would blow
            the 2,000-byte driver tail (stderr gets the full text)."""
            try:
                return _section(name, fn, *args)
            except Exception as e:  # noqa: BLE001
                print(
                    f"[bench] {name} FAILED ({type(e).__name__}: {e})",
                    file=sys.stderr, flush=True,
                )
                return {"error": f"{type(e).__name__}: {e}"[:200]}

        # Same product loop with all timed epochs in ONE dispatch
        # (TrainConfig.epoch_chunk): the delta to the leg above is the
        # per-epoch host round trip.
        # frac=0.3: this A/B leg runs AHEAD of the headline scaled-MFU
        # section and costs 2K epochs plus a fresh XLA compile of the
        # multi-epoch program — an ungated run here can push
        # scaled_transformer over its own deadline gate, trading the
        # record's primary deliverable for a secondary number.
        if not _gate("trainer_loop_chunked", frac=0.3):
            # K >= 2 always: at DCT_BENCH_EPOCHS=1 a chunk of 1 would
            # silently re-measure the unchunked path into the same dirs.
            chunked = _optional(
                "trainer_loop_chunked", bench_trainer_loop, data, tmp,
                max(2, TIMED_EPOCHS),
            )
            if isinstance(chunked, float):
                record["trainer_loop_chunked_samples_per_sec_per_chip"] = (
                    round(chunked, 1)
                )
            else:
                record["trainer_loop_chunked_samples_per_sec_per_chip"] = None
            _flush_partial(record)

        # Roofline leg (ISSUE 14): cost-model FLOPs/bytes of a small
        # train-scan joined with its measured step time.
        # DCT_BENCH_ROOFLINE=0 skips (the smoke's knob, like
        # DCT_BENCH_SCALED).
        skip_roofline = os.environ.get(
            "DCT_BENCH_ROOFLINE", "1"
        ).strip().lower() in ("0", "false", "no")
        if not (skip_roofline or _gate("roofline", frac=0.5)):
            record["roofline"] = _optional("roofline", bench_roofline)
            _flush_partial(record)

        if not (skip_scaled or _gate("scaled_transformer")):
            scaled = _optional(
                "scaled_transformer", bench_scaled_transformer
            )
            record["scaled"] = scaled
            if isinstance(scaled, dict) and "error" not in scaled:
                # the streamed legs were a crash hedge; the full dict
                # supersedes them
                record.pop("scaled_legs", None)
            # The headline mfu is the scaled leg's measured step time
            # over the device table's peak — absent off the TPU.
            record["mfu"] = scaled.get("mfu")
            if record["mfu"] is not None:
                record["mfu_source"] = "scaled_onchip"
            _flush_partial(record)

        if not (skip_scaled or _gate("scaled_moe")):
            record["moe"] = _optional("scaled_moe", bench_scaled_moe)
            if isinstance(record["moe"], dict) and "error" not in record["moe"]:
                legs = record.get("scaled_legs")
                if legs:
                    for k in [k for k in legs if k.startswith("moe_")]:
                        legs.pop(k)
                    if not legs:
                        record.pop("scaled_legs", None)
            _flush_partial(record)

        # After scaled/MoE (on-chip those are the headline) but gated so
        # the record's ONE JSON line still lands:
        # the north-star val-loss parity (BASELINE.md protocol row 1).
        if not _gate("val_parity", frac=0.85):
            record["val_parity"] = _optional(
                "val_parity", bench_val_parity, data, tmp
            )
            if (
                isinstance(record["val_parity"], dict)
                and "error" not in record["val_parity"]
            ):
                legs = record.get("scaled_legs")
                if legs:  # the streamed hedges are superseded
                    legs.pop("val_parity", None)
                    legs.pop("val_parity_torch", None)
                    if not legs:
                        record.pop("scaled_legs", None)
            _flush_partial(record)

        if not _gate("serving"):
            record["serving"] = _optional("serving", bench_serving, tmp)
            _flush_partial(record)

        # The serving tier under traffic: qps/p50/p99 at >= 2
        # concurrency levels + the saturation knee (ISSUE 7). Runs on
        # the host CPU, like `serving`.
        if not _gate("serving_load"):
            record["serving_load"] = _optional(
                "serving_load", bench_serving_load, tmp
            )
            _flush_partial(record)

        # Elastic overload A/B (ISSUE 15): one diurnal+spike open-loop
        # trace, controls off vs on — bounded-p99-vs-collapse as a
        # tracked pair every round. Host-CPU leg like serving_load;
        # DCT_BENCH_ELASTIC=0 skips (the in-process smoke's knob).
        skip_elastic = os.environ.get(
            "DCT_BENCH_ELASTIC", "1"
        ).strip().lower() in ("0", "false", "no")
        if not (skip_elastic or _gate("elastic_serving", frac=0.9)):
            record["elastic_serving"] = _optional(
                "elastic_serving", bench_elastic_serving, tmp
            )
            _flush_partial(record)

        # Restart/spin-up debt cold vs warm (ISSUE 9): supervised
        # SIGKILL-relaunch + endpoint first-score through the compile
        # cache. Runs on the host CPU; the
        # frac carve-out keeps two supervised subprocess worlds from
        # starving the remaining host legs on a tight deadline.
        # DCT_BENCH_SPINUP=0 skips (the in-process smoke's knob, like
        # DCT_BENCH_SCALED).
        skip_spinup = os.environ.get(
            "DCT_BENCH_SPINUP", "1"
        ).strip().lower() in ("0", "false", "no")
        if not (skip_spinup or _gate("restart_spinup", frac=0.9)):
            record["restart_spinup"] = _optional(
                "restart_spinup", bench_restart_spinup, tmp
            )
            _flush_partial(record)

        # Always-on freshness (ISSUE 10): serial episodic cycle vs the
        # overlapped loop on one workload — data-arrival -> deployed
        # latency + platform goodput, recorded every round. Host-CPU
        # leg like serving/spinup; DCT_BENCH_FRESHNESS=0 skips (the
        # in-process smoke's knob), frac carve-out keeps the two
        # runners from starving the dataplane tail.
        skip_fresh = os.environ.get(
            "DCT_BENCH_FRESHNESS", "1"
        ).strip().lower() in ("0", "false", "no")
        if not (skip_fresh or _gate("cycle_freshness", frac=0.95)):
            record["cycle_freshness"] = _optional(
                "cycle_freshness", bench_cycle_freshness, tmp
            )
            _flush_partial(record)

        # Sharded vs DP at matched config (ISSUE 11): two subprocess
        # worlds on the virtual CPU mesh — throughput ratio, peak host
        # RSS per variant. DCT_BENCH_SHARDED=0 skips (the in-process
        # smoke's knob, like DCT_BENCH_SPINUP).
        skip_sharded = os.environ.get(
            "DCT_BENCH_SHARDED", "1"
        ).strip().lower() in ("0", "false", "no")
        if not (skip_sharded or _gate("model_sharded", frac=0.97)):
            record["model_sharded"] = _optional(
                "model_sharded", bench_model_sharded
            )
            _flush_partial(record)

        # Multi-tenant scheduler (ISSUE 12): a short 2-tenant session at
        # 1:2 quota weights — worst-tenant goodput fraction, mean
        # round-lease wait, quota convergence error, every round.
        # Host-CPU leg like cycle_freshness; DCT_BENCH_TENANTS=0 skips
        # (the in-process smoke's knob).
        skip_tenants = os.environ.get(
            "DCT_BENCH_TENANTS", "1"
        ).strip().lower() in ("0", "false", "no")
        if not (skip_tenants or _gate("multi_tenant", frac=0.97)):
            record["multi_tenant"] = _optional(
                "multi_tenant", bench_multi_tenant, tmp
            )
            _flush_partial(record)

        # MPMD pipeline A/B (ISSUE 13): MPMD-1F1B on disjoint slices vs
        # the SPMD-GPipe lockstep program at matched P=2/M=8 — bubble
        # fraction both schedules + samples/s/chip. Subprocess-isolated
        # 2-device worlds like model_sharded; DCT_BENCH_MPMD=0 skips.
        skip_mpmd = os.environ.get(
            "DCT_BENCH_MPMD", "1"
        ).strip().lower() in ("0", "false", "no")
        if not (skip_mpmd or _gate("mpmd_pipeline", frac=0.97)):
            record["mpmd_pipeline"] = _optional(
                "mpmd_pipeline", bench_mpmd_pipeline
            )
            _flush_partial(record)

        # Telemetry history plane (ISSUE 17): armed-vs-plain snapshot
        # publish p50 + seconds from a planted slow_score fault to the
        # anomaly detector firing FROM the on-disk history, through the
        # real serving chain. Host-CPU leg like elastic_serving;
        # DCT_BENCH_TELEMETRY=0 skips (the in-process smoke's knob).
        skip_telemetry = os.environ.get(
            "DCT_BENCH_TELEMETRY", "1"
        ).strip().lower() in ("0", "false", "no")
        if not (skip_telemetry or _gate("telemetry_history", frac=0.97)):
            record["telemetry_history"] = _optional(
                "telemetry_history", bench_telemetry_history, tmp
            )
            _flush_partial(record)

        # Streaming ingest data plane (ISSUE 19): sustained events/s +
        # arrival→trainable lag p99 through the partitioned log and the
        # exactly-once stream ETL, vs the polling watcher moving the
        # same rows — plus the backpressure bounded-lag proof. Host-CPU
        # leg; DCT_BENCH_STREAM=0 skips (the streaming smoke's knob).
        skip_stream = os.environ.get(
            "DCT_BENCH_STREAM", "1"
        ).strip().lower() in ("0", "false", "no")
        if not (skip_stream or _gate("stream_ingest", frac=0.97)):
            record["stream_ingest"] = _optional(
                "stream_ingest", bench_stream_ingest, tmp
            )
            _flush_partial(record)

        # Low-precision A/Bs + gate safety net (ISSUE 20): int8/bf16
        # serving twins vs f32, bf16-dtype-rules train step vs f32, and
        # the quantized challenger's promote/block pair through the
        # real gate. Host-CPU leg (the serving twins are numpy; the
        # train A/B lowers locally); DCT_BENCH_LOWPREC=0 skips (the
        # lowprec smoke's knob, like DCT_BENCH_SCALED).
        skip_lowprec = os.environ.get(
            "DCT_BENCH_LOWPREC", "1"
        ).strip().lower() in ("0", "false", "no")
        if not (skip_lowprec or _gate("low_precision", frac=0.97)):
            record["low_precision"] = _optional(
                "low_precision", bench_low_precision, tmp
            )
            _flush_partial(record)

        if not _gate("host_dataplane"):
            dataplane = _optional(
                "host_dataplane", bench_host_dataplane
            )
            # Distinguish "ran, native lib absent" from the deadline-skip
            # null: the former means the numpy fallback IS the product
            # path, not that a bigger budget would produce numbers.
            record["host_dataplane"] = (
                dataplane
                if dataplane is not None
                else {"native": "unavailable"}
            )
            _flush_partial(record)

    # One null-marker pass for every skippable section: null means
    # "skipped this run" (deadline or DCT_BENCH_SCALED=0), never "not part
    # of this bench" — and the partial file must match the printed record.
    for skippable in (
        "scaled", "moe", "val_parity", "serving", "serving_load",
        "elastic_serving", "restart_spinup", "cycle_freshness",
        "model_sharded", "multi_tenant", "mpmd_pipeline",
        "telemetry_history", "stream_ingest", "low_precision",
        "host_dataplane", "roofline",
    ):
        record.setdefault(skippable, None)
    _flush_partial(record)
    # Same crash-proof serialization as the partials: the ONE deliverable
    # line must not die on a numpy scalar that leaked into a leg value.
    # Printed via _stdout_record: the digest keeps the line inside the
    # driver's 2,000-byte tail; the verbatim record is the partial above.
    print(json.dumps(_stdout_record(record), default=_json_default))


if __name__ == "__main__":
    main()

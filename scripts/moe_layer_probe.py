#!/usr/bin/env python3
"""What the grouped expert layer costs on the chip, at a cell's shape.

    chiprun -- python scripts/moe_layer_probe.py [--bound 2 4 8] [--chunk 1 2]

One process on one TPU chip: (1) ``chip_smoke.py``'s kernel check at the
attention shape of ``lfm2_24b_ep8.fit_seq8192_balanced`` (head size 64, 8,192
positions, full causal); (2) the grouped engine of one ``MoEFFN`` layer of
the cell (8 of 64 sigmoid-routed top-4 SwiGLU experts of 2048 x 1536 held,
8,192 tokens, bf16, the layer's own routing), forward and backward, host
clock around ``block_until_ready``, for each ``--bound`` (ONE static row
bound as a multiple of the uniform expectation; 8 = every row that can
come), with the three grouped products and the sort timed apart; (3) the
layer AS SHIPPED, which runs as many chunks of 8,192 sorted rows as hold
what was routed (``models/moe.py:_chunked_moe``), at the layer's own
routing and at synthetic loads of 1, 2 and all 4 chunks (a selection bias
that sends every token to some held experts, or keeps them off some), with
the rows it ran, the device's own time of the program (a profile's ``XLA
Modules`` line) and its temporary memory, and the same
engine at the same loads for each ``--chunk`` (the chunk as a multiple of
the uniform expectation; the layer runs 2); (4) whether an
executable that went through ``serialize`` / ``deserialize_and_load`` still
gives its HLO text with ``op_name``. Times are host-clock medians of single
calls unless the key says ``device``: for sizing a choice, not results.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def median_ms(fn, *args, reps: int = 10) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times)


def cell_layer(n: int):
    """One MoE layer of the cell, its seeded generator, ``n`` tokens and
    its parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dct_tpu.models.moe import MoEFFN

    layer = MoEFFN(
        d_model=2048, d_ff=1536, n_experts=64, aux_weight=0.0,
        dtype=jnp.bfloat16, dispatch="grouped", top_k=4,
        experts_held=8, first_expert=0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, n, 2048)), jnp.bfloat16)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]
    return layer, rng, x, params


def probe_layer(bound: float, n: int = 8192) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dct_tpu.models.moe import _grouped_moe

    # The layer gives its parameters and its routing; the engine is then
    # run under ``bound`` x the uniform expectation.
    layer, rng, x, params = cell_layer(n)
    _, sown = jax.jit(lambda p, x: layer.apply(
        {"params": p}, x, mutable=["counters", "intermediates"]))(params, x)
    topi = sown["intermediates"]["topk"][0]
    gates = jnp.full(topi.shape, 0.25, jnp.float32)
    row_bound = int(min(n * 4, bound * n * 4 * 8 / 64))

    def loss(p, x):
        w = [jnp.asarray(p[f"experts_{k}_kernel"], jnp.bfloat16)
             for k in ("gate", "in", "out")]
        out, rows, overflow = _grouped_moe(
            x[0], topi, gates, *w, first_expert=0, row_bound=row_bound)
        return (out ** 2).mean(), (rows, overflow)

    step = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))
    (gp, gx), (rows, overflow) = jax.block_until_ready(step(params, x))
    out = {
        "grads_finite": bool(all(
            np.isfinite(np.asarray(g, np.float32)).all()
            for g in jax.tree.leaves((gp, gx)))),
        "bound_over_uniform": bound,
        "row_bound": row_bound,
        "rows": np.asarray(rows).tolist(),
        "overflow": int(overflow),
        "engine_fwd_bwd_ms": median_ms(step, params, x),
        "engine_fwd_ms": median_ms(
            jax.jit(lambda p, x: loss(p, x)[0]), params, x),
    }
    # The grouped products alone, on rows already sorted: what a perfect
    # dispatch would leave.
    sizes = jnp.asarray(rows, jnp.int32)
    xs = jnp.asarray(rng.standard_normal((row_bound, 2048)), jnp.bfloat16)
    w = {k: jnp.asarray(v, jnp.bfloat16) for k, v in params.items()
         if k.startswith("experts_")}

    def products(xs, w):
        h = jax.nn.silu(jax.lax.ragged_dot(
            xs, w["experts_gate_kernel"], sizes)) * jax.lax.ragged_dot(
                xs, w["experts_in_kernel"], sizes)
        y = jax.lax.ragged_dot(h, w["experts_out_kernel"], sizes)
        return (y.astype(jnp.float32) ** 2).mean()

    out["products_fwd_bwd_ms"] = median_ms(
        jax.jit(jax.grad(products, argnums=(0, 1))), xs, w)
    routed = float(sizes.sum())
    out["products_peak_share"] = (
        18 * 2048 * 1536 * routed / (out["products_fwd_bwd_ms"] * 1e-3)
        / 197e12)
    # The sort alone.
    flat = jnp.asarray(rng.integers(0, 9, (n * 4,)), jnp.int32)
    out["argsort_ms"] = median_ms(
        jax.jit(lambda f: jnp.argsort(f, stable=True)), flat)
    return out


#: Synthetic loads: held experts (of 8) the selection bias keeps every
#: token off / sends every token to. 1, 1, 1, 2 and all 4 chunks of 8,192.
LOADS = {"few": (6, 0), "most": (2, 0), "own": (0, 0),
         "one_takes_all": (0, 1), "all": (0, 4)}


def biased(params, off: int, on: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    bias = np.zeros(64, np.float32)
    bias[8 - off:8] = -10.0
    bias[:on] = 10.0
    return jax.device_put({**params, "expert_bias": jnp.asarray(bias)})


def device_ms(fn, calls, reps: int = 3) -> list:
    """The device's own time of ``fn`` for each of ``calls`` (argument
    tuples, each already run once): the median of ``reps`` runs on the
    profiler's ``XLA Modules`` line of the first device."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as where:
        jax.profiler.start_trace(where)
        for args in calls:
            for _ in range(reps):
                jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        data = ProfileData.from_file(
            glob.glob(where + "/**/*.xplane.pb", recursive=True)[0])
    plane = next(p for p in data.planes if p.name.startswith("/device:"))
    line = next(ln for ln in plane.lines if ln.name == "XLA Modules")
    runs = [e.duration_ns / 1e6
            for e in sorted(line.events, key=lambda e: e.start_ns)]
    assert len(runs) == reps * len(calls), len(runs)
    return [statistics.median(runs[reps * i:reps * (i + 1)])
            for i in range(len(calls))]


def probe_shipped(n: int = 8192) -> list[dict]:
    """The layer as shipped, forward + backward through ``layer.apply``
    (router, top-k and sort included, as a step runs it), by load: the
    host's clock around the call and the device's own time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    layer, _, x, params = cell_layer(n)

    def loss(p, x):
        out, sown = layer.apply({"params": p}, x, mutable=["counters"])
        return (out.astype(jnp.float32) ** 2).mean(), sown["counters"]

    step = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))
    compiled = step.lower(params, x).compile()
    loads = {load: biased(params, off, on)
             for load, (off, on) in LOADS.items()}
    found = []
    for load, p in loads.items():
        _, counters = jax.block_until_ready(compiled(p, x))
        found.append({
            "load": load,
            "rows": int(counters["moe_rows"][0].sum()),
            "rows_bound": int(counters["moe_rows_bound"][0]),
            "overflow": int(counters["moe_rows_overflowed"][0]),
            "layer_fwd_bwd_ms": median_ms(compiled, p, x),
        })
    on_device = device_ms(compiled, [(p, x) for p in loads.values()])
    for one, ms, p in zip(found, on_device, loads.values()):
        one["layer_device_ms"] = ms
        one["grads_finite"] = bool(all(
            np.isfinite(np.asarray(g, np.float32)).all()
            for g in jax.tree.leaves(compiled(p, x)[0])))
    found.append({
        "program_temp_gb":
            compiled.memory_analysis().temp_size_in_bytes / 1e9,
        "process_peak_gb": jax.devices()[0].memory_stats()[
            "peak_bytes_in_use"] / 1e9,
    })
    return found


def probe_chunks(multiple: float, n: int = 8192) -> list[dict]:
    """The chunk loop alone (sort included, router and top-k not), forward
    + backward, its chunk ``multiple`` x the uniform expectation, by load."""
    import jax
    import jax.numpy as jnp

    from dct_tpu.models.moe import _chunked_moe

    layer, _, x, params = cell_layer(n)
    routing = jax.jit(lambda p, x: layer.apply(
        {"params": p}, x, mutable=["intermediates"])[1]["intermediates"])
    gates = jnp.full((n, 4), 0.25, jnp.float32)
    chunk = int(multiple * n * 4 * 8 / 64)

    def loss(p, x, topi):
        w = [jnp.asarray(p[f"experts_{k}_kernel"], jnp.bfloat16)
             for k in ("gate", "in", "out")]
        out, rows, bound, _ = _chunked_moe(
            x[0], topi, gates, *w, first_expert=0, chunk=chunk)
        return (out ** 2).mean(), (rows.sum(), bound)

    step = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))
    found = []
    for load, (off, on) in LOADS.items():
        topi = routing(biased(params, off, on), x)["topk"][0]
        _, (rows, bound) = step(params, x, topi)
        found.append({
            "chunk": chunk, "load": load, "rows": int(rows),
            "rows_bound": int(bound),
            "engine_fwd_bwd_ms": median_ms(step, params, x, topi),
        })
    return found


def probe_text() -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import serialize_executable as se

    def f(x):
        with jax.named_scope("probe.scope"):
            return jnp.tanh(x @ x)

    x = jnp.ones((512, 512), jnp.bfloat16)
    compiled = jax.jit(f).lower(x).compile()
    payload, in_tree, out_tree = se.serialize(compiled)
    loaded = se.deserialize_and_load(payload, in_tree, out_tree)
    return {
        "compiled_has_op_name": "probe.scope" in compiled.as_text(),
        "loaded_has_op_name": "probe.scope" in loaded.as_text(),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bound", type=float, nargs="*", default=[2, 4, 8])
    ap.add_argument("--chunk", type=float, nargs="*", default=[1, 2])
    ap.add_argument("--skip-kernel", action="store_true")
    args = ap.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("moe_layer_probe: needs a TPU", file=sys.stderr)
        return 2
    if not args.skip_kernel:
        import chip_smoke

        chip_smoke.phase_kernels([chip_smoke.FULL.kernels[-1]])
    print(json.dumps({"hlo_text": probe_text()}), flush=True)
    finite = True
    # The layer first: the process's peak memory only ever rises.
    for found in probe_shipped():
        finite &= found.get("grads_finite", True)
        print(json.dumps(found), flush=True)
    for c in args.chunk:
        for found in probe_chunks(c):
            print(json.dumps(found), flush=True)
    for c in args.bound:
        found = probe_layer(c)
        finite &= found["grads_finite"]
        print(json.dumps(found), flush=True)
    return 0 if finite else 1


if __name__ == "__main__":
    sys.exit(main())

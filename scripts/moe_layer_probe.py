#!/usr/bin/env python3
"""What the grouped expert layer costs on the chip, at a cell's shape.

    chiprun -- python scripts/moe_layer_probe.py [--bound 2 4 8]

One process on one TPU chip: (1) ``chip_smoke.py``'s kernel check at the
attention shape of ``lfm2_24b_ep8.fit_seq8192`` (head size 64, 8,192
positions, full causal); (2) the grouped engine of one ``MoEFFN`` layer of
the cell (8 of 64 sigmoid-routed top-4 SwiGLU experts of 2048 x 1536 held,
8,192 tokens, bf16, the layer's own routing), forward and backward, host
clock around ``block_until_ready``, for each ``--bound`` (the grouped
products' row bound as a multiple of the uniform expectation; the layer
runs 8 = every row that can come), with the three grouped products and the
sort timed apart; (3) whether an
executable that went through ``serialize`` / ``deserialize_and_load`` still
gives its HLO text with ``op_name``. Times are host-clock medians of single
calls: for sizing a choice, not results.
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


def probe_layer(bound: float, n: int = 8192) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dct_tpu.models.moe import MoEFFN, _grouped_moe

    # The layer gives its parameters and its routing; the engine is then
    # run under ``bound`` x the uniform expectation (the layer itself runs
    # the N x 4 rows that can come: 8x here).
    layer = MoEFFN(
        d_model=2048, d_ff=1536, n_experts=64, aux_weight=0.0,
        dtype=jnp.bfloat16, dispatch="grouped", top_k=4,
        experts_held=8, first_expert=0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, n, 2048)), jnp.bfloat16)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]
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
    ap.add_argument("--bound", type=float, nargs="+", default=[2, 4, 8])
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
    for c in args.bound:
        found = probe_layer(c)
        finite &= found["grads_finite"]
        print(json.dumps(found), flush=True)
    return 0 if finite else 1


if __name__ == "__main__":
    sys.exit(main())

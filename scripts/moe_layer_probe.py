#!/usr/bin/env python3
"""What the grouped expert layer costs on the chip, at a cell's shape.

    chiprun -- python scripts/moe_layer_probe.py [--cell lfm2|mellum2] \
        [--bound 2 4 8] [--chunk 1.25 2] [--root build/parent]

One process on one TPU chip: (1) ``chip_smoke.py``'s kernel check at the
attention shapes of the cell (``lfm2_24b_ep8.fit_seq8192_balanced``: head
size 64, 8,192 positions, full causal; ``mellum2_12b.fit_seq8192``: 32 / 4
heads of 128 under the 1,024 window and under none); (2) the grouped engine
of one ``MoEFFN`` layer of the cell (``CELLS``: lfm2 holds 8 of 64
sigmoid-routed top-4 SwiGLU experts of 2048 x 1536, mellum2 16 of 64
softmax-routed top-8 experts of 2304 x 896;
8,192 tokens, bf16, the layer's own routing), forward and backward, host
clock around ``block_until_ready``, for each ``--bound`` (ONE static row
bound as a multiple of the uniform expectation; 8 = every row that can
come), with the three grouped products and the sort timed apart; (3) the
layer AS SHIPPED, which runs as many chunks of 5,120 sorted rows (1.25 x
the even share; 20,480 in mellum2) as hold
what was routed (``models/moe.py:_chunked_moe``), at the layer's own
routing and at synthetic loads of one, two and every chunk (``CELLS``: a
constant input feature and a router row that sends every token to some
held experts, or keeps them off some), with the rows it ran, the device's
own time of the program (a profile's ``XLA Modules`` line) and its
temporary memory, and the same
engine at the same loads for each ``--chunk`` (the chunk as a multiple of
the uniform expectation; the layer runs 1.25, and ran 2 until PR 36); (4)
the combine alone at the layer's own routing and each ``--chunk``, device
time: the chunk's rows scatter-added by token (what the layer runs)
against each token's k rows gathered through the sort's inverse and
summed (k takes of [N, D], or one take of [N x k, D]; PR 36 measured this
form and withdrew it, PERF.md section 6), forward (gates, f32)
and as the tokens' cotangent, the gates' cotangent both ways, and the ways
to that inverse (a second ``argsort``, the stable rank by counting, an
integer scatter) beside the sort itself; (5) whether an
executable that went through ``serialize`` / ``deserialize_and_load`` still
gives its HLO text with ``op_name``. ``--root DIR`` runs (3) alone on the
tree unpacked at DIR (the parent's ``git archive``), for parent against
change in one call. Times are host-clock medians of single
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


#: One routed layer of a cell, 64 experts wide: (width, experts' width,
#: experts a token, experts held, scoring, the cell's kernel cases in
#: ``chip_smoke.FULL.kernels``, and the synthetic loads: held experts the
#: router keeps every token off / sends every token to. lfm2: 1, 1, 2, 3
#: and all 7 chunks of 5,120 (the parent: 1, 1, 2, 2 and 4 of 8,192);
#: mellum2: 1, 1, 2 and all 4 chunks of 20,480 (1, 1, 2 and 2 of 32,768).
CELLS = {
    "lfm2": dict(d=2048, f=1536, k=4, held=8, scoring="sigmoid",
                 kernels=("lfm2_seq8192",),
                 loads={"few": (6, 0), "own": (0, 0), "two": (4, 1),
                        "one_takes_all": (0, 1), "all": (0, 4)}),
    "mellum2": dict(d=2304, f=896, k=8, held=16, scoring="softmax",
                    kernels=("mellum2_swa_seq8192", "mellum2_full_seq8192"),
                    loads={"few": (12, 0), "own": (0, 0), "half": (0, 4),
                           "all": (0, 8)}),
}
CELL = CELLS["lfm2"]  # main() sets it from --cell


def cell_layer(n: int):
    """One MoE layer of the cell, its seeded generator, ``n`` tokens and
    its parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dct_tpu.models.moe import MoEFFN

    layer = MoEFFN(
        d_model=CELL["d"], d_ff=CELL["f"], n_experts=64, aux_weight=0.0,
        dtype=jnp.bfloat16, dispatch="grouped", top_k=CELL["k"],
        experts_held=CELL["held"], first_expert=0, scoring=CELL["scoring"])
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, n, CELL["d"]))
    x[..., 0] = 8.0  # what :func:`steered` pulls the router by
    x = jnp.asarray(x, jnp.bfloat16)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]
    return layer, rng, x, steered(params, 0, 0)


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
    k, held = CELL["k"], CELL["held"]
    gates = jnp.full(topi.shape, 1.0 / k, jnp.float32)
    row_bound = int(min(n * min(k, held), bound * n * k * held / 64))

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
    xs = jnp.asarray(
        rng.standard_normal((row_bound, CELL["d"])), jnp.bfloat16)
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
        18 * CELL["d"] * CELL["f"] * routed
        / (out["products_fwd_bwd_ms"] * 1e-3)
        / 197e12)
    # The sort alone.
    flat = jnp.asarray(rng.integers(0, held + 1, (n * k,)), jnp.int32)
    out["argsort_ms"] = median_ms(
        jax.jit(lambda f: jnp.argsort(f, stable=True)), flat)
    return out


def steered(params, off: int, on: int) -> dict:
    """``params`` with a router that keeps every token off the last
    ``off`` held experts and sends every token to the first ``on``: the
    row of the router's kernel that meets the inputs' constant feature,
    zero elsewhere, so (0, 0) is the router's own load."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    held = CELL["held"]
    pull = np.zeros(64, np.float32)
    pull[held - off:held] = -4.0
    pull[:on] = 4.0
    kernel = jnp.asarray(params["router"]["kernel"]).at[0].set(pull)
    return jax.device_put({**params, "router": {"kernel": kernel}})


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
    by_load = {load: steered(params, off, on)
               for load, (off, on) in CELL["loads"].items()}
    found = []
    for load, p in by_load.items():
        _, counters = jax.block_until_ready(compiled(p, x))
        found.append({
            "load": load,
            "rows": int(counters["moe_rows"][0].sum()),
            "rows_bound": int(counters["moe_rows_bound"][0]),
            "overflow": int(counters["moe_rows_overflowed"][0]),
            "layer_fwd_bwd_ms": median_ms(compiled, p, x),
        })
    on_device = device_ms(compiled, [(p, x) for p in by_load.values()])
    for one, ms, p in zip(found, on_device, by_load.values()):
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
    k, held = CELL["k"], CELL["held"]
    gates = jnp.full((n, k), 1.0 / k, jnp.float32)
    chunk = int(multiple * n * k * held / 64)

    def loss(p, x, topi):
        w = [jnp.asarray(p[f"experts_{k}_kernel"], jnp.bfloat16)
             for k in ("gate", "in", "out")]
        out, rows, bound, _ = _chunked_moe(
            x[0], topi, gates, *w, first_expert=0, chunk=chunk)
        return (out ** 2).mean(), (rows.sum(), bound)

    step = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))
    found = []
    for load, (off, on) in CELL["loads"].items():
        topi = routing(steered(params, off, on), x)["topk"][0]
        _, (rows, bound) = step(params, x, topi)
        found.append({
            "chunk": chunk, "load": load, "rows": int(rows),
            "rows_bound": int(bound),
            "engine_fwd_bwd_ms": median_ms(step, params, x, topi),
        })
    return found


def probe_combine(multiple: float, n: int = 8192) -> list[dict]:
    """The combine alone, at the layer's own routing and one chunk of
    ``multiple`` x the even share, device time: the row scatter-adds the
    layer runs against gathers through the sort's inverse (measured and
    withdrawn in PR 36), and the ways to that inverse. Written out here,
    not imported, so the forms stay comparable whatever the layer ships."""
    import jax
    import jax.numpy as jnp

    layer, rng, x, params = cell_layer(n)
    topi = jax.jit(lambda p, x: layer.apply(
        {"params": p}, x, mutable=["intermediates"])[1]["intermediates"])(
            params, x)["topk"][0]
    k, held, d = CELL["k"], CELL["held"], CELL["d"]
    chunk = int(multiple * n * k * held / 64)
    flat = jnp.where(topi < held, topi, held).reshape(-1).astype(jnp.int32)
    order = jnp.argsort(flat, stable=True)
    place = jnp.argsort(order).reshape(n, k)
    routed = int((flat < held).sum())
    row = order[:chunk]
    valid = (jnp.arange(chunk) < routed)[:, None]
    y = jnp.where(valid, jnp.asarray(
        rng.standard_normal((chunk, d)), jnp.bfloat16), 0)
    gates = jnp.asarray(rng.random((n, k)), jnp.float32)
    d_gate = jnp.where(valid[:, 0], jnp.asarray(
        rng.standard_normal(chunk), jnp.float32), 0)

    def slots(place):
        here = place < chunk
        return here, jnp.clip(place, 0, chunk - 1)

    def scatter_out(y, row, gates):
        return jnp.zeros((n, d), jnp.float32).at[row // k].add(
            y.astype(jnp.float32) * gates.reshape(-1)[row][:, None])

    def k_takes(a, place, gates=1.0):
        here, at = slots(place)
        w = jnp.where(here, gates, 0)
        return sum(w[:, j, None] * a[at[:, j]].astype(jnp.float32)
                   for j in range(k))

    def one_take(a, place, gates=1.0):
        here, at = slots(place)
        rows = a[at.reshape(-1)].reshape(n, k, d).astype(jnp.float32)
        return (rows * jnp.where(here, gates, 0)[:, :, None]).sum(1)

    def scatter_tokens(dx, row):
        return jnp.zeros((n, d), dx.dtype).at[row // k].add(dx)

    def scatter_gates(d_gate, row):
        return jnp.zeros(n * k, jnp.float32).at[row].add(d_gate)

    def take_gates(d_gate, place):
        here, at = slots(place)
        return jnp.where(here, d_gate[at], 0)

    def counted(flat, axis):
        # The stable rank by counting: a row's place is its expert's first
        # row plus the rows of that expert before it. No sort, no scatter.
        experts = jnp.arange(held + 1, dtype=jnp.int32)
        hot = (jnp.expand_dims(flat, 1 - axis)
               == jnp.expand_dims(experts, axis)).astype(jnp.int32)
        before = jnp.cumsum(hot, axis=axis) - hot
        counts = hot.sum(axis=axis, keepdims=True)
        starts = jnp.cumsum(counts, axis=1 - axis) - counts
        return ((before + starts) * hot).sum(axis=1 - axis)

    forms = {
        "out_scatter_add": (scatter_out, (y, row, gates)),
        "out_k_takes": (k_takes, (y, place, gates)),
        "out_one_take": (one_take, (y, place, gates)),
        "tokens_scatter_add": (scatter_tokens, (y, row)),
        "tokens_k_takes": (
            lambda dx, place: k_takes(dx, place).astype(dx.dtype),
            (y, place)),
        "tokens_one_take": (
            lambda dx, place: one_take(dx, place).astype(dx.dtype),
            (y, place)),
        "gates_scatter_add": (scatter_gates, (d_gate, row)),
        "gates_take": (take_gates, (d_gate, place)),
        "sort": (lambda f: jnp.argsort(f, stable=True), (flat,)),
        "inverse_argsort": (jnp.argsort, (order,)),
        "inverse_counted_rows": (lambda f: counted(f, 0), (flat,)),
        "inverse_counted_lanes": (lambda f: counted(f, 1), (flat,)),
        "inverse_scatter": (
            lambda o: jnp.zeros(n * k, jnp.int32).at[o].set(
                jnp.arange(n * k, dtype=jnp.int32)), (order,)),
    }
    found = [{"n": n, "k": k, "d": d, "chunk": chunk, "routed": routed}]
    results = {}
    for name, (fn, args) in forms.items():
        compiled = jax.jit(fn).lower(*args).compile()
        results[name] = jax.block_until_ready(compiled(*args))
        found.append({
            "form": name,
            "device_ms": device_ms(compiled, [args], reps=5)[0],
            "temp_mb": compiled.memory_analysis().temp_size_in_bytes / 1e6,
        })
    # Every form of one quantity gives the same thing.
    for same in (("out_scatter_add", "out_k_takes", "out_one_take"),
                 ("tokens_scatter_add", "tokens_k_takes", "tokens_one_take"),
                 ("gates_scatter_add", "gates_take"),
                 ("inverse_argsort", "inverse_counted_rows",
                  "inverse_counted_lanes", "inverse_scatter")):
        base, *others = (
            results[name].astype(jnp.float32).reshape(-1) for name in same)
        found.append({"agree": same, "max_abs_difference": [
            float(jnp.abs(other - base).max()) for other in others]})
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
    ap.add_argument("--chunk", type=float, nargs="*", default=[1.25, 2])
    ap.add_argument("--skip-kernel", action="store_true")
    ap.add_argument("--cell", choices=list(CELLS), default="lfm2")
    ap.add_argument("--root", help="a tree to probe as shipped, alone")
    args = ap.parse_args()
    global CELL
    CELL = CELLS[args.cell]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("moe_layer_probe: needs a TPU", file=sys.stderr)
        return 2
    if args.root:
        sys.path.insert(0, os.path.abspath(args.root))
        for found in probe_shipped():
            print(json.dumps({"root": args.root, **found}), flush=True)
        return 0
    if not args.skip_kernel:
        import chip_smoke

        chip_smoke.phase_kernels([
            case for case in chip_smoke.FULL.kernels
            if case[0] in CELL["kernels"]])
    print(json.dumps({"hlo_text": probe_text()}), flush=True)
    finite = True
    # The layer first: the process's peak memory only ever rises.
    for found in probe_shipped():
        finite &= found.get("grads_finite", True)
        print(json.dumps(found), flush=True)
    for c in args.chunk:
        for found in probe_combine(c) + probe_chunks(c):
            print(json.dumps(found), flush=True)
    for c in args.bound:
        found = probe_layer(c)
        finite &= found["grads_finite"]
        print(json.dumps(found), flush=True)
    return 0 if finite else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Time the three Mosaic flash kernels over a grid of tiles, on the chip.

    chiprun -- python scripts/flash_tile_sweep.py [--root build/parent]
    JAX_PLATFORMS=cpu python scripts/flash_tile_sweep.py --compile-only

The sweep behind ``pallas_attention.flash_tiles`` (PERF.md section 6, PR
26): for each shape (the benchmark's two by default) and each
``(block_q, block_k)`` it compiles the forward (with lse), the dK/dV and
the dQ kernel, runs each ``--iters`` times and prints one JSON line per
shape with the median milliseconds per kernel. A tile the compiler refuses
(alignment, scoped VMEM) is recorded as the refusal, not as a time.

``--root`` imports ``dct_tpu`` from another checkout (the parent commit,
unpacked with ``git archive``): a tree whose backward is one entry point
(``_flash_bwd``, both kernels on one tile) is timed as ``bwd``.
``--always-mask`` times the change's kernels with the mask built on every
working tile, as the parent builds it: the A/B for the interior-tile skip.
``--compile-only`` compiles for a described v5e without a chip and times
nothing; it says which tiles Mosaic accepts. Times come only from a chip
run: without ``--compile-only`` the script refuses any other platform.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

#: (b, q heads, kv heads, T, d, window[, value width]): the benchmark's 4k
#: and 512 cells (the default), the lfm2 cell's attention, and latent
#: attention at 8,192 positions (PERF.md section 6, PR 32): queries and
#: keys 192 wide against values of 128 as the kernels take them, the same
#: operands zero-padded to the next 128 (256 / 128) and to one width of 256
#: for all three, which is what a kernel with one width would have to run.
SHAPES = {
    "seq4096": (2, 24, 2, 4096, 128, 4096),
    "seq512": (16, 24, 2, 512, 128, 4096),
    "lfm2_seq8192": (1, 32, 8, 8192, 64, None),
    "mla_192_128": (1, 16, 16, 8192, 192, None, 128),
    "mla_256_128": (1, 16, 16, 8192, 256, None, 128),
    "mla_256_256": (1, 16, 16, 8192, 256, None, 256),
}
DEFAULT_SHAPES = ("seq4096", "seq512")
TILES = (128, 256, 512, 1024)


def _kernels(pa, window, bq, bk):
    """name -> (fn, argument names) for the tile pair, on whichever entry
    points the imported tree has."""
    kw = dict(block_q=bq, block_k=bk, causal=True, scale=None,
              interpret=False, window=window)
    out = {"fwd": (
        lambda q, k, v: pa._flash_fwd(q, k, v, with_lse=True, **kw),
        ("q", "k", "v"),
    )}
    args = ("q", "k", "v", "o", "lse", "do")
    if hasattr(pa, "_flash_bwd_dkdv"):
        out["dkdv"] = (
            lambda *a: pa._flash_bwd_dkdv(*a, **kw), args)
        out["dq"] = (lambda *a: pa._flash_bwd_dq(*a, **kw), args)
    else:
        out["bwd"] = (lambda *a: pa._flash_bwd(*a, **kw), args)
    return out


def _mask_every_tile(pa) -> None:
    """Replace the change's tile dispatch by the parent's: one branch, the
    mask on every tile that has a visible key."""
    from jax.experimental import pallas as pl

    def run_tile(block, causal, q_first, bq, k_first, bk, window):
        if not causal:
            block(None)
            return
        work, _ = pa._tile_visibility(q_first, bq, k_first, bk, window)
        pl.when(work)(
            lambda: block(pa._tile_keep(q_first, bq, k_first, bk, window)))

    pa._run_tile = run_tile


def sweep(shape_name: str, tiles, iters: int, compile_only: bool,
          always_mask: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dct_tpu.ops import pallas_attention as pa

    if always_mask:
        _mask_every_tile(pa)

    b, h, h_kv, t, d, window, *rest = SHAPES[shape_name]
    d_v = rest[0] if rest else d
    shapes = {
        "q": ((b, h, t, d), jnp.bfloat16), "k": ((b, h_kv, t, d), jnp.bfloat16),
        "v": ((b, h_kv, t, d_v), jnp.bfloat16),
        "o": ((b, h, t, d_v), jnp.bfloat16),
        "do": ((b, h, t, d_v), jnp.bfloat16), "lse": ((b, h, t), jnp.float32),
    }
    if compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])
        arrays = {
            n: jax.ShapeDtypeStruct(s, dt, sharding=chip)
            for n, (s, dt) in shapes.items()
        }
    else:
        rng = np.random.default_rng(0)
        arrays = {
            n: jnp.asarray(rng.standard_normal(s), dt)
            for n, (s, dt) in shapes.items() if n not in ("o", "lse")
        }
        arrays["o"], arrays["lse"] = jax.block_until_ready(jax.jit(
            lambda q, k, v: pa._flash_fwd(
                q, k, v, block_q=128, block_k=128, causal=True, scale=None,
                interpret=False, with_lse=True, window=window)
        )(arrays["q"], arrays["k"], arrays["v"]))
    rows = {}
    for bq in tiles:
        for bk in tiles:
            if t % bq or t % bk:
                continue
            row = {}
            for name, (fn, names) in _kernels(pa, window, bq, bk).items():
                args = [arrays[n] for n in names]
                try:
                    compiled = jax.jit(fn).lower(*args).compile()
                except Exception as e:  # noqa: BLE001 - the refusal is the result
                    msg = str(e)
                    at = msg.find("exceed")
                    row[name] = "refused: " + (
                        msg[max(0, at - 120):at + 160] if at >= 0
                        else msg[:240]).replace("\n", " ")
                    continue
                if compile_only:
                    row[name] = "compiles"
                    continue
                jax.block_until_ready(compiled(*args))
                times = []
                for _ in range(iters):
                    t0 = time.perf_counter()
                    jax.block_until_ready(compiled(*args))
                    times.append((time.perf_counter() - t0) * 1e3)
                row[name] = round(statistics.median(times), 3)
            rows[f"{bq}x{bk}"] = row
            print(f"[sweep] {shape_name} {bq}x{bk} {row}",
                  file=sys.stderr, flush=True)
    return {"shape": shape_name, "dims": SHAPES[shape_name], "ms": rows}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=None,
                    help="import dct_tpu from this checkout instead")
    ap.add_argument("--shapes", nargs="+", default=list(DEFAULT_SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--tiles", type=int, nargs="+", default=list(TILES))
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--always-mask", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    root = os.path.abspath(args.root or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".."))
    sys.path.insert(0, root)
    if args.compile_only:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    platform = jax.devices()[0].platform
    if not args.compile_only and platform != "tpu":
        print(f"flash_tile_sweep times kernels on a TPU; JAX selected "
              f"{platform!r} (use --compile-only off the chip)",
              file=sys.stderr)
        return 2
    device = {"platform": platform, "kind": jax.devices()[0].device_kind}
    lines = []
    for name in args.shapes:
        res = sweep(name, args.tiles, args.iters, args.compile_only,
                    args.always_mask)
        res.update(device=device, root=root, compile_only=args.compile_only,
                   always_mask=args.always_mask)
        lines.append(json.dumps(res))
        print(lines[-1], flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

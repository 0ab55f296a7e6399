"""Pallas TPU flash-attention kernel: the single-chip attention hot path.

The JAX-level paths in :mod:`dct_tpu.ops.attention` rely on XLA fusion;
this kernel takes manual control of the memory hierarchy per the Pallas TPU
playbook. The grid is ``(batch*heads, q_blocks, kv_blocks)`` with the KV
block as the innermost (sequential) dimension, so VMEM residency per grid
step is one ``[block_q, D]`` Q tile plus one ``[block_k, D]`` K/V tile —
O(block) regardless of sequence length — while the online-softmax running
stats (m, l, acc) persist in VMEM scratch across the KV sweep. The score
matrix never exists in HBM, so memory is O(T·D) instead of O(T²); with
``causal=True`` KV blocks entirely above the diagonal skip their MXU work,
and with ``window`` set the sliding-window band also skips every block
entirely behind the band (compute AND DMA, in forward and both backward
kernels) — O(T·window) FLOPs instead of the causal O(T²/2). ``q_offset``
statically shifts the q positions so the windowed ring's partial-band
shards (q-k distance = step·T_local) reuse the same kernel.

The tiles are a function of the shape (:func:`flash_tiles`): the largest
multiple of 128 that divides the sequence extent, up to a cap
measured on the chip (1024 at head size 128 in bf16: 1024 x 1024 at 4,096
positions, one 512 x 512 tile at 512). A grid step costs a fixed ~0.35 us
and the per-step bookkeeping is per q row, so a step has to carry enough
MXU work to bury both; at 128 x 128 the kernels spent nine tenths of their
time on neither matmuls nor softmax (PERF.md section 6, PR 26). The mask
(two iotas, the compares, two selects over the f32 score tile) is built
only on tiles the diagonal or the band's trailing edge cuts; an interior
tile, where every pair is visible, runs the same math without it.

The running stats use the same online update as
:func:`dct_tpu.ops.attention._online_block`; they are re-expressed here in
2-D keepdims layout ([block_q, 1] rows, lane-broadcast scratch tiles)
because Mosaic wants >=2-D vector layouts in VMEM — tests pin the two
implementations to the same dense oracle so they cannot drift silently.

Backward is a pair of FlashAttention-2-style Pallas kernels (dK/dV with
the Q sweep innermost, dQ with the KV sweep innermost): the forward saves
only (q, k, v, o, lse) and each backward block recovers its softmax
weights from the lse — O(T·D) memory end to end, with ``delta`` =
rowsum(dO⊙O) recomputed in-kernel rather than shipped through HBM.
``DCT_FLASH_BWD=remat`` swaps in the older differentiate-through-
blockwise escape hatch.

CPU rigs run the same kernel with ``interpret=True`` (tests); on TPU it
compiles to Mosaic. Reference note: the reference has no kernels of any
kind (pure torch CPU, SURVEY §2.2) — this file is capability the TPU build
adds at the layer the reference delegates to libtorch.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dct_tpu.ops.attention import _NEG

# Lane width of the m/l scratch tiles: the stats are per-Q-row scalars, but
# Mosaic lays vectors out in (sublane, lane) tiles, so they live broadcast
# across a full 128-lane row (the official TPU flash kernels do the same).
_STATS_LANES = 128


def _kv_flat_row(bh, h: int, h_kv: int):
    """Flat [b*h] Q row -> flat [b*h_kv] KV row under the group-major GQA
    layout (q head g*group + j reads kv head g). The single source of the
    head mapping for the forward AND backward kernels' index maps."""
    if h == h_kv:
        return bh
    group = h // h_kv
    return (bh // h) * h_kv + (bh % h) // group


def _compiler_params():
    """Shared grid semantics for all three kernels: dims 0/1 are
    parallel (each (row, block) instance owns its scratch lifecycle —
    init at its inner sweep's first step, finalize at its last), only
    the innermost accumulation sweep is order-dependent. One helper so
    forward and backward cannot drift."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


#: Largest tile side, measured on a v5e at head size 128 in bf16 (PERF.md
#: section 6, PR 26: the sweep over {128..2048}^2 at T=4096 and T=512,
#: each kernel timed alone — the forward, dK/dV and dQ all came out at
#: the same cap, for block_q and block_k alike). A grid step costs
#: ~0.35 us whatever it holds, and the per-step bookkeeping (accumulator
#: rescale, lane-broadcast stats, the backward's delta) is per q row, so
#: the wider the tile the smaller its share; past the cap the causal
#: diagonal's wasted half-tiles cost more than that saves, and the f32
#: score temporaries outgrow the 16 MiB of scoped VMEM.
_TILE_CAP = 1024
#: Bytes of one operand row (d * itemsize) the cap was measured at.
_CAP_ROW_BYTES = 128 * 2


def _largest_tile(n: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``n``, at most ``cap``.
    An extent that is no multiple of 128 gets ``min(n, 128)``: a short
    sequence is one tile, and a long unaligned one is refused by the
    kernel's own divisibility check ("pad upstream")."""
    if n % 128:
        return min(n, 128)
    return max(c for c in range(128, min(n, cap) + 1, 128) if n % c == 0)


def _lanes(width: int) -> int:
    """The lanes a row of ``width`` elements takes in VMEM once it is
    wider than one 128-lane tile: whole tiles (192 -> 256)."""
    return width if width <= 128 else -(-width // 128) * 128


def flash_tiles(t: int, tk: int, d: int, dtype,
                d_v: int | None = None) -> tuple[int, int]:
    """``(block_q, block_k)`` of the three kernels from what they can see:
    the two sequence extents, the head size and the operand dtype. Pure,
    static per shape, no knob: the largest multiple of 128 that divides
    the extent, up to the measured cap; the cap halves for every doubling
    of the operand row's bytes over the measured 256: the streamed tiles
    and the f32 accumulators grow with d, the scoped VMEM does not, and
    at the measured row 1024 x 1024 is the last pair that fits.

    Where the values are not as wide as the queries and keys (``d_v``;
    latent attention), the row is the mean of the two widths, each in
    the whole lane tiles it takes in VMEM once over 128 (192 -> 256): a
    kernel streams and accumulates as many value-wide operands as
    query-wide ones. Measured at (1, 16, 8192) in bf16 (PERF.md section
    6, PR 32): 192 and 256 against values of 128 (a row of 1.5 x the
    measured one) compile and run fastest at 1024 x 1024 in all three
    kernels; 256 against 256 (2 x) is refused there by 312 KB of scoped
    VMEM in dK/dV, hence the halving at the doubling and not before it."""
    row_bytes = (_lanes(d) + _lanes(d if d_v is None else d_v)) / 2 \
        * jnp.dtype(dtype).itemsize
    cap = _TILE_CAP
    while cap > 128 and row_bytes >= 2 * _CAP_ROW_BYTES * (_TILE_CAP // cap):
        cap //= 2
    return _largest_tile(t, cap), _largest_tile(tk, cap)


def _resolve_tiles(block_q, block_k, t: int, tk: int, d: int, dtype,
                   d_v: int | None = None) -> tuple[int, int]:
    """The tiles a kernel runs on: explicit ``block_q`` / ``block_k``
    (tests, sweeps) win over :func:`flash_tiles`, clamped to the extents;
    a tile that does not divide its extent is refused."""
    rule_q, rule_k = flash_tiles(t, tk, d, dtype, d_v)
    block_q = rule_q if block_q is None else min(block_q, t)
    block_k = rule_k if block_k is None else min(block_k, tk)
    if t % block_q or tk % block_k:
        raise ValueError(
            f"seq lens q={t}, kv={tk} must be multiples of "
            f"block_q={block_q} and block_k={block_k} (pad upstream)"
        )
    return block_q, block_k


def _tile_visibility(q_first, bq: int, k_first, bk: int, window):
    """For the causal (banded) tile of q rows ``[q_first, q_first+bq)``
    against keys ``[k_first, k_first+bk)``: ``(work, cut)``. ``work``:
    some key is visible to some row, else the tile is skipped whole.
    ``cut``: some (row, key) pair is masked — the diagonal or the band's
    trailing edge crosses the tile — so the mask must be built; an
    interior tile (work and not cut) needs no mask at all. The single
    source of both conditions for the three kernels."""
    q_last = q_first + bq - 1
    k_last = k_first + bk - 1
    work = k_first <= q_last
    cut = k_last > q_first
    if window is not None:
        work &= q_first - k_last < window
        cut |= q_last - k_first >= window
    return work, cut


def _tile_keep(q_first, bq: int, k_first, bk: int, window):
    """The [bq, bk] visibility mask of a tile the diagonal or the band
    cuts: attend iff 0 <= q_pos - k_pos (< window)."""
    q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = q_pos >= k_pos
    if window is not None:
        keep &= q_pos - k_pos < window
    return keep


def _run_tile(block, causal: bool, q_first, bq: int, k_first, bk: int,
              window):
    """Run ``block(keep)`` for one grid step: not at all on a tile with
    no visible key, with the mask on a tile the diagonal or the band's
    trailing edge cuts, and with ``keep=None`` (no iotas, compares or
    selects) on an interior tile, where every pair is visible."""
    if not causal:
        block(None)
        return
    work, cut = _tile_visibility(q_first, bq, k_first, bk, window)
    pl.when(work & cut)(
        lambda: block(_tile_keep(q_first, bq, k_first, bk, window))
    )
    pl.when(work & jnp.logical_not(cut))(lambda: block(None))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, block_k: int,
                      n_kv: int, causal: bool, scale: float,
                      with_lse: bool, window: int | None = None,
                      q_offset: int = 0):
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    qi = pl.program_id(1)
    j = pl.program_id(2)
    bq = q_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _block(keep):
        # MXU operands stay in the INPUT dtype (bf16 on the product
        # path): upcasting q/k/v to f32 before the dots would run the
        # matmuls at f32 MXU rate — a fraction of bf16 throughput, and
        # the likely reason the kernel lost to XLA blockwise on-chip in
        # r2. bf16xbf16 products accumulate in f32 on the MXU (each
        # product is exactly representable), so only the p·V cast below
        # changes numerics, the same trade the official TPU flash
        # kernels make. The scale moves AFTER the dot so it applies in
        # f32 regardless of input dtype.
        q = q_ref[...]  # [bq, D]
        k = k_ref[...]  # [block_k, D]
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, block_k] f32
        if keep is not None:
            s = jnp.where(keep, s, _NEG)
        m_prev = m_ref[:, :1]  # [bq, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if keep is not None:
            # A fully-masked row would otherwise get p=exp(0)=1 per entry
            # (same guard as attention._online_block).
            p = jnp.where(keep, p, 0.0)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # A KV tile entirely above the diagonal, or entirely behind the
    # window's band, skips all compute (its DMA is elided too — the index
    # map refetches the resident block): this is where windowed flash
    # recovers O(T*window) FLOPs from the O(T^2) causal sweep.
    # ``q_offset`` shifts the q positions (the windowed ring's static
    # inter-shard distance); k positions stay 0-based.
    _run_tile(_block, causal, q_offset + qi * bq, bq, j * block_k, block_k,
              window)

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
        if with_lse:
            # log-sum-exp per Q row, lane-broadcast ([block_q, LANES] like
            # the running stats) — callers slice lane 0.
            lse_ref[...] = jnp.broadcast_to(
                m_ref[:, :1] + jnp.log(jnp.maximum(l, 1e-20)), lse_ref.shape
            )


def _flash_fwd(q, k, v, *, block_q: int | None = None,
               block_k: int | None = None, causal: bool,
               scale: float | None, interpret: bool, with_lse: bool = False,
               window: int | None = None, q_offset: int = 0):
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    tk = k.shape[2]  # rectangular Tq != Tk supported (striped ring blocks)
    d_v = v.shape[3]  # values (and o) may be narrower or wider than q/k
    if causal and tk != t:
        raise ValueError(
            f"causal flash needs square Tq==Tk, got {t} vs {tk}"
        )
    if window is not None and not causal:
        raise ValueError("flash window requires causal attention")
    if q_offset and not causal:
        # The offset only participates in the causal position math; a
        # non-causal caller would silently get unshifted full attention.
        raise ValueError("flash q_offset requires causal attention")
    if h % h_kv:
        raise ValueError(
            f"GQA needs q heads ({h}) divisible by kv heads ({h_kv})"
        )
    # GQA: KV stay at their n_kv_heads in HBM — the grid runs per Q head
    # and the KV index maps divide by the group size, so each KV head's
    # tiles are fetched once per group sweep instead of being repeated
    # H/h_kv times through memory.
    group = h // h_kv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q, block_k = _resolve_tiles(
        block_q, block_k, t, tk, d, q.dtype, d_v)
    n_kv = tk // block_k
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h_kv, tk, d)
    vf = v.reshape(b * h_kv, tk, d_v)
    kernel = functools.partial(
        _flash_fwd_kernel, block_k=block_k, n_kv=n_kv, causal=causal,
        scale=scale, with_lse=with_lse, window=window, q_offset=q_offset,
    )
    def kv_bh(bh):
        return _kv_flat_row(bh, h, h_kv)

    if causal:
        # Skipped blocks would otherwise still be DMA'd: clamp the index
        # map so they re-address a needed block (already resident -> the
        # fetch is elided). Above-diagonal blocks clamp down (~half the
        # KV HBM traffic for plain causal); with a window, behind-the-band
        # blocks also clamp up, so KV traffic is O(T*window/block) total.
        def kv_index(bh, i, j):
            last_needed = (q_offset + (i + 1) * block_q - 1) // block_k
            jj = jnp.minimum(j, last_needed)
            if window is not None:
                first_needed = jnp.maximum(
                    0, (q_offset + i * block_q - window + 1) // block_k
                )
                jj = jnp.maximum(jj, jnp.minimum(first_needed, n_kv - 1))
            return (kv_bh(bh), jj, 0)
    else:
        def kv_index(bh, i, j):
            return (kv_bh(bh), j, 0)
    compiler_params = _compiler_params()
    # Under a vma-checked shard_map the outputs must declare the inputs'
    # device-varying axes explicitly; outside shard_map this resolves to
    # no kwarg at all.
    vma = frozenset().union(*(jax.typeof(a).vma for a in (q, k, v)))
    vma_kw = {"vma": vma} if vma else {}
    out_specs = [
        pl.BlockSpec((None, block_q, d_v), lambda bh, i, j: (bh, i, 0)),
    ]
    out_shape = [jax.ShapeDtypeStruct((b * h, t, d_v), q.dtype, **vma_kw)]
    if with_lse:
        out_specs.append(
            pl.BlockSpec(
                (None, block_q, _STATS_LANES), lambda bh, i, j: (bh, i, 0)
            )
        )
        out_shape.append(
            jax.ShapeDtypeStruct(
                (b * h, t, _STATS_LANES), jnp.float32, **vma_kw
            )
        )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((None, block_k, d), kv_index),
            pl.BlockSpec((None, block_k, d_v), kv_index),
        ],
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=out_shape if with_lse else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, d_v), jnp.float32),  # acc
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(qf, kf, vf)
    if with_lse:
        o, lse = out
        return o.reshape(b, h, t, d_v), lse[:, :, 0].reshape(b, h, t)
    return out.reshape(b, h, t, d_v)


def _bwd_block(q, k, v, do, lse, delta, scale, keep):
    """Shared per-(i,j) backward math: returns (p, ds) with
    p = softmax weights recovered from the forward lse, ds = the score
    cotangent. q,do [bq,d] · k,v [bk,d] · lse,delta [bq,1]. MXU operands
    stay in the input dtype (see the forward's dtype note); p/ds come
    back f32 and are cast at their consuming matmuls."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bk] f32
    p = jnp.exp(s - lse)
    if keep is not None:
        p = jnp.where(keep, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [bq, bk] f32
    ds = p * (dp - delta) * scale
    return p, ds


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *,
                           block_q: int, n_q: int, causal: bool,
                           scale: float, window: int | None = None,
                           group: int = 1):
    """dK/dV: grid (b*h_kv, kv blocks, group*n_q). The innermost sweep
    runs the GROUP's q heads back to back (i = member*n_q + qi) into one
    sequential accumulator — that is how GQA stays kernel-resident here:
    a q-head-parallel grid would race grouped dk/dv. With group == 1 this
    is exactly the classic per-head sweep."""
    j = pl.program_id(1)
    i = pl.program_id(2)
    qi = i % n_q  # q block WITHIN the current group member's sweep
    bk = k_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _block(keep):
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        o = o_ref[...]
        lse = lse_ref[:, :1]
        # delta_i = rowsum(dO ⊙ O): recomputed per block (cheap VPU work,
        # upcast — elementwise f32 is free relative to the matmuls)
        # instead of shipping a [bh, T] side input through HBM.
        delta = jnp.sum(
            do.astype(jnp.float32) * o.astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        p, ds = _bwd_block(q, k, v, do, lse, delta, scale, keep)
        # dV_j += P^T dO_i ; dK_j += dS^T Q_i  (contract over the q rows)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # q block qi contributes to kv block j iff its last query position
    # reaches the block's first key position (and, windowed, iff its
    # first query is still inside the band of the block's last key).
    _run_tile(_block, causal, qi * block_q, block_q, j * bk, bk, window)

    @pl.when(i == group * n_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                         dq_ref, dq_acc, *, block_k: int, n_kv: int,
                         causal: bool, scale: float,
                         window: int | None = None):
    i = pl.program_id(1)
    j = pl.program_id(2)
    bq = q_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _block(keep):
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        o = o_ref[...]
        lse = lse_ref[:, :1]
        delta = jnp.sum(
            do.astype(jnp.float32) * o.astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        _, ds = _bwd_block(q, k, v, do, lse, delta, scale, keep)
        # dQ_i += dS K_j
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _run_tile(_block, causal, i * bq, bq, j * block_k, block_k, window)

    @pl.when(j == n_kv - 1)
    def _finalize():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_operands(q, k, v, o, lse, do):
    """Flat [bh, T, width] views of the backward's operands (q and k at
    the query/key width, v, o and do at the value width), the forward lse
    [B,H,T] lane-broadcast to [bh, T, LANES] (Mosaic wants >=2-D vector
    tiles; lane 0 is read back in-kernel), and the vma declaration the
    outputs need under a checked shard_map."""
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    flat = lambda a: a.reshape(b * h, t, a.shape[3])
    lsef = jnp.broadcast_to(
        lse.reshape(b * h, t, 1), (b * h, t, _STATS_LANES)
    )
    vma = frozenset().union(*(jax.typeof(a).vma for a in (q, k, v)))
    return (
        flat(q), k.reshape(b * h_kv, t, d),
        v.reshape(b * h_kv, t, v.shape[3]), flat(o), flat(do), lsef,
    ), ({"vma": vma} if vma else {})


def _flash_bwd_dkdv(q, k, v, o, lse, do, *, block_q: int | None = None,
                    block_k: int | None = None, causal: bool,
                    scale: float | None, interpret: bool,
                    window: int | None = None):
    """dK/dV kernel: grid over the b*h_kv KV heads and kv tiles, with the
    group's q heads swept sequentially into one resident accumulator pair
    (a q-head-parallel grid would race); dk/dv come back at the grouped
    head count."""
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    d_v = v.shape[3]
    group = h // h_kv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q, block_k = _resolve_tiles(
        block_q, block_k, t, t, d, q.dtype, d_v)
    n_q = t // block_q
    operands, vma_kw = _bwd_operands(q, k, v, o, lse, do)

    # Same DMA-elision trick as the forward: clamp skipped blocks'
    # addresses onto a needed (resident) block so their fetch is elided.
    # The sweep is i = member*n_q + qi per kv block j (grid row is a KV
    # head): causal needs qi >= j*bk/bq, a window needs
    # qi*bq <= window + (j+1)*bk - 2; the flat q row is the member's head.
    def q_row(bh, i):
        if group == 1:
            return bh
        return (bh // h_kv) * h + (bh % h_kv) * group + i // n_q

    def q_index(bh, j, i):
        qi = i % n_q
        if causal:
            qi = jnp.maximum(qi, (j * block_k) // block_q)
            if window is not None:
                i_last = (window + (j + 1) * block_k - 2) // block_q
                qi = jnp.minimum(qi, jnp.maximum(i_last, 0))
        return (q_row(bh, i), qi, 0)

    q_spec = pl.BlockSpec((None, block_q, d), q_index)
    o_spec = pl.BlockSpec((None, block_q, d_v), q_index)
    k_spec = pl.BlockSpec((None, block_k, d), lambda bh, j, i: (bh, j, 0))
    v_spec = pl.BlockSpec((None, block_k, d_v), lambda bh, j, i: (bh, j, 0))
    lse_spec = pl.BlockSpec((None, block_q, _STATS_LANES), q_index)
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkdv_kernel, block_q=block_q, n_q=n_q,
            causal=causal, scale=scale, window=window, group=group,
        ),
        grid=(b * h_kv, t // block_k, group * n_q),
        in_specs=[q_spec, k_spec, v_spec, o_spec, o_spec, lse_spec],
        out_specs=[k_spec, v_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b * h_kv, t, d), k.dtype, **vma_kw),
            jax.ShapeDtypeStruct((b * h_kv, t, d_v), v.dtype, **vma_kw),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),  # dk accumulator
            pltpu.VMEM((block_k, d_v), jnp.float32),  # dv accumulator
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*operands)
    return dk.reshape(b, h_kv, t, d), dv.reshape(b, h_kv, t, d_v)


def _flash_bwd_dq(q, k, v, o, lse, do, *, block_q: int | None = None,
                  block_k: int | None = None, causal: bool,
                  scale: float | None, interpret: bool,
                  window: int | None = None):
    """dQ kernel: a q tile resident, the kv sweep innermost; the grouped
    KV are read through divided index maps, like the forward."""
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    d_v = v.shape[3]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q, block_k = _resolve_tiles(
        block_q, block_k, t, t, d, q.dtype, d_v)
    n_kv = t // block_k
    operands, vma_kw = _bwd_operands(q, k, v, o, lse, do)
    kv_row = lambda bh: _kv_flat_row(bh, h, h_kv)

    # Same clamp as the forward's kv_index (above-diagonal down,
    # behind-the-band up), KV rows divided to the grouped head.
    if causal:
        def kv_index(bh, i, j):
            jj = jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
            if window is not None:
                j_first = jnp.maximum(
                    0, (i * block_q - window + 1) // block_k
                )
                jj = jnp.maximum(jj, jnp.minimum(j_first, n_kv - 1))
            return (kv_row(bh), jj, 0)
    else:
        def kv_index(bh, i, j):
            return (kv_row(bh), j, 0)
    q_spec = pl.BlockSpec((None, block_q, d), lambda bh, i, j: (bh, i, 0))
    o_spec = pl.BlockSpec((None, block_q, d_v), lambda bh, i, j: (bh, i, 0))
    k_spec = pl.BlockSpec((None, block_k, d), kv_index)
    v_spec = pl.BlockSpec((None, block_k, d_v), kv_index)
    lse_spec = pl.BlockSpec(
        (None, block_q, _STATS_LANES), lambda bh, i, j: (bh, i, 0)
    )
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_k=block_k, n_kv=n_kv,
            causal=causal, scale=scale, window=window,
        ),
        grid=(b * h, t // block_q, n_kv),
        in_specs=[q_spec, k_spec, v_spec, o_spec, o_spec, lse_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype, **vma_kw),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*operands)
    return dq.reshape(b, h, t, d)


def _flash_bwd(q, k, v, o, lse, do, **kw):
    """FlashAttention-2-style backward: two Pallas kernels (dK/dV with the
    Q sweep innermost; dQ with the KV sweep innermost). The score matrix
    is recovered blockwise from the forward's lse — nothing O(T^2) ever
    touches HBM in the backward either — and GQA runs kernel-resident in
    BOTH directions."""
    dk, dv = _flash_bwd_dkdv(q, k, v, o, lse, do, **kw)
    return _flash_bwd_dq(q, k, v, o, lse, do, **kw), dk, dv


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def flash_attention(q, k, v, block_q=None, block_k=None, causal=False,
                    scale=None, interpret=False, window=None):
    """Flash attention; q,k [B, H, T, D], v [B, H, T, Dv] -> [B, H, T, Dv]
    (Dv = D in the classic layers; the scale defaults to 1/sqrt(D)).

    ``block_q`` / ``block_k`` None (the product path): the three kernels
    take their tiles from :func:`flash_tiles`. Explicit values win
    (tests, sweeps).

    ``window`` (causal-only sliding window): the band mask lives in the
    kernel and fully-out-of-band KV tiles skip compute AND DMA — the
    causal O(T^2/2) sweep becomes O(T*window)."""
    return _flash_fwd(
        q, k, v, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale, interpret=interpret, window=window,
    )


def _vjp_fwd(q, k, v, block_q, block_k, causal, scale, interpret, window):
    out, lse = _flash_fwd(
        q, k, v, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale, interpret=interpret, with_lse=True, window=window,
    )
    return out, (q, k, v, out, lse)


def _remat_block(block_k, k) -> int:
    """KV block of the blockwise remat backward: the explicit key tile,
    else 128 as before the shape rule — the JAX-level scan keeps a
    [.., Tq, block] f32 score block in HBM, so the kernels' wide tiles
    are not its optimum."""
    return min(128 if block_k is None else block_k, k.shape[-2])


def _vjp_bwd(block_q, block_k, causal, scale, interpret, window, res, g):
    q, k, v, o, lse = res
    rectangular = q.shape[-2] != k.shape[-2]  # bwd kernels assume square
    if rectangular or os.environ.get(
        "DCT_FLASH_BWD", "kernel"
    ).strip().lower() == "remat":
        # Escape hatch: differentiate the numerically-identical blockwise
        # path instead of running the backward kernels.
        from dct_tpu.ops.attention import blockwise_attention

        block = _remat_block(block_k, k)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: blockwise_attention(
                q_, k_, v_, block_size=block, causal=causal, scale=scale,
                window=window,
            ),
            q, k, v,
        )
        return vjp(g)
    return _flash_bwd(
        q, k, v, o, lse, g, block_q=block_q, block_k=block_k,
        causal=causal, scale=scale, interpret=interpret, window=window,
    )


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_lse(q, k, v, block_q=None, block_k=None, causal=False,
                        scale=None, interpret=False, window=None,
                        q_offset=0):
    """Flash attention that also returns the per-row log-sum-exp:
    (o [B,H,T,D], lse [B,H,T] f32). The lse makes finalized outputs
    MERGEABLE — ring attention combines per-KV-shard flash results with
    softmax weights ``exp(lse_j - logaddexp_j lse_j)``, which is exactly
    the online-softmax accumulation factored across kernel calls.

    ``window``/``q_offset``: causal sliding-window band with the q
    positions shifted by a STATIC offset — the windowed ring passes its
    per-step inter-shard distance here, so partial-band shards run
    kernel-resident with out-of-band tiles skipped."""
    return _flash_fwd(
        q, k, v, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale, interpret=interpret, with_lse=True, window=window,
        q_offset=q_offset,
    )


def _vjp_lse_fwd(q, k, v, block_q, block_k, causal, scale, interpret,
                 window, q_offset):
    out = _flash_fwd(
        q, k, v, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale, interpret=interpret, with_lse=True, window=window,
        q_offset=q_offset,
    )
    return out, (q, k, v)


def _vjp_lse_bwd(block_q, block_k, causal, scale, interpret, window,
                 q_offset, res, g):
    # Rematerialize through the numerically-identical JAX-level blockwise
    # path, which carries the SAME (o, lse) pair — so cotangents w.r.t.
    # the lse output (the ring merge weights depend on it) flow correctly.
    from dct_tpu.ops.attention import blockwise_attention_lse

    q, k, v = res
    block_k = _remat_block(block_k, k)
    # Static KV front-slice: with an offset band (the windowed ring's
    # partial shards), keys at j <= q_offset - window are behind the band
    # for EVERY q row — scanning them in the remat backward would waste
    # the forward's O(T*window) bound on zeroed blocks (code-review r4).
    # Their dk/dv are exactly zero, restored by the front pad below.
    j0 = 0
    if window is not None and q_offset:
        j0 = max(0, q_offset - window + 1)
        j0 -= j0 % block_k
        j0 = min(j0, k.shape[-2])  # fully-out-of-band shard: empty slice
    k_sl = k[..., j0:, :] if j0 else k
    v_sl = v[..., j0:, :] if j0 else v
    if k_sl.shape[-2] == 0:
        return (
            jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v)
        )
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention_lse(
            q_, k_, v_, block_size=block_k, causal=causal, scale=scale,
            window=window, q_offset=q_offset - j0,
        ),
        q, k_sl, v_sl,
    )
    dq, dk, dv = vjp(g)
    if j0:
        pad = [(0, 0)] * (k.ndim - 2) + [(j0, 0), (0, 0)]
        dk = jnp.pad(dk, pad)
        dv = jnp.pad(dv, pad)
    return dq, dk, dv


flash_attention_lse.defvjp(_vjp_lse_fwd, _vjp_lse_bwd)

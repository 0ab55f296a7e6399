"""Attention ops: dense, blockwise (flash-style), and ring (sequence-parallel).

The reference has no attention anywhere (5-feature tabular MLP only,
SURVEY §5.7) — long-context support is a capability this framework adds, and
it is designed TPU-first rather than bolted on:

- :func:`dense_attention` — the O(T^2)-memory reference numerics; fine for
  short sequences, and the oracle the other paths are tested against.
- :func:`blockwise_attention` — online-softmax ``lax.scan`` over KV blocks:
  O(T) memory on a single chip, XLA fuses the inner block into MXU matmuls.
- :func:`ring_attention` — sequence parallelism over the mesh's ``seq``
  axis: each device keeps its Q shard and rotates KV shards around the ring
  with ``lax.ppermute`` (ICI neighbor hops — bandwidth-optimal, no
  all-gather), accumulating the same online softmax. Compute on the current
  block overlaps the DMA of the next block's permute in XLA's schedule.

All three share one accumulation kernel (:func:`_online_block`) so their
numerical equivalence is structural; tests assert it on an 8-device mesh.
The Pallas flash kernel (:mod:`dct_tpu.ops.pallas_attention`) slots in per
:func:`select_attention_path` — single-shard on TPU, and as the per-shard
block compute inside the ring.

Causal ring attention additionally supports the STRIPED ("zigzag")
layout: the contiguous P("seq") layout gives device i exactly i+1
visible KV shards, so the lock-stepped ring runs at the tail device's
pace — a ~2x load imbalance. Striping splits the sequence into 2R
chunks and hands device i chunks (i, 2R-1-i); every device then does
exactly two half-chunk blocks of visible work at every ring step
(:func:`striped_layout` derivation), so the causal ring is perfectly
balanced at the cost of one static sequence permutation each way.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG = -1e30  # finite "minus infinity": keeps the online max/exp NaN-free


def _online_block(q, k, v, scale, mask, m, l, o):
    """Fold one KV block into the running online-softmax state.

    q [..., Tq, D] · k,v [..., Tk, D] · mask broadcastable to [..., Tq, Tk]
    (True = attend) · m,l [..., Tq] f32 · o [..., Tq, D] f32.
    """
    s = jnp.einsum(
        "...qd,...kd->...qk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        # A fully-masked row would otherwise get p=exp(0)=1 per entry.
        p = jnp.where(mask, p, 0.0)
    l_new = l * alpha + p.sum(axis=-1)
    # P·V with operands in V's dtype (bf16 on the product path — f32 MXU
    # rate is a fraction of bf16's; accumulation stays f32 via
    # preferred_element_type). f32 inputs are untouched: p is already f32.
    o_new = o * alpha[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, o_new


def _finalize(l, o, dtype):
    return (o / jnp.maximum(l, 1e-20)[..., None]).astype(dtype)


def _check_window(window: int | None, causal: bool) -> None:
    """Op-layer window validation. ``None`` means full attention; "off"
    must never be spelled 0 here — a 0 band would make every row fully
    masked and softmax silently uniform over ALL positions (causality
    broken). The '0 = off' convention lives in the CONFIG layer
    (registry normalizes attn_window<=0 to None)."""
    if window is None:
        return
    if not causal:
        raise ValueError("window requires causal attention")
    if window < 1:
        raise ValueError(
            f"window must be >= 1 (got {window}); pass None for full "
            "causal attention"
        )


def expand_kv(q, k, v):
    """Grouped-query attention (GQA) KV expansion: K/V carry
    ``n_kv_heads`` heads with ``H % n_kv_heads == 0``; each KV head
    serves ``H/n_kv_heads`` consecutive query heads (the fused
    projection's group-major layout). Returns (k, v) broadcast to the
    full H — XLA fuses the broadcast into the downstream matmuls, and
    the paths where materializing would cost real bandwidth (the Pallas
    kernel, the SP engines' collectives) expand later or never
    (grouped index maps)."""
    h, hkv = q.shape[-3], k.shape[-3]
    if h == hkv:
        return k, v
    if h % hkv:
        raise ValueError(
            f"GQA needs q heads ({h}) divisible by kv heads ({hkv})"
        )
    group = h // hkv
    k = jnp.repeat(k, group, axis=-3)
    v = jnp.repeat(v, group, axis=-3)
    return k, v


def dense_attention(
    q, k, v, *, causal: bool = False, scale: float | None = None,
    window: int | None = None,
):
    """Reference numerics: full [Tq, Tk] score matrix. q,k,v [B, H, T, D]
    (K/V may carry fewer GQA heads — :func:`expand_kv`).

    ``window`` (causal-only): position t attends to at most the last
    ``window`` positions [t-window+1, t] — sliding-window local
    attention (Mistral/Longformer-style), the standard long-context
    complement to sequence parallelism."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    _check_window(window, causal)
    k, v = expand_kv(q, k, v)
    s = jnp.einsum(
        "...qd,...kd->...qk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        pos_q = jnp.arange(tq)[:, None]
        pos_k = jnp.arange(tk)[None, :]
        mask = pos_q >= pos_k
        if window is not None:
            mask &= pos_q - pos_k < window
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


def _blockwise_stats(q, k, v, *, block_size: int, causal: bool,
                     scale: float | None, window: int | None = None,
                     q_offset: int = 0):
    """Shared blockwise scan returning the raw online-softmax state
    (m, l, o) — finalized by the callers into output (and optionally lse).

    ``q_offset`` shifts the q positions relative to k's (both default to
    0-based): the windowed flash ring passes the static inter-shard
    distance here so its partial-band shards reuse this O(T*block)-memory
    scan instead of materializing a full [Tq, Tk] mask."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    _check_window(window, causal)
    k, v = expand_kv(q, k, v)
    t = k.shape[-2]
    if t % block_size:
        raise ValueError(f"seq len {t} not a multiple of block {block_size}")
    n_blocks = t // block_size
    tq = q.shape[-2]

    # [n_blocks, ..., block, D] scan layout.
    ks = jnp.moveaxis(k.reshape(*k.shape[:-2], n_blocks, block_size, k.shape[-1]), -3, 0)
    vs = jnp.moveaxis(v.reshape(*v.shape[:-2], n_blocks, block_size, v.shape[-1]), -3, 0)

    q_pos = q_offset + jnp.arange(tq)

    def body(carry, blk):
        m, l, o = carry
        kb, vb, b_idx = blk
        mask = None
        if causal:
            k_pos = b_idx * block_size + jnp.arange(block_size)
            mask = q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                # Sliding window: blocks fully outside every row's window
                # contribute an all-False mask (their p rows zero out);
                # XLA's scan keeps the shape static — the win is HBM and
                # numerics, not skipped FLOPs (the Pallas kernel's tile
                # skip is the FLOPs lever, single-shard TPU only).
                mask &= q_pos[:, None] - k_pos[None, :] < window
        m, l, o = _online_block(q, kb, vb, scale, mask, m, l, o)
        return (m, l, o), None

    m0 = jnp.full(q.shape[:-1], _NEG, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    o0 = jnp.zeros((*q.shape[:-1], v.shape[-1]), jnp.float32)
    (m, l, o), _ = lax.scan(body, (m0, l0, o0), (ks, vs, jnp.arange(n_blocks)))
    return m, l, o


def blockwise_attention(
    q, k, v, *, block_size: int = 512, causal: bool = False,
    scale: float | None = None, window: int | None = None,
):
    """O(T)-memory attention on one device: scan KV in blocks of
    ``block_size`` through the shared online-softmax kernel. q,k,v
    [B, H, T, D]; T must be a multiple of block_size (pad upstream).
    ``window``: causal sliding-window local attention."""
    m, l, o = _blockwise_stats(
        q, k, v, block_size=block_size, causal=causal, scale=scale,
        window=window,
    )
    return _finalize(l, o, q.dtype)


def blockwise_attention_lse(
    q, k, v, *, block_size: int = 512, causal: bool = False,
    scale: float | None = None, window: int | None = None,
    q_offset: int = 0,
):
    """Blockwise attention returning (o, lse [..., T] f32) — the JAX-level
    twin of :func:`dct_tpu.ops.pallas_attention.flash_attention_lse`, used
    as its rematerialized backward (incl. the windowed/offset variants the
    ring's partial-band shards run)."""
    m, l, o = _blockwise_stats(
        q, k, v, block_size=block_size, causal=causal, scale=scale,
        window=window, q_offset=q_offset,
    )
    return _finalize(l, o, q.dtype), m + jnp.log(jnp.maximum(l, 1e-20))


def flash_interpret_mode() -> bool | None:
    """Resolve whether the Pallas flash kernel is usable here, and how.

    Returns False (real Mosaic kernel), True (interpret mode), or None
    (don't use flash). Policy, overridable via ``DCT_FLASH``:

    - ``auto`` (default): Mosaic on the TPU backend; None elsewhere —
      interpret mode is orders of magnitude slower than XLA's fused
      blockwise path, so CPU rigs fall back unless they opt in.
    - ``interpret``: force interpret mode (CPU test rigs).
    - ``on``/``1``: Mosaic on TPU, interpret elsewhere.
    - ``off``/``0``: never.
    """
    mode = os.environ.get("DCT_FLASH", "auto").strip().lower()
    on_tpu = jax.default_backend() == "tpu"
    if mode in ("off", "0", "false", "no"):
        return None
    if mode == "interpret":
        return True
    if mode in ("on", "1", "true", "yes"):
        return False if on_tpu else True
    return False if on_tpu else None


def _resolve_flash(use_flash: bool | None) -> tuple[bool, bool | None]:
    """Shared tri-state resolution for the SP engines: returns
    (flash_on, interpret). ``use_flash`` None follows the
    :func:`flash_interpret_mode` policy; True forces flash (interpret
    everywhere except a real TPU backend); False disables it."""
    interpret = flash_interpret_mode()
    if use_flash is None:
        return interpret is not None, interpret
    if use_flash:
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return True, interpret
    return False, interpret


def sp_engine() -> str:
    """The sequence-parallel engine policy (``DCT_SP_ENGINE``):
    'ring' (default — KV shards rotate with ppermute, O(T/sp) memory) or
    'a2a' (Ulysses-style head<->seq all_to_all exchange)."""
    engine = os.environ.get("DCT_SP_ENGINE", "ring").strip().lower()
    if engine not in ("ring", "a2a"):
        raise ValueError(f"DCT_SP_ENGINE={engine!r} must be 'ring' or 'a2a'")
    return engine


def select_attention_path(
    t: int, *, mesh: Mesh | None = None, block_size: int = 512,
    flash_block: int = 128, flash_min_len: int = 256,
) -> str:
    """The attention-path policy, exposed for tests and the bench:
    'ring' | 'a2a' | 'flash' | 'blockwise' | 'dense'. ``t`` is the
    (single-shard) sequence length."""
    if mesh is not None and mesh.shape.get("seq", 1) > 1:
        return sp_engine()
    if (
        flash_interpret_mode() is not None
        and t >= flash_min_len
        and t % flash_block == 0
    ):
        return "flash"
    if t > block_size and t % block_size == 0:
        return "blockwise"
    return "dense"


def striped_layout(t: int, ring_size: int):
    """Striped ("zigzag") sequence layout for balanced causal ring
    attention.

    Splits ``t`` positions into ``2*ring_size`` chunks; device i holds
    chunks (i, 2R-1-i) concatenated. Under a causal mask, chunk x sees
    chunk y fully iff y < x and diagonally iff y == x, so at ring step s
    every device's visible work is exactly two half-shard blocks:

    - step 0 (src == my): diag(A_my) + full(B_my, A_my) + diag(B_my)
    - src < my:            full(A_my, A_src) + full(B_my, A_src)
    - src > my:            full(B_my, A_src) + full(B_my, B_src)

    (A_i = chunk i, B_i = chunk 2R-1-i; B_my sees every A_src because
    2R-1-my >= R > src, and never the other way.) Returns ``(perm,
    inv)`` int arrays: ``x[..., perm, :]`` reorders a contiguous
    sequence into striped layout, ``o[..., inv, :]`` undoes it.
    """
    if t % (2 * ring_size):
        raise ValueError(
            f"striped layout needs seq len {t} % {2 * ring_size} == 0"
        )
    c = t // (2 * ring_size)
    order = []
    for i in range(ring_size):
        order.extend(range(i * c, (i + 1) * c))
        j = 2 * ring_size - 1 - i
        order.extend(range(j * c, (j + 1) * c))
    perm = np.asarray(order, np.int32)
    inv = np.argsort(perm).astype(np.int32)
    return perm, inv


def _merge_lse(o, lse, o_j, lse_j):
    """Fold a finalized (o_j, lse_j) attention block into the running
    (o, lse) pair: softmax-weighted combine — the online-softmax update
    factored across already-normalized results."""
    lse_new = jnp.logaddexp(lse, lse_j)
    w = jnp.exp(lse - lse_new)[..., None]
    w_j = jnp.exp(lse_j - lse_new)[..., None]
    return o * w + o_j.astype(jnp.float32) * w_j, lse_new


def _ring_window_steps(window: int | None, t_local: int, ring_size: int) -> int:
    """How many CONTIGUOUS-layout ring steps can contribute under a causal
    sliding window: step s >= 1 consumes the shard ``s`` hops back, whose
    minimum q-k distance is (s-1)*t_local + 1 — once that reaches
    ``window`` every later shard is fully out of band for EVERY device,
    so both the block compute and the ppermute hops stop. This is the
    windowed ring's asymptotic win: O(window) work and communication per
    device instead of O(T)."""
    if window is None:
        return ring_size
    return min(ring_size, (window - 1 + t_local - 1) // t_local + 1)


def _ring_body_flash(q, k, v, *, axis_name: str, ring_size: int,
                     causal: bool, scale: float | None, interpret: bool,
                     block_q: int | None = None, block_k: int | None = None,
                     window: int | None = None):
    """Ring attention whose per-shard block compute is the Pallas flash
    kernel. Runs inside shard_map on LOCAL shards [B, h_local, T_local, D].

    Causal structure over ring steps (my = this device's seq index,
    src = origin of the current KV shard = (my - step) mod ring):
    step 0 is always the diagonal shard (standard causal mask, offsets
    cancel); for step >= 1 the shard is either fully visible (src < my,
    i.e. my >= step) or fully masked — so only two STATIC kernel variants
    are needed, selected by a traced ``lax.cond``. Fully-masked steps
    contribute (o=0, lse=-inf) and vanish in the merge.

    ``window`` (causal sliding window) refines the step analysis with
    STATIC per-step distance bounds (q-k distance at step s spans
    [(s-1)L+1, (s+1)L-1], L = T_local): fully-in-band shards run the
    plain flash kernel, partial band shards run the SAME kernel with its
    in-kernel band mask and the static inter-shard distance as
    ``q_offset`` (out-of-band tiles skip compute and DMA), and
    fully-out-of-band steps are not executed at all —
    :func:`_ring_window_steps` truncates the ring, so far KV shards are
    neither computed NOR communicated."""
    from dct_tpu.ops.pallas_attention import flash_attention_lse

    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    my = lax.axis_index(axis_name)
    t_local = q.shape[-2]
    perm = [(j, (j + 1) % ring_size) for j in range(ring_size)]
    n_steps = _ring_window_steps(window, t_local, ring_size)

    def call(q_, k_, v_, causal_, window_=None, q_offset=0):
        return flash_attention_lse(
            q_, k_, v_, block_q, block_k, causal_, scale, interpret,
            window_, q_offset,
        )

    k_cur, v_cur = k, v
    o = None
    for step in range(n_steps):  # static unroll: ring_size is mesh shape
        if step == 0:
            if window is not None and window < t_local:
                o_j, lse_j = call(q, k_cur, v_cur, True, window, 0)
                o, lse = o_j.astype(jnp.float32), lse_j
            else:
                o_j, lse_j = call(q, k_cur, v_cur, causal)
                o, lse = o_j.astype(jnp.float32), lse_j
        else:
            if causal:
                d_max = (step + 1) * t_local - 1
                if window is not None and d_max >= window:
                    # Partial band shard: windowed kernel, q shifted by
                    # the static inter-shard distance.
                    o_j, lse_j = lax.cond(
                        my >= step,
                        lambda kc=k_cur, vc=v_cur, s=step: call(
                            q, kc, vc, True, window, s * t_local
                        ),
                        lambda: (
                            jnp.zeros(q.shape, q.dtype),
                            jnp.full(q.shape[:-1], _NEG, jnp.float32),
                        ),
                    )
                else:
                    o_j, lse_j = lax.cond(
                        my >= step,
                        lambda kc=k_cur, vc=v_cur: call(q, kc, vc, False),
                        lambda: (
                            jnp.zeros(q.shape, q.dtype),
                            jnp.full(q.shape[:-1], _NEG, jnp.float32),
                        ),
                    )
            else:
                o_j, lse_j = call(q, k_cur, v_cur, False)
            o, lse = _merge_lse(o, lse, o_j, lse_j)
        if step < n_steps - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    return o.astype(q.dtype)


def _ring_body_flash_striped(q, k, v, *, axis_name: str, ring_size: int,
                             scale: float | None, interpret: bool,
                             block_q: int | None = None,
                             block_k: int | None = None):
    """Balanced CAUSAL ring attention on the striped layout, flash
    per-shard compute. Local shards are [B, h, L, D] in striped order
    (first half = chunk ``my``, second half = chunk ``2R-1-my``; see
    :func:`striped_layout` for the three-case visibility analysis).
    Every ring step costs exactly two half-chunk flash blocks on every
    device — the causal ring's tail-device bottleneck is gone."""
    from dct_tpu.ops.pallas_attention import flash_attention_lse

    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    my = lax.axis_index(axis_name)
    half = q.shape[-2] // 2
    perm = [(j, (j + 1) % ring_size) for j in range(ring_size)]

    def call(q_, k_, v_, causal_):
        return flash_attention_lse(
            q_, k_, v_, block_q, block_k, causal_, scale, interpret
        )

    q1, q2 = q[..., :half, :], q[..., half:, :]
    k_cur, v_cur = k, v

    # Step 0: the diagonal shard. A_my is causal over itself; B_my sees
    # all of A_my plus its own causal diagonal.
    k1, v1 = k_cur[..., :half, :], v_cur[..., :half, :]
    k2, v2 = k_cur[..., half:, :], v_cur[..., half:, :]
    o1_0, lse1 = call(q1, k1, v1, True)
    o2a, lse2a = call(q2, k1, v1, False)
    o2b, lse2b = call(q2, k2, v2, True)
    o1 = o1_0.astype(jnp.float32)
    o2, lse2 = _merge_lse(o2a.astype(jnp.float32), lse2a, o2b, lse2b)

    for step in range(1, ring_size):  # static unroll: ring_size is static
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)

        def visible_low(kc=k_cur, vc=v_cur):
            # src < my: both halves of q see A_src fully, B_src never.
            oa, la = call(q, kc[..., :half, :], vc[..., :half, :], False)
            return (
                oa[..., :half, :], la[..., :half],
                oa[..., half:, :], la[..., half:],
            )

        def visible_high(kc=k_cur, vc=v_cur):
            # src > my: A_my sees nothing, B_my sees the whole shard.
            ob, lb = call(q2, kc, vc, False)
            return (
                jnp.zeros(q1.shape, q.dtype),
                jnp.full(q1.shape[:-1], _NEG, jnp.float32),
                ob, lb,
            )

        c1o, c1l, c2o, c2l = lax.cond(my >= step, visible_low, visible_high)
        o1, lse1 = _merge_lse(o1, lse1, c1o, c1l)
        o2, lse2 = _merge_lse(o2, lse2, c2o, c2l)

    return jnp.concatenate([o1, o2], axis=-2).astype(q.dtype)


def _ring_body(q, k, v, *, axis_name: str, ring_size: int, causal: bool,
               scale: float | None, vary_axes: tuple = (),
               striped: bool = False, window: int | None = None):
    """Per-shard ring attention (runs inside shard_map).

    q,k,v are the LOCAL shards [B, h_local, T_local, D]. Each of the
    ``ring_size`` steps consumes the KV shard that originated on device
    ``(my_index - step) mod ring_size`` and then forwards it to the next
    neighbor — a classic ICI ring pipeline. With ``striped`` the local
    shard is in :func:`striped_layout` order and the causal mask is
    built from the striped GLOBAL positions instead of contiguous ones.

    ``window`` (causal sliding window, VERDICT r3 item 6) adds the
    ``q_pos - k_pos < window`` band to the mask — on GLOBAL positions, so
    it is correct for both layouts. Contiguous rings also truncate to
    :func:`_ring_window_steps` hops (far shards are neither computed nor
    communicated); striped rings keep all hops — each device's second
    chunk has near neighbors arriving late in the rotation — and instead
    skip the block compute of shards the band fully masks."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    my = lax.axis_index(axis_name)
    t_local = q.shape[-2]
    n_steps = (
        ring_size if striped else _ring_window_steps(window, t_local, ring_size)
    )

    def positions(dev):
        if not striped:
            return dev * t_local + jnp.arange(t_local)
        c = t_local // 2
        return jnp.concatenate([
            dev * c + jnp.arange(c),
            (2 * ring_size - 1 - dev) * c + jnp.arange(c),
        ])

    q_pos = positions(my)
    perm = [(j, (j + 1) % ring_size) for j in range(ring_size)]

    # pcast-to-varying: the accumulators inherit q's device-varying axes
    # from the first iteration on; typing them that way up front keeps
    # every step's accumulator type fixed.
    axes = tuple(vary_axes) or (axis_name,)
    m = lax.pcast(
        jnp.full(q.shape[:-1], _NEG, jnp.float32), axes, to="varying"
    )
    l = lax.pcast(jnp.zeros(q.shape[:-1], jnp.float32), axes, to="varying")
    o = lax.pcast(jnp.zeros(q.shape, jnp.float32), axes, to="varying")
    k_cur, v_cur = k, v
    for step in range(n_steps):  # static unroll: ring_size is mesh shape
        src = (my - step) % ring_size
        mask = None
        if causal:
            k_pos = positions(src)
            d = q_pos[:, None] - k_pos[None, :]
            mask = d >= 0
            if window is not None:
                mask &= d < window
        # GQA: the ring rotates the GROUPED kv shards (ICI payload stays
        # at n_kv_heads); expansion to full heads happens per-use INSIDE
        # the branch that computes, so band-skipped steps pay neither the
        # matmuls nor the group-times KV materialization.
        if window is not None and (striped or step > 0):
            # Skip the QK/AV matmuls of shards the band fully masks (the
            # striped rotation interleaves near and far shards, so which
            # steps those are is traced, not static); the mask alone
            # would zero their contribution but still pay their FLOPs.
            # Step 0 of a contiguous ring is always the visible diagonal.
            m, l, o = lax.cond(
                jnp.any(mask),
                lambda kc=k_cur, vc=v_cur, mk=mask, m=m, l=l, o=o: (
                    _online_block(q, *expand_kv(q, kc, vc), scale, mk,
                                  m, l, o)
                ),
                lambda m=m, l=l, o=o: (m, l, o),
            )
        else:
            ke, ve = expand_kv(q, k_cur, v_cur)
            m, l, o = _online_block(q, ke, ve, scale, mask, m, l, o)
        if step < n_steps - 1:  # the truncated ring skips the far hops
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    return _finalize(l, o, q.dtype)


def _is_init_trace_escape(q, b: int, n_data: int) -> bool:
    """Single-sourced policy for the SP engines' batch-1 dense escape.

    The batch-1 init trace (flax shape inference, jitted by
    create_train_state) cannot tile the data axis; dense attention is
    numerically identical there. Gated to (batch 1, tracer) so any OTHER
    undersized batch — eager misuse, or a jitted loader that skipped
    BatchLoader's divisibility guarantee — raises the engine's sizing
    error instead of silently replicating an O(T^2) global computation
    per device (ADVICE r3). Residual risk: a genuinely batch-1 jitted
    train step over a populated data axis would take this escape, but
    such a step cannot tile the mesh at all and BatchLoader refuses to
    produce it."""
    return b == 1 and b < n_data and isinstance(q, jax.core.Tracer)


def ring_attention(
    q, k, v, *, mesh: Mesh, causal: bool = False, scale: float | None = None,
    seq_axis: str = "seq", data_axis: str = "data", model_axis: str = "model",
    use_flash: bool | None = None, striped: bool | None = None,
    window: int | None = None,
):
    """Sequence-parallel attention over ``mesh[seq_axis]``.

    q,k,v: GLOBAL [B, H, T, D] arrays (jit-sharded); internally shard_mapped
    to [B, H/model, T/seq, D] per device. Batch rides ``data_axis``, heads
    ride ``model_axis`` — so DP x TP x SP compose in one op.

    ``use_flash``: True forces the Pallas flash per-shard block compute,
    False disables it, None (default) follows the
    :func:`flash_interpret_mode` policy. Interpret-vs-Mosaic is always
    resolved from the backend; the JAX-level online-softmax body is the
    fallback when flash is off or the local shard is not block-aligned.

    ``striped``: causal-only. True runs the :func:`striped_layout` ring
    (perfect per-step load balance — see module docstring); None follows
    the ``DCT_RING_STRIPED`` env policy — ``auto`` (default) enables it
    whenever the flash path is on and the half-chunk is kernel-aligned
    (that is where balance pays: the flash causal ring skips invisible
    shards, so the contiguous layout runs at the tail device's pace),
    ``on`` forces it for causal rings (like ``striped=True``), ``off``
    keeps the contiguous layout (the A/B baseline); False keeps the
    contiguous layout.

    ``window`` (causal sliding window): supported on every ring variant —
    the contiguous layouts truncate the ring to the in-band hops
    (:func:`_ring_window_steps`, O(window) work and communication per
    device instead of O(T)); the striped flash body has no band tiles,
    so windowed striped rings route to the JAX-level masked body.
    """
    ring_size = mesh.shape[seq_axis]
    b, h, t, _ = q.shape
    _check_window(window, causal)
    if v.shape[-1] != q.shape[-1]:
        # The ring bodies size their accumulators and the skipped steps'
        # zeros from q; the single-shard paths and the a2a engine take
        # values of another width than the queries and keys.
        raise ValueError(
            f"ring attention needs values as wide as the queries "
            f"({q.shape[-1]}), got {v.shape[-1]}; use DCT_SP_ENGINE=a2a"
        )
    if striped and not causal:
        # Validate BEFORE any fallback: a non-causal layer misconfigured
        # with striped=True must fail at trace time, not pass the batch-1
        # init trace and surprise on the first real batch.
        raise ValueError("striped ring layout only applies to causal")
    if _is_init_trace_escape(q, b, mesh.shape[data_axis]):
        return dense_attention(q, k, v, causal=causal, scale=scale,
                               window=window)
    h_kv = k.shape[1]
    if (
        b % mesh.shape[data_axis]
        or h % mesh.shape[model_axis]
        or h_kv % mesh.shape[model_axis]
        or t % ring_size
    ):
        # Anything else is a sizing bug: silently falling back to dense
        # would discard sequence parallelism (and its O(T/P) memory bound)
        # on every step with no sign beyond the OOM/slowdown.
        raise ValueError(
            f"ring_attention shapes B={b}, H={h} (kv heads {h_kv}), T={t} "
            f"do not tile mesh axes data={mesh.shape[data_axis]}, "
            f"model={mesh.shape[model_axis]}, seq={ring_size}; adjust "
            "batch/heads/seq_len or the mesh"
        )
    spec = P(data_axis, model_axis, seq_axis, None)
    flash_on, interpret = _resolve_flash(use_flash)
    t_local = t // ring_size
    half = t_local // 2

    def flash_aligned(n: int) -> bool:
        # Mosaic tiles want 128-multiples. Interpret mode takes any size
        # as long as every extent the striped body passes (half-chunk Tq,
        # whole-shard Tq/Tk) divides its clamped block min(128, extent).
        if not interpret:
            return n % 128 == 0
        divisible = lambda e: e >= 1 and e % min(128, e) == 0
        return divisible(n) and divisible(t_local)
    if striped is None:
        # DCT_RING_STRIPED: "auto" (default — striped whenever the causal
        # flash ring is kernel-aligned), "0"/"off" (force contiguous,
        # the on-chip A/B baseline), "1"/"on" (striped even for the
        # JAX-level body).
        mode = os.environ.get("DCT_RING_STRIPED", "auto").strip().lower()
        if mode in ("0", "off", "false", "no"):
            striped = False
        elif mode in ("1", "on", "true", "yes"):
            # Forced on behaves like striped=True for causal rings
            # (below it raises on an odd t_local rather than silently
            # measuring the contiguous layout); non-causal rings have no
            # striped concept and are unaffected.
            striped = bool(causal and ring_size > 1)
        else:
            # Windowed rings skip out-of-band shards, so the contiguous
            # layout's causal imbalance mostly vanishes and the striped
            # flash body has no band support — auto keeps contiguous.
            striped = bool(
                causal
                and window is None
                and ring_size > 1
                and t_local % 2 == 0
                and flash_on
                and flash_aligned(half)
            )
    elif striped:
        if t_local % 2:
            raise ValueError(
                f"striped ring needs T/ring ({t_local}) even; got T={t}, "
                f"ring={ring_size}"
            )
    if striped:
        perm, inv = striped_layout(t, ring_size)
        if window is None and flash_on and flash_aligned(half):
            fn = functools.partial(
                _ring_body_flash_striped,
                axis_name=seq_axis,
                ring_size=ring_size,
                scale=scale,
                interpret=bool(interpret),
            )
            vma_kw = {"check_vma": False}
        else:
            fn = functools.partial(
                _ring_body,
                axis_name=seq_axis,
                ring_size=ring_size,
                causal=True,
                scale=scale,
                vary_axes=(data_axis, model_axis, seq_axis),
                striped=True,
                window=window,
            )
            vma_kw = {}
        qs, ks, vs = (jnp.take(a, perm, axis=-2) for a in (q, k, v))
        out = shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            **vma_kw,
        )(qs, ks, vs)
        return jnp.take(out, inv, axis=-2)
    if flash_on and t_local % 128 == 0 and t_local >= 128:
        fn = functools.partial(
            _ring_body_flash,
            axis_name=seq_axis,
            ring_size=ring_size,
            causal=causal,
            scale=scale,
            interpret=bool(interpret),
            window=window,
        )
        # check_vma=False: pallas interpret mode evaluates the kernel
        # jaxpr with non-varying internal consts, tripping the vma checker
        # (jax suggests exactly this workaround); numerics are unaffected.
        return shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)
    fn = functools.partial(
        _ring_body,
        axis_name=seq_axis,
        ring_size=ring_size,
        causal=causal,
        scale=scale,
        vary_axes=(data_axis, model_axis, seq_axis),
        window=window,
    )
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def a2a_attention(
    q, k, v, *, mesh: Mesh, causal: bool = False, scale: float | None = None,
    seq_axis: str = "seq", data_axis: str = "data", model_axis: str = "model",
    use_flash: bool | None = None, block_size: int = 512,
    window: int | None = None,
):
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism over
    ``mesh[seq_axis]`` — the second SP engine beside :func:`ring_attention`.

    One ``lax.all_to_all`` trades each device's sequence shard for a HEAD
    shard: [B, H/tp, T/sp, D] -> [B, H/(tp*sp), T, D]. Every device then
    holds the FULL sequence for its head subset and runs the best
    single-shard kernel (Pallas flash / blockwise / dense) with exact
    causal semantics — no per-step visibility bookkeeping, no striping
    needed for balance (causal work is identical per head). A second
    all_to_all restores the sequence layout.

    Trade-off vs the ring: two collectives total instead of sp-1 ppermute
    hops (latency win, and the a2a rides ICI), but the full [T] sequence
    must fit one device's memory for H/(tp*sp) heads, and heads must tile
    ``tp*sp``. Select per workload with ``DCT_SP_ENGINE`` (ring | a2a) —
    ring for the longest sequences (O(T/sp) memory), a2a when heads are
    plentiful and T fits.

    q,k,v: GLOBAL [B, H, T, D] arrays (jit-sharded); batch rides
    ``data_axis``, heads ``model_axis`` — DP x TP x SP compose in one op.
    """
    sp = mesh.shape[seq_axis]
    b, h, t, _ = q.shape
    if _is_init_trace_escape(q, b, mesh.shape[data_axis]):
        return dense_attention(
            q, k, v, causal=causal, scale=scale, window=window
        )
    tp = mesh.shape[model_axis]
    h_kv = k.shape[1]
    h_local = h // tp if h % tp == 0 else 0
    hkv_local = h_kv // tp if h_kv % tp == 0 else 0
    if (
        b % mesh.shape[data_axis]
        or h % tp
        or h_kv % tp
        or t % sp
        or h_local % sp
        or hkv_local % sp
    ):
        alternative = "or use DCT_SP_ENGINE=ring"
        raise ValueError(
            f"a2a_attention shapes B={b}, H={h} (kv heads {h_kv}), T={t} "
            f"do not tile mesh axes data={mesh.shape[data_axis]}, "
            f"model={tp}, seq={sp} (the seq axis must divide the heads "
            f"per TP shard: H/tp={h_local}, kv/tp={hkv_local}, sp={sp}); "
            f"adjust heads/seq_len or the mesh, {alternative}"
        )
    spec = P(data_axis, model_axis, seq_axis, None)
    flash_on, interpret = _resolve_flash(use_flash)

    def _kernel(ql, kl, vl):
        # Full-sequence single-shard compute on [B_l, H_l/sp, T, D] —
        # each device sees every position for its heads, so windowing is
        # just the single-shard (in-kernel) band mask.
        if flash_on and t % 128 == 0 and t >= 128:
            from dct_tpu.ops.pallas_attention import flash_attention

            return flash_attention(
                ql, kl, vl, causal=causal, scale=scale,
                interpret=bool(interpret), window=window,
            )
        if t > block_size and t % block_size == 0:
            return blockwise_attention(
                ql, kl, vl, block_size=block_size, causal=causal,
                scale=scale, window=window,
            )
        return dense_attention(
            ql, kl, vl, causal=causal, scale=scale, window=window
        )

    def body(ql, kl, vl):
        # seq shard -> head shard: [B_l, H_l, T_l, D] -> [B_l, H_l/sp, T, D]
        ql, kl, vl = (
            lax.all_to_all(a, seq_axis, split_axis=1, concat_axis=2,
                           tiled=True)
            for a in (ql, kl, vl)
        )
        out = _kernel(ql, kl, vl)
        # head shard -> seq shard (the inverse exchange).
        return lax.all_to_all(
            out, seq_axis, split_axis=2, concat_axis=1, tiled=True
        )

    # check_vma=False for the same reason as the flash ring: interpret-
    # mode pallas internals trip the varying-axes checker spuriously.
    return shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _flash_per_shard(kernel, mesh: Mesh | None, q, k, v, *,
                     data_axis: str = "data", model_axis: str = "model"):
    """Run the single-shard flash ``kernel`` under ``mesh``.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), and inside a shard_map it needs EVERY
    mesh axis manual. So on a multi-device mesh the kernel runs per
    shard inside a full-manual shard_map — batch over ``data``, heads
    over ``model``, the layout the surrounding program already has, so
    no data moves. A shape that does not tile the mesh is a sizing bug
    and raises (the batch-1 init trace never gets here — the caller
    sends it down a JAX-level path)."""
    if mesh is None or mesh.size == 1:
        return kernel(q, k, v)
    b, h = q.shape[0], q.shape[1]
    h_kv = k.shape[1]
    n_data, n_model = mesh.shape[data_axis], mesh.shape[model_axis]
    if b % n_data or h % n_model or h_kv % n_model:
        raise ValueError(
            f"flash attention shapes B={b}, H={h} (kv heads {h_kv}) do "
            f"not tile mesh axes data={n_data}, model={n_model}; adjust "
            "batch/heads or the mesh"
        )
    spec = P(data_axis, model_axis, None, None)
    # check_vma=False for the same reason as the flash ring: interpret-
    # mode pallas internals trip the varying-axes checker spuriously.
    return shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def make_attention_fn(mesh: Mesh | None = None, *, causal: bool = False,
                      block_size: int = 512, window: int | None = None):
    """Pick the attention path per :func:`select_attention_path`: ring (or
    the all-to-all engine, ``DCT_SP_ENGINE=a2a``) when the ``seq`` axis is
    populated, the Pallas flash kernel for long single-shard sequences on
    TPU, blockwise/dense otherwise.

    ``window`` (causal sliding-window local attention) composes with
    every path: the single-shard kernels mask, the a2a engine windows its
    full-sequence per-head compute, and the ring engine truncates to the
    in-band hops (O(window) work/communication per device — the engine of
    choice for exactly the long sequences where windowing matters)."""
    _check_window(window, causal)
    if mesh is not None and mesh.shape.get("seq", 1) > 1:
        if sp_engine() == "a2a":
            return functools.partial(
                a2a_attention, mesh=mesh, causal=causal, window=window
            )
        return functools.partial(
            ring_attention, mesh=mesh, causal=causal, window=window
        )

    def attn(q, k, v):
        t = q.shape[-2]
        path = select_attention_path(t, block_size=block_size)
        init_trace = mesh is not None and _is_init_trace_escape(
            q, q.shape[0], mesh.shape["data"]
        )
        if path == "flash" and not init_trace:
            from dct_tpu.ops.pallas_attention import flash_attention

            # The kernels pick their tiles from the shape
            # (pallas_attention.flash_tiles): every multiple of 128 the
            # policy sends here has a tile that divides it. A kernel that
            # fails to compile raises; nothing degrades silently.
            # Windowed calls stay kernel-resident: the band mask lives in
            # the kernel and out-of-band tiles skip compute + DMA.
            return _flash_per_shard(
                functools.partial(
                    flash_attention, causal=causal,
                    interpret=bool(flash_interpret_mode()), window=window,
                ),
                mesh, q, k, v,
            )
        if t > block_size and t % block_size == 0:
            return blockwise_attention(
                q, k, v, block_size=block_size, causal=causal, window=window
            )
        return dense_attention(q, k, v, causal=causal, window=window)

    return attn

"""Pallas flash-attention kernel vs the dense oracle (interpret mode on the
CPU rig; the same kernel compiles via Mosaic on TPU)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dct_tpu.ops.attention import dense_attention
from dct_tpu.ops.pallas_attention import flash_attention
from tests.test_flash_compile_v5e import SHAPES

B, H, T, D = 2, 2, 128, 16

#: (T, block_q, block_k). The first is the historical toy tile; the rest
#: are tiles above 128, as the shape rule picks them on the chip: a square
#: tile pair where the diagonal cuts tiles (0,0), (1,1) and leaves (1,0)
#: interior (no mask built), rectangular pairs either way round, and the
#: rule's own choice (None: one 512 tile, the whole triangle in one step).
TILE_CASES = [
    (128, 32, 32),
    (512, 256, 256),
    (1024, 512, 256),
    (1024, 256, 512),
    (512, None, None),
]
BIG_D = 8  # head size of the T >= 512 cases: interpret mode stays cheap


def _qkv(rng, t):
    """The toy [B, H, T, D] at T=128; above it one batch row at head size
    8, so the interpreter and the dense oracle stay cheap."""
    shape = (B, H, T, D) if t == T else (1, H, t, BIG_D)
    return tuple(
        jnp.asarray(rng.standard_normal(shape), jnp.float32) for _ in range(3)
    )


@pytest.fixture()
def qkv(rng):
    return _qkv(rng, T)


@pytest.mark.parametrize("t,block_q,block_k", TILE_CASES)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(rng, causal, t, block_q, block_k):
    q, k, v = _qkv(rng, t)
    ref = dense_attention(q, k, v, causal=causal)
    out = flash_attention(
        q, k, v, block_q=block_q, block_k=block_k, causal=causal,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("t,block_q,block_k", TILE_CASES)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_matches_dense(rng, causal, t, block_q, block_k):
    """The Pallas backward kernels (dQ / dK+dV) against AD through the
    dense oracle."""
    q, k, v = _qkv(rng, t)

    def loss_flash(q, k, v):
        return flash_attention(
            q, k, v, block_q=block_q, block_k=block_k, causal=causal,
            interpret=True,
        ).sum()

    def loss_dense(q, k, v):
        return dense_attention(q, k, v, causal=causal).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=1e-4)


def test_flash_grad_weighted_cotangent(qkv):
    """Non-uniform output cotangents (a real loss, not .sum()) flow
    correctly through the backward kernels."""
    q, k, v = qkv
    w = jnp.asarray(
        np.random.default_rng(3).standard_normal((B, H, T, D)), jnp.float32
    )

    def loss(f):
        return lambda q, k, v: (f(q, k, v) * w).sum()

    flash = loss(
        lambda q, k, v: flash_attention(
            q, k, v, block_q=32, block_k=64, causal=True, interpret=True
        )
    )
    dense = loss(lambda q, k, v: dense_attention(q, k, v, causal=True))
    g_flash = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=1e-4)


def test_flash_bwd_remat_escape_hatch(qkv, monkeypatch):
    """DCT_FLASH_BWD=remat must produce the same gradients as the kernel
    backward (it differentiates the numerically-identical blockwise path)."""
    q, k, v = qkv

    def loss(q, k, v):
        return flash_attention(
            q, k, v, block_q=32, block_k=32, causal=True, interpret=True
        ).sum()

    g_kernel = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("DCT_FLASH_BWD", "remat")
    g_remat = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for gk, gr in zip(g_kernel, g_remat):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-5)


def test_flash_bf16_io(qkv):
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=2e-2
    )


def test_flash_short_seq_default_blocks(rng):
    """T shorter than the default block size: forward clamps the blocks,
    and the backward must clamp identically instead of crashing."""
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 2, 64, 16)), jnp.float32)
        for _ in range(3)
    )
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    g = jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: dense_attention(q, k, v, causal=True).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for gf, gd in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=1e-4)


@pytest.mark.parametrize(
    "tq,tk,block_q,block_k",
    [(256, 512, 128, 256), (512, 256, 256, 256), (256, 1024, None, None)],
)
def test_flash_rectangular_matches_dense(rng, tq, tk, block_q, block_k):
    """Tq != Tk (the striped ring's blocks; non-causal only): forward on
    the kernel at tiles above 128, backward through the blockwise remat
    the rectangular case takes, both against dense."""
    q = jnp.asarray(rng.standard_normal((1, 2, tq, BIG_D)), jnp.float32)
    k, v = (
        jnp.asarray(rng.standard_normal((1, 2, tk, BIG_D)), jnp.float32)
        for _ in range(2)
    )

    def flash(q, k, v):
        return flash_attention(
            q, k, v, block_q=block_q, block_k=block_k, interpret=True
        )

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(dense_attention(q, k, v)),
        atol=1e-5,
    )
    g_flash = jax.grad(
        lambda q, k, v: (flash(q, k, v) ** 2).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    g_dense = jax.grad(
        lambda q, k, v: (dense_attention(q, k, v) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=1e-4)


def test_flash_rejects_bad_blocks(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=96, block_k=32, interpret=True)


def test_flash_under_jit(qkv):
    q, k, v = qkv
    out = jax.jit(
        lambda q, k, v: flash_attention(
            q, k, v, block_q=32, block_k=32, interpret=True
        )
    )(q, k, v)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# --- sliding window in the kernel ----------------------------------------


#: (T, block_q, block_k, window) above 128: a band whose trailing edge
#: cuts tile (1,0) and skips none; one that also puts whole tiles behind
#: the band (768 x 256: tile (2,0) is skipped, (2,1) edge-cut, (1,0)
#: interior); window == T, which cuts nothing and must mask nothing; and
#: the rule's own tiles with the band inside the single tile.
BIG_WINDOW_CASES = [
    (512, 256, 256, 300),
    (768, 256, 256, 257),
    (512, 256, 256, 512),
    (1024, 512, 256, 384),
    (512, None, None, 200),
]


@pytest.mark.parametrize(
    "t,block_q,block_k,window",
    [(T, 32, 32, w) for w in (1, 17, 32, 100, 128)] + BIG_WINDOW_CASES,
)
def test_flash_window_matches_dense(rng, t, block_q, block_k, window):
    """The in-kernel band mask (incl. the tile-skip conditions: blocks
    entirely behind the band execute nothing, interior blocks build no
    mask) against the masked dense oracle, at windows inside one tile,
    spanning tiles, and >= T."""
    q, k, v = _qkv(rng, t)
    ref = dense_attention(q, k, v, causal=True, window=window)
    out = flash_attention(
        q, k, v, block_q=block_q, block_k=block_k, causal=True,
        interpret=True, window=window,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("bwd_mode", ["kernel", "remat"])
@pytest.mark.parametrize(
    "t,block_q,block_k,window",
    [(T, 32, 32, 17), (T, 32, 32, 64)] + BIG_WINDOW_CASES,
)
def test_flash_window_grad_matches_dense(rng, t, block_q, block_k, window,
                                         bwd_mode, monkeypatch):
    """Windowed backward: both the FA2 backward kernels (band mask +
    tile skip) and the blockwise remat escape against dense AD."""
    monkeypatch.setenv("DCT_FLASH_BWD", bwd_mode)
    q, k, v = _qkv(rng, t)

    def loss_flash(q, k, v):
        return flash_attention(
            q, k, v, block_q=block_q, block_k=block_k, causal=True,
            interpret=True, window=window,
        ).sum()

    def loss_dense(q, k, v):
        return dense_attention(q, k, v, causal=True, window=window).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=1e-4)


def test_flash_window_requires_causal(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="causal"):
        flash_attention(
            q, k, v, block_q=32, block_k=32, causal=False, interpret=True,
            window=8,
        )


@pytest.mark.parametrize(
    "t,block,window,offset_blocks",
    [
        (T, 32, 100, 1), (T, 32, 100, 3),
        # Tiles above 128 one shard away: the band's trailing edge cuts
        # tiles (0,0) and (1,1), (0,1) is interior, (1,0) behind the band.
        (512, 256, 512, 1),
        # The rule's tiles, band inside the one tile.
        (512, None, 700, 1),
    ],
)
def test_flash_lse_q_offset_matches_blockwise(rng, t, block, window,
                                              offset_blocks):
    """The static q_offset (the windowed ring's inter-shard distance)
    against the JAX-level blockwise twin with the same offset — forward
    o AND lse, since the ring's merge weights come from the lse."""
    from dct_tpu.ops.attention import blockwise_attention_lse
    from dct_tpu.ops.pallas_attention import flash_attention_lse

    q, k, v = _qkv(rng, t)
    q_offset = offset_blocks * t  # whole-shard distances like the ring's
    o_k, lse_k = flash_attention_lse(
        q, k, v, block, block, True, None, True, window, q_offset
    )
    o_b, lse_b = blockwise_attention_lse(
        q, k, v, block_size=block or 128, causal=True, window=window,
        q_offset=q_offset,
    )
    # Rows fully out of band produce o=0 and lse ~ -inf in both paths;
    # compare only the finite-lse rows for lse equality.
    finite = np.asarray(lse_b) > -1e29
    np.testing.assert_allclose(
        np.asarray(o_k), np.asarray(o_b), atol=1e-5
    )
    if finite.any():
        np.testing.assert_allclose(
            np.asarray(lse_k)[finite], np.asarray(lse_b)[finite], atol=1e-5
        )


# --- the band grid: the sweep over the band's tiles only -----------------


#: (T, window, block_q, block_k, q heads, kv heads), each a window under
#: which the band grid engages (some key tile is out of every query tile's
#: reach): windows that are no multiple of the tile, one narrower than a
#: tile, a band wider than a tile and narrower than T, rectangular tile
#: pairs either way round (the last two also move a sweep's start back
#: from the band's first tile, in the forward and in dK/dV), and GQA at
#: group 1, 2 and 4. In every case the first query tiles' band reaches
#: before position 0.
BAND_CASES = [
    (1024, 384, 128, 128, 2, 2),
    (1024, 100, 128, 128, 2, 1),
    (2048, 512, 256, 256, 2, 2),
    (1024, 256, 256, 128, 4, 2),
    (1024, 300, 128, 256, 2, 2),
    (1024, 300, 256, 128, 4, 1),
]


def _band_qkv(rng, t, h, h_kv):
    shape = lambda heads: (1, heads, t, BIG_D)
    return tuple(
        jnp.asarray(rng.standard_normal(shape(n)), jnp.float32)
        for n in (h, h_kv, h_kv)
    )


def _dense_lse(q, k, window):
    """log-sum-exp of the band-masked scores, from the full matrix."""
    from dct_tpu.ops.attention import expand_kv

    k, _ = expand_kv(q, k, k)
    s = jnp.einsum("...qd,...kd->...qk", q, k) / np.sqrt(q.shape[-1])
    pos = jnp.arange(q.shape[2])
    diff = pos[:, None] - pos[None, :]
    s = jnp.where((diff >= 0) & (diff < window), s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1)


def _engages(t, window, block_q, block_k):
    from dct_tpu.ops import pallas_attention as pa

    return (pa._kv_band(t, window, block_q, block_k) is not None
            and pa._q_band(t, window, block_q, block_k) is not None)


@pytest.mark.parametrize("t,window,block_q,block_k,h,h_kv", BAND_CASES)
def test_flash_band_grid_matches_dense(rng, t, window, block_q, block_k,
                                       h, h_kv):
    """The forward on the band grid: outputs and lse against the dense
    oracle, every row (the first tiles' clamped bands among them)."""
    from dct_tpu.ops.pallas_attention import flash_attention_lse

    assert _engages(t, window, block_q, block_k)
    q, k, v = _band_qkv(rng, t, h, h_kv)
    o, lse = flash_attention_lse(
        q, k, v, block_q, block_k, True, None, True, window)
    ref = dense_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(_dense_lse(q, k, window)), atol=1e-5)


@pytest.mark.parametrize("t,window,block_q,block_k,h,h_kv", BAND_CASES)
def test_flash_band_grid_grad_matches_dense(rng, t, window, block_q, block_k,
                                            h, h_kv):
    """dQ and dK/dV on the band grid against AD through the dense oracle,
    under a weighted cotangent."""
    assert _engages(t, window, block_q, block_k)
    q, k, v = _band_qkv(rng, t, h, h_kv)
    w = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def loss_flash(q, k, v):
        return (flash_attention(
            q, k, v, block_q=block_q, block_k=block_k, causal=True,
            interpret=True, window=window,
        ) * w).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal=True, window=window)
                * w).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=1e-4)


def _pallas_grids(fn, *args):
    """The grid of every ``pallas_call`` in ``fn``'s jaxpr."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [
        tuple(e.params["grid_mapping"].grid) for e in jaxpr.jaxpr.eqns
        if e.primitive.name == "pallas_call"
    ]


@pytest.mark.parametrize("shape", list(SHAPES))
def test_flash_grid_steps_at_the_cells_shapes(shape):
    """``flash_grid_steps`` against the grids the three ``pallas_call``s
    receive at each cell's shape on the rule's tiles (the shapes the v5e
    compile test holds the kernels to): the band grid at mellum2's
    sliding layer (2,048 -> 512 steps a call, 480 with work) and at
    laguna's (a 512 band on 1024-row tiles: 4,096 -> 1,024 steps, 960 with
    work), and every other call's full grid, ``(b*h, T/bq, T/bk)``."""
    from dct_tpu.ops import pallas_attention as pa

    b, h, h_kv, t, d, window, *rest = SHAPES[shape]
    d_v = rest[0] if rest else d
    bq, bk = pa.flash_tiles(t, t, d, jnp.bfloat16, d_v)
    counts = pa.flash_grid_steps(t, window, bq, bk, h, h_kv, b=b)
    full = b * h * (t // bq) * (t // bk)
    if shape == "mellum2_12b_swa_seq8192":
        assert counts == {k: (512, 480) for k in ("fwd", "dkdv", "dq")}
        assert full == 2048
    elif shape == "laguna_xs2_swa_seq8192":
        # Two key tiles a query tile, one where the first has no earlier.
        assert counts == {k: (1024, 960) for k in ("fwd", "dkdv", "dq")}
        assert full == 4096
    else:
        assert {k: n for k, (n, _) in counts.items()} == {
            "fwd": full, "dkdv": full, "dq": full}

    arg = lambda heads, width, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        (b, heads, t, width), dtype)
    q, k, v, o = arg(h, d), arg(h_kv, d), arg(h_kv, d_v), arg(h, d_v)
    lse = jax.ShapeDtypeStruct((b, h, t), jnp.float32)
    kw = dict(causal=True, scale=None, interpret=False, window=window)
    grids = {
        "fwd": _pallas_grids(
            lambda q, k, v: pa._flash_fwd(q, k, v, with_lse=True, **kw),
            q, k, v),
        "dkdv": _pallas_grids(
            lambda *a: pa._flash_bwd_dkdv(*a, **kw), q, k, v, o, lse, o),
        "dq": _pallas_grids(
            lambda *a: pa._flash_bwd_dq(*a, **kw), q, k, v, o, lse, o),
    }
    for kernel, (grid,) in grids.items():
        assert math.prod(grid) == counts[kernel][0], kernel

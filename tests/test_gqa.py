"""Grouped-query attention (GQA): K/V carry fewer heads than Q — the
KV-bandwidth lever (smaller projections, KV HBM reads divided by the
group size in the Pallas kernel, smaller KV payloads on the SP engines'
collectives). No reference counterpart (the reference has no attention
at all, SURVEY §2.2); capability beyond parity.

Contract under test: group-major head layout everywhere — q head
``g*Hg + j`` reads kv head ``g`` — across the op layer (expand_kv, the
flash kernel's divided index maps), the model layer (the fused
``(G, Hg+2, Dh)`` projection, which degenerates to the classic
``(H, 3, Dh)`` when n_kv_heads == n_heads), the SP engines, and the
numpy serving twin."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dct_tpu.config import MeshConfig, ModelConfig
from dct_tpu.models.registry import get_model
from dct_tpu.ops.attention import (
    a2a_attention,
    blockwise_attention,
    dense_attention,
    expand_kv,
    ring_attention,
)
from dct_tpu.parallel.mesh import make_mesh

B, H, HKV, T, D = 2, 4, 2, 64, 8


@pytest.fixture()
def grouped_qkv(rng):
    q = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, HKV, T, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, HKV, T, D)), jnp.float32)
    return q, k, v


def _dense_oracle(q, k, v, causal=False, window=None):
    """Independent oracle: explicit group-major repeat + dense softmax."""
    group = q.shape[1] // k.shape[1]
    kf = np.repeat(np.asarray(k, np.float64), group, axis=1)
    vf = np.repeat(np.asarray(v, np.float64), group, axis=1)
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q, np.float64), kf)
    s /= np.sqrt(q.shape[-1])
    if causal:
        pos = np.arange(q.shape[-2])
        mask = pos[:, None] >= pos[None, :]
        if window is not None:
            mask &= pos[:, None] - pos[None, :] < window
        s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, vf)


def test_expand_kv_group_major(grouped_qkv):
    q, k, v = grouped_qkv
    ke, ve = expand_kv(q, k, v)
    assert ke.shape == q.shape
    # q head g*Hg + j must read kv head g (consecutive repeat).
    group = H // HKV
    for h in range(H):
        np.testing.assert_array_equal(
            np.asarray(ke[:, h]), np.asarray(k[:, h // group])
        )


def test_expand_kv_rejects_non_dividing():
    q = jnp.zeros((1, 3, 8, 4))
    k = v = jnp.zeros((1, 2, 8, 4))
    with pytest.raises(ValueError, match="divisible"):
        expand_kv(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_grouped_dense_and_blockwise_match_oracle(grouped_qkv, causal):
    q, k, v = grouped_qkv
    ref = _dense_oracle(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(dense_attention(q, k, v, causal=causal)), ref, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(
            blockwise_attention(q, k, v, block_size=16, causal=causal)
        ),
        ref, atol=1e-5,
    )


#: (T, block_q, block_k) of the grouped kernel cases: the toy tile, and
#: tiles above 128 (group 2 on [1, 4, T, 8]): square with an interior
#: tile, rectangular either way round, and the shape rule's own (None).
GROUPED_TILES = [
    (T, 16, 16), (512, 256, 256), (1024, 512, 256), (1024, 256, 512),
    (512, None, None),
]


def _grouped(rng, t):
    if t == T:
        b, d = B, D
    else:
        b, d = 1, 8
    q = jnp.asarray(rng.standard_normal((b, H, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, HKV, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, HKV, t, d)), jnp.float32)
    return q, k, v


def _band(t, window):
    """The toy band (24 of 64) scaled to the case's length (192 of 512,
    384 of 1024): its trailing edge cuts some tiles above 128, leaves
    others interior and puts the far ones wholly behind it."""
    return None if window is None else window * t // T


@pytest.mark.parametrize("t,block_q,block_k", GROUPED_TILES)
@pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                           (True, 24)])
def test_grouped_flash_matches_oracle(rng, causal, window, t, block_q,
                                      block_k):
    """The kernel's divided KV index maps (KV tiles fetched once per
    group, never materialized at H heads) against the repeat oracle —
    composed with the causal skip and the window band."""
    from dct_tpu.ops.pallas_attention import flash_attention

    q, k, v = _grouped(rng, t)
    window = _band(t, window)
    ref = _dense_oracle(q, k, v, causal=causal, window=window)
    out = flash_attention(
        q, k, v, block_q=block_q, block_k=block_k, causal=causal,
        interpret=True, window=window,
    )
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


@pytest.mark.parametrize("t,block_q,block_k", GROUPED_TILES)
@pytest.mark.parametrize("bwd_mode", ["kernel", "remat"])
@pytest.mark.parametrize("window", [None, 24])
def test_grouped_flash_grad_matches_dense(rng, bwd_mode, window, t, block_q,
                                          block_k, monkeypatch):
    """GQA backward, both modes: the kernel path grids dK/dV over the KV
    heads and sweeps the group's q heads sequentially into one
    accumulator (no race — a q-head-parallel grid would have one); the
    remat escape gets the group-sum from AD through expand_kv's
    broadcast. Both must equal dense AD, composed with the window."""
    from dct_tpu.ops.pallas_attention import flash_attention

    monkeypatch.setenv("DCT_FLASH_BWD", bwd_mode)
    q, k, v = _grouped(rng, t)
    window = _band(t, window)

    def loss_flash(q, k, v):
        return flash_attention(
            q, k, v, block_q=block_q, block_k=block_k, causal=True,
            interpret=True, window=window,
        ).sum()

    def loss_dense(q, k, v):
        return dense_attention(q, k, v, causal=True, window=window).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    assert g_flash[1].shape == k.shape  # grads stay grouped
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=1e-4)


@pytest.mark.parametrize("engine", ["ring", "a2a"])
def test_grouped_sp_engines_match_oracle(grouped_qkv, engine, monkeypatch):
    """Both SP engines with grouped KV: the ring rotates the grouped
    shards (ICI payload at n_kv_heads) and expands per use; a2a
    exchanges the grouped KV and the kernel consumes them grouped."""
    monkeypatch.setenv("DCT_RING_STRIPED", "off")
    q, k, v = grouped_qkv
    # a2a exchanges the KV head axis over sp, so kv-heads-per-TP-shard
    # must divide sp — with HKV=2 that means tp=1 here; the ring has no
    # such constraint and runs tp=2.
    tp = 2 if engine == "ring" else 1
    mesh = make_mesh(MeshConfig(data=1, model=tp, seq=2), allow_subset=True)
    ref = _dense_oracle(q, k, v, causal=True)
    fn = ring_attention if engine == "ring" else a2a_attention
    out = fn(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


def test_grouped_windowed_ring_matches_oracle(grouped_qkv, monkeypatch):
    monkeypatch.setenv("DCT_RING_STRIPED", "off")
    q, k, v = grouped_qkv
    mesh = make_mesh(MeshConfig(data=1, model=1, seq=4), allow_subset=True)
    ref = _dense_oracle(q, k, v, causal=True, window=12)
    out = ring_attention(q, k, v, mesh=mesh, causal=True, window=12)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


# --- model layer ---------------------------------------------------------


CFG = dict(
    name="weather_transformer_causal", seq_len=8, d_model=16, n_heads=4,
    n_layers=1, d_ff=32, dropout=0.0,
)


def test_mha_param_layout_unchanged_without_gqa():
    """n_kv_heads off must produce byte-identical param SHAPES to the
    classic fused (H, 3, Dh) layout — existing checkpoints keep loading."""
    model = get_model(ModelConfig(**CFG), input_dim=5)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 5)))
    kern = params["params"]["block_0"]["attn"]["qkv_proj"]["kernel"]
    assert kern.shape == (16, 3 * 16)


def test_gqa_shrinks_qkv_projection():
    model = get_model(ModelConfig(**CFG, n_kv_heads=2), input_dim=5)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 5)))
    kern = params["params"]["block_0"]["attn"]["qkv_proj"]["kernel"]
    # (H + 2*G) * Dh = (4 + 4) * 4 = 32 outputs instead of 48.
    assert kern.shape == (16, 32)


def test_gqa_model_trains_and_matches_mesh(rng):
    """The causal family with GQA: finite loss meshless, and the same
    params produce the same logits over a seq-sharded mesh (ring engine
    with grouped KV shards)."""
    x = rng.standard_normal((4, 8, 5)).astype(np.float32)
    meshless = get_model(ModelConfig(**CFG, n_kv_heads=2), input_dim=5)
    params = meshless.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 5)))
    out_local = meshless.apply(params, jnp.asarray(x))
    assert np.isfinite(np.asarray(out_local)).all()

    mesh = make_mesh(MeshConfig(data=2, model=2, seq=2))
    ringed = get_model(
        ModelConfig(**CFG, n_kv_heads=2), input_dim=5, mesh=mesh
    )
    out_ring = ringed.apply(params, jnp.asarray(x))
    np.testing.assert_allclose(
        np.asarray(out_ring), np.asarray(out_local), atol=1e-4
    )


def test_gqa_rejects_non_dividing_heads():
    model = get_model(ModelConfig(**CFG, n_kv_heads=3), input_dim=5)
    with pytest.raises(ValueError, match="divide"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 5)))


def test_gqa_serving_numpy_parity(rng):
    """The numpy serving twin mirrors the GQA layout AND the sliding
    window — last-position logits must equal the JAX model's."""
    from dct_tpu.serving.runtime import forward_numpy
    from dct_tpu.serving.score_gen import _flatten_params

    cfg = ModelConfig(**CFG, n_kv_heads=2, attn_window=3)
    model = get_model(cfg, input_dim=5)
    variables = model.init(jax.random.PRNGKey(2), jnp.zeros((1, 8, 5)))
    params = {"params": variables["params"]}
    x = rng.standard_normal((3, 8, 5)).astype(np.float32)
    jax_logits = np.asarray(model.apply(params, jnp.asarray(x)))[:, -1]
    weights = _flatten_params(params["params"])
    meta = {
        "model": "weather_transformer_causal", "input_dim": 5,
        "seq_len": 8, "d_model": 16, "n_heads": 4, "n_layers": 1,
        "d_ff": 32, "num_classes": 2, "dropout": 0.0, "horizon": 1,
        "n_kv_heads": 2, "attn_window": 3,
        "feature_names": ["a"] * 5,
    }
    np_logits = forward_numpy(weights, meta, x)
    np.testing.assert_allclose(np_logits, jax_logits, atol=2e-5)


def test_windowed_serving_numpy_parity_without_gqa(rng):
    """Regression: serving previously IGNORED attn_window — a windowed
    causal model served with full attention. Now the band is honored."""
    from dct_tpu.serving.runtime import forward_numpy
    from dct_tpu.serving.score_gen import _flatten_params

    cfg = ModelConfig(**CFG, attn_window=2)
    model = get_model(cfg, input_dim=5)
    variables = model.init(jax.random.PRNGKey(4), jnp.zeros((1, 8, 5)))
    params = {"params": variables["params"]}
    x = rng.standard_normal((2, 8, 5)).astype(np.float32)
    jax_logits = np.asarray(model.apply(params, jnp.asarray(x)))[:, -1]
    weights = _flatten_params(params["params"])
    meta = {
        "model": "weather_transformer_causal", "input_dim": 5,
        "seq_len": 8, "d_model": 16, "n_heads": 4, "n_layers": 1,
        "d_ff": 32, "num_classes": 2, "dropout": 0.0, "horizon": 1,
        "attn_window": 2, "feature_names": ["a"] * 5,
    }
    np_logits = forward_numpy(weights, meta, x)
    np.testing.assert_allclose(np_logits, jax_logits, atol=2e-5)


@pytest.mark.parametrize(
    "family",
    ["weather_transformer", "weather_transformer_causal",
     "weather_transformer_pp", "weather_moe"],
)
def test_gqa_every_family_numpy_parity(family, rng):
    """Every deployable transformer-family must honor n_kv_heads
    end-to-end into the numpy serving twin (the MoE family initially
    missed the threading and crashed at serve time — code-review r4)."""
    from dct_tpu.serving.runtime import forward_numpy
    from dct_tpu.serving.score_gen import _flatten_params

    cfg = ModelConfig(
        name=family, seq_len=10, d_model=16, n_heads=4, n_layers=2,
        d_ff=32, dropout=0.0, n_kv_heads=2,
    )
    model = get_model(cfg, input_dim=5)
    variables = model.init(jax.random.PRNGKey(5), jnp.zeros((1, 10, 5)))
    params = {"params": variables["params"]}
    meta = {
        "model": family, "input_dim": 5, "seq_len": 10, "d_model": 16,
        "n_heads": 4, "n_layers": 2, "d_ff": 32, "n_experts": 4,
        "capacity_factor": 1.25, "n_stages": 2, "num_classes": 2,
        "dropout": 0.0, "horizon": 1, "n_kv_heads": 2,
        "feature_names": [f"f{i}_norm" for i in range(5)],
    }
    x = rng.standard_normal((3, 10, 5)).astype(np.float32)
    jax_logits = np.asarray(model.apply(params, jnp.asarray(x), train=False))
    if family == "weather_transformer_causal":
        jax_logits = jax_logits[:, -1]
    np_logits = forward_numpy(_flatten_params(params["params"]), meta, x)
    np.testing.assert_allclose(np_logits, jax_logits, atol=2e-5)


def test_serving_normalizes_negative_window_and_kv_like_registry(rng):
    """A negative attn_window/n_kv_heads sentinel trains as OFF (registry
    uses '> 0'); serving must normalize identically, not serve an
    all-masked band (code-review r4)."""
    from dct_tpu.serving.runtime import forward_numpy
    from dct_tpu.serving.score_gen import _flatten_params

    model = get_model(ModelConfig(**CFG), input_dim=5)
    variables = model.init(jax.random.PRNGKey(6), jnp.zeros((1, 8, 5)))
    params = {"params": variables["params"]}
    x = rng.standard_normal((2, 8, 5)).astype(np.float32)
    jax_logits = np.asarray(model.apply(params, jnp.asarray(x)))[:, -1]
    weights = _flatten_params(params["params"])
    meta = {
        "model": "weather_transformer_causal", "input_dim": 5,
        "seq_len": 8, "d_model": 16, "n_heads": 4, "n_layers": 1,
        "d_ff": 32, "num_classes": 2, "dropout": 0.0, "horizon": 1,
        "attn_window": -1, "n_kv_heads": -1,
        "feature_names": ["a"] * 5,
    }
    np.testing.assert_allclose(
        forward_numpy(weights, meta, x), jax_logits, atol=2e-5
    )


def test_registry_normalizes_negative_kv_heads():
    """The registry must treat n_kv_heads <= 0 as OFF ('> 0' rule, same
    as attn_window and serving) — truthiness alone would pass -1 through
    to a negative head count (4 % -1 == 0 in Python) and crash init
    (code-review r4)."""
    model = get_model(ModelConfig(**CFG, n_kv_heads=-1), input_dim=5)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 5)))
    kern = params["params"]["block_0"]["attn"]["qkv_proj"]["kernel"]
    assert kern.shape == (16, 3 * 16)  # classic MHA layout

"""The hybrid family as the laguna layer (full layers of one head count
with a half-width YaRN rotation, windowed layers of another with the whole
head rotated plainly, a sigmoid gate on every head's output, a leading
dense layer, then sigmoid-routed SwiGLU experts beside a shared expert with
the routed scale and the selection bias's balancing update) against its
plain float32 reference, at small widths on the CPU with seeded weights;
the partial rotation, the gate and the YaRN tables at 64 rotated dims by
hand; the heads' gate on a ``model`` axis; the four shares of the experts
against the uncut layer; and the accepted configurations, without a
gate."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding

import reference_laguna_moe as REF
from dct_tpu.config import ModelConfig
from dct_tpu.models.moe import MoEFFN
from dct_tpu.models.registry import get_model
from dct_tpu.models.transformer import (
    MultiHeadAttention,
    WeatherTransformer,
    apply_rope,
    rope_tables,
    yarn_tables,
)
from dct_tpu.ops.attention import make_attention_fn
from dct_tpu.parallel.sharding_rules import spec_for_path
from dct_tpu.train.state import create_train_state
from dct_tpu.train.steps import make_train_step

TOL = 2e-5
SPEED = 0.01
HERE = os.path.dirname(os.path.abspath(__file__))
TYPES = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
YARN = {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 64.0,
        "original_max_position_embeddings": 32, "beta_fast": 64.0,
        "beta_slow": 1.0, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}

#: A small laguna: one dense layer, then one whole period (three layers
#: under a window of 16 of 64 positions with 8 query heads, one full layer
#: with 6), 2 key-value heads of 16 on a hidden size of 64 (heads x head is
#: 96 or 128, not 64), the full layers rotating 8 of a head's 16 dims; 32
#: experts of which 8 are held, top-8, a shared expert.
REF_CONFIG = {
    "num_hidden_layers": 5, "layer_types": TYPES + TYPES[1:],
    "mlp_layer_types": ["dense"] + ["sparse"] * 8,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6, 8, 8, 8, 6],
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 16,
    "rope_parameters": {
        "full_attention": YARN,
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0,
                              "partial_rotary_factor": 1}},
    "gating": True, "rms_norm_eps": 1e-6, "num_experts_per_tok": 8,
    "num_experts": 8, "first_expert": 8, "moe_routed_scaling_factor": 2.5,
}
ENV = {
    "DCT_MODEL": "weather_hybrid_moe_causal", "DCT_D_MODEL": "64",
    "DCT_N_HEADS": "6", "DCT_N_KV_HEADS": "2", "DCT_HEAD_DIM": "16",
    "DCT_N_LAYERS": "5", "DCT_D_FF": "96", "DCT_SEQ_LEN": "64",
    "DCT_LAYER_TYPES": ",".join(TYPES), "DCT_ATTN_WINDOW": "16",
    "DCT_ATTN_KINDS": "full_attention:rotary_fraction=0.5;"
                      "sliding_attention:n_heads=8,rope_theta=10000",
    "DCT_ATTN_GATE": "per_head",
    "DCT_POS_EMBED": "rope", "DCT_ROPE_THETA": "500000",
    "DCT_ROPE_SCALING": "yarn", "DCT_ROPE_FACTOR": "64",
    "DCT_ROPE_ORIGINAL_LEN": "32", "DCT_ROPE_BETA_FAST": "64",
    "DCT_ROPE_BETA_SLOW": "1",
    "DCT_ROPE_ATTENTION_FACTOR": "1.4158883083359672",
    "DCT_DROPOUT": "0", "DCT_NORM": "rmsnorm", "DCT_NORM_EPS": "1e-6",
    "DCT_MLP": "swiglu", "DCT_USE_BIAS": "0", "DCT_NUM_DENSE_LAYERS": "1",
    "DCT_N_EXPERTS": "32", "DCT_ROUTER_TOP_K": "8", "DCT_MOE_D_FF": "24",
    "DCT_MOE_SHARED_D_FF": "24", "DCT_EXPERTS_HELD": "8",
    "DCT_FIRST_EXPERT": "8", "DCT_ROUTED_SCALING": "2.5",
    "DCT_ROUTER_GATE_EPS": "1e-20", "DCT_BIAS_UPDATE_SPEED": str(SPEED),
}


def _from_env(env):
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        return ModelConfig.from_env()
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.update({k: v})


def _model(**changed):
    return get_model(
        _from_env({**ENV, **changed}), input_dim=5, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def family():
    """(model, params with a seeded non-zero selection bias, x, y), the
    model built from the environment the way ``RunConfig.from_env`` builds
    it."""
    model = _model()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 64, 5)).astype(np.float32)
    y = rng.integers(0, 2, (2, 64)).astype(np.int32)
    params = jax.device_get(
        jax.jit(model.init)(jax.random.PRNGKey(1), jnp.asarray(x[:1]))
    )["params"]
    for block in params.values():
        if "moe" in block:
            block["moe"]["expert_bias"] = (
                0.05 * rng.standard_normal(32)).astype(np.float32)
    return model, params, x, y


def _ce(logits, y):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], -1).mean()


def test_family_reads_the_kinds_the_gate_and_the_router_from_env(family):
    model, params, _, _ = family
    assert model.layer_types == tuple(TYPES)
    kinds = dict(model.attn_kinds)
    assert (kinds["full_attention"].n_heads,
            kinds["full_attention"].rotary_fraction) == (None, 0.5)
    assert (kinds["sliding_attention"].n_heads,
            kinds["sliding_attention"].rope_theta) == (8, 10000.0)
    for i, heads in enumerate([6, 8, 8, 8, 6]):
        attn = params[f"block_{i}"]["attn"]
        assert {k: v["kernel"].shape for k, v in attn.items()} == {
            "qkv_proj": (64, (heads + 2 * 2) * 16),
            "o_proj": (heads * 16, 64), "gate_proj": (64, heads)}
    assert "ffn_gate" in params["block_0"] and "moe" not in params["block_0"]
    moe = params["block_4"]["moe"]
    assert moe["experts_in_kernel"].shape == (8, 64, 24)
    assert moe["shared_gate"]["kernel"].shape == (64, 24)
    assert moe["router"]["kernel"].shape == (64, 32)
    assert moe["expert_bias"].shape == (32,)
    for changed, match in [
        ({"DCT_ATTN_KINDS": "full_attention:heads=4"}, "unknown key"),
        ({"DCT_ATTN_KINDS": "conv:n_heads=4"}, "kind"),
        ({"DCT_ATTN_GATE": "per_token"}, "gate"),
        ({"DCT_ATTN_KINDS": "full_attention:rotary_fraction=0.3"},
         "rotary_dim"),
    ]:
        with pytest.raises(ValueError, match=match):
            _model(**changed).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 64, 5)))


@pytest.fixture(scope="module")
def outputs(family):
    """The program's f32 logits and the reference's forward (logits and its
    own choice) on the family's batch, once."""
    model, params, x, _ = family
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, x, train=False))
    return got, REF.forward(params, x, REF_CONFIG)


@pytest.mark.parametrize("what", ["logits", "loss", "gradients", "step"])
def test_program_matches_the_reference_in_float32(family, outputs, what):
    model, params, x, y = family
    got, ref = outputs
    kw = REF.settings(REF_CONFIG)
    want_loss = REF.cross_entropy(ref["logits"], y)
    with jax.default_matmul_precision("highest"):
        if what == "logits":
            assert got.shape == ref["logits"].shape == (2, 64, 2)
            np.testing.assert_allclose(got, ref["logits"], rtol=0, atol=TOL)
        elif what == "loss":
            assert abs(REF.cross_entropy(got, y) - want_loss) < TOL
        elif what == "gradients":
            got = jax.jit(jax.grad(lambda p: _ce(
                model.apply({"params": p}, x, train=False), y)))(params)
            want = jax.jit(jax.grad(
                lambda p: REF.objective(p, x, y, **kw)))(params)
            flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
            flat_got = jax.tree_util.tree_leaves_with_path(got)
            assert len(flat_got) == len(flat_want) == 3 + 8 + 4 * 13
            for path, g in flat_got:
                np.testing.assert_allclose(
                    g, flat_want[path], rtol=0, atol=TOL, err_msg=str(path))
            # The gate carries gradient; the selection bias is under
            # stop_gradient: Adam sees zero.
            assert np.abs(
                got["block_1"]["attn"]["gate_proj"]["kernel"]).max() > 10 * TOL
            assert not np.asarray(got["block_1"]["moe"]["expert_bias"]).any()
        else:
            # One optimizer step of the program reports the reference's
            # loss, and every routed layer's bias moves by the reference's
            # balancing update of the routing that step saw.
            state = create_train_state(
                model, input_dim=5, lr=1e-3, seed=1,
                example_shape=(1, 64, 5), grad_clip_norm=1.0)
            state = state.replace(params={"params": params})
            new, metrics = make_train_step(donate=False)(
                state, jnp.asarray(x), jnp.asarray(y), jnp.ones(2))
            assert abs(float(metrics["train_loss"]) - want_loss) < TOL
            topk = ref["topk"]  # [N, M, T, k]
            for m in range(4):
                name = f"block_{m + 1}"
                bias = params[name]["moe"]["expert_bias"]
                want = REF.bias_step(bias, topk[:, m], SPEED)
                assert 0 < np.abs(want - bias).max() <= SPEED * 1.001
                np.testing.assert_allclose(
                    new.params["params"][name]["moe"]["expert_bias"], want,
                    rtol=0, atol=1e-7)


def _layer(held, first):
    return MoEFFN(
        d_model=32, d_ff=24, n_experts=32, aux_weight=0.0,
        dispatch="grouped", top_k=8, experts_held=held, first_expert=first,
        routed_scale=2.5, gate_eps=1e-20, shared_d_ff=24)


def _apply(layer, p, x):
    with jax.default_matmul_precision("highest"):
        out, sown = layer.apply(
            {"params": p}, x, mutable=["counters", "intermediates"])
    return np.asarray(out), jax.device_get(sown)


def test_four_shares_routed_parts_and_the_shared_expert_once_add_up():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((2, 48, 32)).astype(np.float32)
    p = jax.device_get(_layer(0, 0).init(jax.random.PRNGKey(5), x))["params"]
    p["expert_bias"] = (0.05 * rng.standard_normal(32)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want, *_ = jax.jit(lambda u, q: REF._moe(
            u, q, top_k=8, first=0, scaling=2.5, routing=None))(
                jnp.asarray(x.reshape(-1, 32)), p)
    uncut, _ = _apply(_layer(0, 0), p, x)
    np.testing.assert_allclose(uncut.reshape(-1, 32), want, rtol=0, atol=TOL)
    shared = np.asarray(REF._gated_mlp(
        jnp.asarray(x.reshape(-1, 32)), p["shared_gate"]["kernel"],
        p["shared_in"]["kernel"], p["shared_out"]["kernel"]))
    total, rows = np.zeros_like(shared), 0
    for share in range(4):
        first = 8 * share
        held = {
            k: (v[first:first + 8] if k.startswith("experts_") else v)
            for k, v in p.items()}
        out, sown = _apply(_layer(8, first), held, x)
        routed = out.reshape(-1, 32) - shared  # every share computes it
        assert np.abs(routed).max() > 100 * TOL
        total += routed
        rows += int(sown["counters"]["moe_rows"][0].sum())
        assert int(sown["counters"]["moe_rows_overflowed"][0]) == 0
    assert rows == 96 * 8  # every routed row is some share's
    np.testing.assert_allclose(total + shared, want, rtol=0, atol=TOL)


def test_a_rotation_over_the_whole_head_is_todays_and_the_rest_passes():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 3, 40, 16)), jnp.float32)
    cos, sin = rope_tables(40, 16, 10000.0)
    # The rotation as it was written before a rotated share existed.
    half = 8
    x1, x2 = x[..., :half], x[..., half:]
    before = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    np.testing.assert_array_equal(apply_rope(x, cos, sin), before)
    # Six of sixteen rotate: those equal a rotation of six alone, the other
    # ten are the input's.
    cos6, sin6 = rope_tables(40, 6, 10000.0)
    part = apply_rope(x, cos6, sin6)
    np.testing.assert_array_equal(part[..., 6:], x[..., 6:])
    np.testing.assert_array_equal(
        part[..., :6], apply_rope(x[..., :6], cos6, sin6))
    assert np.abs(part[..., :6] - x[..., :6]).max() > 0.1
    # In the attention: a rotary_dim of the whole head is the default's
    # program.
    u = jnp.asarray(rng.standard_normal((1, 40, 32)), jnp.float32)
    p = _attention(None).init(jax.random.PRNGKey(0), u)
    whole = _attention(None).apply(p, u)
    np.testing.assert_array_equal(whole, _attention(16).apply(p, u))
    assert np.abs(whole - _attention(8).apply(p, u)).max() > 1e-4


def _attention(rotary_dim, gate=""):
    """A small GQA attention at a given rotated width and gate."""
    return MultiHeadAttention(
        32, 4, make_attention_fn(None, causal=True), n_kv_heads=2,
        rope=True, use_bias=False, head_dim=16, rotary_dim=rotary_dim,
        gate=gate)


def test_yarn_tables_at_64_rotated_dims_against_a_hand_worked_case():
    """The published full layers rotate 64 of 128 dims: base 500,000,
    factor 64, trained length 4,096, beta_fast 64, beta_slow 1, over 64
    dims. The pair that turns 64 times in 4,096 positions is 64 ln(4096 /
    128 pi) / (2 ln 500000) = 5.66, floored 5; the one that turns once 64
    ln(4096 / 2 pi) / (2 ln 500000) = 15.80, ceiled 16."""
    kw = dict(factor=64.0, original_len=4096, beta_fast=64.0, beta_slow=1.0,
              attention_factor=1.4158883083359672)
    cos, sin = yarn_tables(8192, 64, 500000.0, **kw)
    assert cos.shape == sin.shape == (8192, 32)
    # The stated factor is 0.1 ln 64 + 1 = 1.41589, on cos and sin alike.
    assert 0.1 * np.log(64.0) + 1 == pytest.approx(1.4158883083359672)
    np.testing.assert_allclose(cos[0], 1.4158883083359672, rtol=1e-7)
    np.testing.assert_array_equal(sin[0], 0.0)
    inv = np.arctan2(sin[1].astype(np.float64), cos[1].astype(np.float64))
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-5)      # kept
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-3)
    ramp = (10 - 5) / (16 - 5)  # pair 10, part way up
    np.testing.assert_allclose(
        inv[10], plain[10] * (ramp / 64 + 1 - ramp), rtol=1e-5)
    # The reference's tables over the rotated half of a 128-wide head are
    # the same numbers.
    ref_cos, ref_sin = REF.rotation(64, 128, {
        **YARN, "original_max_position_embeddings": 4096})
    short_cos, short_sin = yarn_tables(64, 64, 500000.0, **kw)
    np.testing.assert_allclose(ref_cos, short_cos, rtol=0, atol=1e-6)
    np.testing.assert_allclose(ref_sin, short_sin, rtol=0, atol=1e-6)


def test_a_gate_of_zero_weights_halves_each_heads_output():
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.standard_normal((2, 40, 32)), jnp.float32)
    key = jax.random.PRNGKey(0)
    plain = _attention(None).init(key, u)["params"]
    gated = _attention(None, "per_head").init(key, u)["params"]
    assert gated["gate_proj"]["kernel"].shape == (32, 4)
    assert "gate_proj" not in plain
    gated = {**plain, "gate_proj": {"kernel": np.zeros((32, 4), np.float32)}}
    with jax.default_matmul_precision("highest"):
        half = _attention(None, "per_head").apply({"params": gated}, u)
        whole = _attention(None).apply({"params": plain}, u)
    np.testing.assert_allclose(half, 0.5 * whole, rtol=0, atol=1e-6)
    # The gate's product runs under its own device scope.
    text = jax.jit(lambda p, v: _attention(None, "per_head").apply(
        {"params": p}, v)).lower(gated, u).as_text(debug_info=True)
    assert "attn.gate" in text


@pytest.mark.parametrize("heads", [48, 64])
def test_the_published_head_counts_build_at_hidden_2048(heads):
    """48 x 128 and 64 x 128 are not 2048: the stated head width frees the
    heads from dividing the hidden size, and without it they must."""
    kw = dict(input_dim=5, seq_len=256, d_model=2048, n_heads=heads,
              n_layers=1, d_ff=64, per_position=True, n_kv_heads=8,
              pos_embed="rope", norm="rmsnorm", mlp="swiglu", use_bias=False,
              attn_gate="per_head")
    x = jnp.zeros((1, 256, 5), jnp.float32)
    shapes = jax.eval_shape(
        WeatherTransformer(head_dim=128, **kw).init, jax.random.PRNGKey(0),
        x)["params"]["block_0"]["attn"]
    assert jax.tree.map(lambda a: a.shape, shapes) == {
        "qkv_proj": {"kernel": (2048, (heads + 16) * 128)},
        "o_proj": {"kernel": (heads * 128, 2048)},
        "gate_proj": {"kernel": (2048, heads)}}
    if 2048 % heads:
        with pytest.raises(ValueError, match="divisible"):
            jax.eval_shape(WeatherTransformer(**kw).init,
                           jax.random.PRNGKey(0), x)


def test_the_heads_gate_shards_with_its_groups_on_the_model_axis(family):
    """``gate_proj`` is column-parallel like ``qkv_proj``: on a model axis
    of 2 each device holds the gate columns of the very q heads whose
    projection columns it holds (whole groups: 2 kv heads, one a device)."""
    _, params, _, _ = family
    attn = params["block_1"]["attn"]  # 8 q heads over 2 groups of 16 wide
    family_name = "weather_hybrid_moe_causal"
    path = ("block_1", "attn")
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    placed = {}
    for name in ("qkv_proj", "gate_proj"):
        spec = spec_for_path(path + (name, "kernel"), 2, family_name)
        assert tuple(spec) == (None, "model"), name
        placed[name] = jax.device_put(
            attn[name]["kernel"], NamedSharding(mesh, spec))
    hg, dh = 4, 16  # q heads a group, head width
    for qkv, gate in zip(placed["qkv_proj"].addressable_shards,
                         placed["gate_proj"].addressable_shards):
        assert qkv.device == gate.device
        cols = range(*qkv.index[1].indices(2 * (hg + 2) * dh))
        # A group's columns: its hg q heads, then k and v, each dh wide.
        q_heads = {
            (c // ((hg + 2) * dh)) * hg + (c % ((hg + 2) * dh)) // dh
            for c in cols if (c % ((hg + 2) * dh)) // dh < hg}
        assert set(range(*gate.index[1].indices(2 * hg))) == q_heads


def test_the_two_reference_copies_are_byte_identical():
    bench = os.path.join(
        os.path.dirname(HERE), "benchmark", "reference", "laguna_moe.py")
    with open(bench, "rb") as a, open(REF.__file__, "rb") as b:
        assert a.read() == b.read()


@pytest.mark.parametrize("preset", [
    "sc2_3b_block", "lfm2_24b_a2b_ep8", "moonlight_16b_a3b_ep8",
    "mellum2_12b_cut"])
def test_accepted_configurations_build_their_tree_and_no_gate(preset):
    """The parameter tree each accepted configuration built at the parent
    (the digest of its names and shapes that test_mellum2_moe.PRESETS
    records), with no heads' gate in it."""
    from test_mellum2_moe import PRESETS

    small, want, _ = PRESETS[preset]
    with open(os.path.join(os.path.dirname(HERE), "benchmark", "configs",
                           preset + ".json")) as f:
        env = {k: str(v) for k, v in json.load(f)["program"]["env"].items()}
    cfg = _from_env({**env, **small, "DCT_SEQ_LEN": "64"})
    model = get_model(cfg, input_dim=5, compute_dtype=jnp.float32)
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 64, 5)))["params"]
    tree = sorted(
        (jax.tree_util.keystr(path), leaf.shape)
        for path, leaf in jax.tree_util.tree_leaves_with_path(params))
    assert not [p for p, _ in tree if "gate_proj" in p]
    assert hashlib.sha256(repr(tree).encode()).hexdigest()[:16] == want

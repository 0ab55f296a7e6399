"""The hybrid family (``weather_hybrid_moe_causal``: per-layer operator,
gated short convolution, QK-norm GQA, sigmoid-routed SwiGLU experts held as
one share of an expert-parallel layer) against its plain float32 reference,
at small widths on the CPU with seeded weights."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_lfm2_moe as REF
from dct_tpu.config import ModelConfig
from dct_tpu.models.moe import MoEFFN, _chunked_moe, _grouped_moe
from dct_tpu.models.registry import (
    get_model,
    is_causal_model,
    is_sequence_model,
)
from dct_tpu.ops.shortconv import causal_depthwise_conv, gated_short_conv

TOL = 2e-5
HERE = os.path.dirname(os.path.abspath(__file__))

#: A small lfm2_moe: (conv, attention, conv, conv, conv), one dense layer,
#: 16 experts of which 4 are held, top-4.
REF_CONFIG = {
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
    "layers_run": [0, 2, 3, 4, 5],
    "num_hidden_layers": 5, "num_dense_layers": 1,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 1000000.0}, "norm_eps": 1e-5,
    "num_experts_per_tok": 4, "num_experts": 4, "first_expert": 4,
    "routed_scaling_factor": 1.0,
}
ENV = {
    "DCT_MODEL": "weather_hybrid_moe_causal", "DCT_D_MODEL": "32",
    "DCT_N_HEADS": "4", "DCT_N_KV_HEADS": "2", "DCT_N_LAYERS": "5",
    "DCT_D_FF": "96", "DCT_SEQ_LEN": "48", "DCT_POS_EMBED": "rope",
    "DCT_ROPE_THETA": "1000000", "DCT_DROPOUT": "0", "DCT_NORM": "rmsnorm",
    "DCT_NORM_EPS": "1e-5", "DCT_MLP": "swiglu", "DCT_USE_BIAS": "0",
    "DCT_QK_NORM": "1",
    "DCT_LAYER_TYPES": "conv,full_attention,conv,conv,conv",
    "DCT_NUM_DENSE_LAYERS": "1", "DCT_N_EXPERTS": "16",
    "DCT_ROUTER_TOP_K": "4", "DCT_MOE_D_FF": "24",
    "DCT_EXPERTS_HELD": "4",
    "DCT_FIRST_EXPERT": "4", "DCT_CAPACITY_FACTOR": "4",
}


@pytest.fixture(scope="module")
def family(tmp_path_factory):
    """(model, params with a seeded non-zero expert bias, x, y), the model
    built from the environment the way ``RunConfig.from_env`` builds it."""
    saved = {k: os.environ.get(k) for k in ENV}
    os.environ.update(ENV)
    try:
        cfg = ModelConfig.from_env()
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.update({k: v})
    model = get_model(cfg, input_dim=5, compute_dtype=jnp.float32)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 48, 5)).astype(np.float32)
    y = rng.integers(0, 2, (2, 48)).astype(np.int32)
    params = jax.device_get(
        jax.jit(model.init)(jax.random.PRNGKey(1), jnp.asarray(x[:1]))
    )["params"]
    for name, block in params.items():
        if "moe" in block:
            block["moe"]["expert_bias"] = (
                0.05 * rng.standard_normal(16)).astype(np.float32)
    return model, params, x, y


def _ce(logits, y):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], -1).mean()


def test_family_is_causal_per_position_and_reads_its_shape_from_env(family):
    model, params, x, _ = family
    assert is_sequence_model("weather_hybrid_moe_causal")
    assert is_causal_model("weather_hybrid_moe_causal")
    assert model.layer_types == (
        "conv", "full_attention", "conv", "conv", "conv")
    assert "conv" in params["block_0"] and "ffn_gate" in params["block_0"]
    assert "attn" in params["block_1"] and "moe" in params["block_1"]
    assert params["block_1"]["moe"]["router"]["kernel"].shape == (32, 16)
    assert params["block_1"]["moe"]["experts_in_kernel"].shape == (4, 32, 24)
    assert not any("bias" in k for b in params.values() for k in b
                   if isinstance(b, dict) and k != "moe")


def test_logits_and_loss_match_the_reference(family):
    model, params, x, y = family
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, x, train=False))
    want, want_loss = REF.forward_and_loss(params, x, y, REF_CONFIG)
    assert got.shape == want.shape == (2, 48, 2)
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    assert abs(REF.cross_entropy(got, y) - want_loss) < TOL


def test_gradients_match_the_reference(family):
    model, params, x, y = family
    kw = REF.settings(REF_CONFIG)

    def system(p):
        return _ce(model.apply({"params": p}, x, train=False), y)

    def reference(p):
        logits = jnp.stack(
            [REF.forward_one(p, xi, None, **kw)[0] for xi in x])
        return _ce(logits, y)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(system)(params)
        want = jax.grad(reference)(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert len(flat_got) == len(flat_want)
    for path, g in flat_got:
        np.testing.assert_allclose(
            g, flat_want[path], rtol=0, atol=TOL, err_msg=str(path))
    # The selection bias is under stop_gradient: Adam sees zero.
    assert not np.asarray(got["block_2"]["moe"]["expert_bias"]).any()


def test_short_convolution_is_causal():
    rng = np.random.default_rng(2)
    z = rng.standard_normal((2, 16, 8)).astype(np.float32)
    taps = rng.standard_normal((8, 3)).astype(np.float32)
    base = np.asarray(causal_depthwise_conv(z, taps))
    later = z.copy()
    later[:, 9:] += 1.0
    moved = np.asarray(causal_depthwise_conv(later, taps))
    np.testing.assert_array_equal(base[:, :9], moved[:, :9])
    assert np.abs(base[:, 9:] - moved[:, 9:]).max() > 0.1


def test_short_convolution_equals_the_depthwise_lax_form():
    rng = np.random.default_rng(3)
    bcx = rng.standard_normal((2, 16, 24)).astype(np.float32)
    taps = rng.standard_normal((8, 3)).astype(np.float32)
    b, c, x = np.split(bcx, 3, axis=-1)
    conv = jax.lax.conv_general_dilated(
        jnp.asarray(b * x), jnp.asarray(taps.T[:, None, :]),
        window_strides=(1,), padding=[(2, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=8,
        precision="highest")
    np.testing.assert_allclose(
        gated_short_conv(bcx, taps), c * np.asarray(conv), rtol=0, atol=1e-5)


def _layer(held, first, e=16):
    return MoEFFN(
        d_model=32, d_ff=24, n_experts=e,
        aux_weight=0.0, dispatch="grouped", top_k=4,
        experts_held=held, first_expert=first)


@pytest.fixture(scope="module")
def whole_layer():
    """An uncut 16-expert layer's parameters and 96 tokens."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal((2, 48, 32)).astype(np.float32)
    layer = _layer(0, 0)
    p = jax.device_get(layer.init(jax.random.PRNGKey(5), x))["params"]
    return p, x


def _apply(layer, p, x):
    with jax.default_matmul_precision("highest"):
        out, sown = layer.apply(
            {"params": p}, x, mutable=["counters", "intermediates"])
    return np.asarray(out), jax.device_get(sown)


def test_expert_bias_changes_which_experts_not_their_weights(whole_layer):
    p, x = whole_layer
    rng = np.random.default_rng(6)
    biased = {**p, "expert_bias": (
        0.2 * rng.standard_normal(16)).astype(np.float32)}
    _, plain = _apply(_layer(0, 0), p, x)
    out, sown = _apply(_layer(0, 0), biased, x)
    top_plain = np.sort(plain["intermediates"]["topk"][0], -1)
    top_biased = np.sort(sown["intermediates"]["topk"][0], -1)
    moved = (top_plain != top_biased).any(-1)
    assert 0.2 < moved.mean() < 1.0
    # The output is the reference's for the biased choice with weights
    # from the UNBIASED scores: s at the chosen experts over their sum.
    want, chosen, _ = REF._moe(
        jnp.asarray(x.reshape(-1, 32)), biased, top_k=4, first=0,
        scaling=1.0, routing=None)
    np.testing.assert_array_equal(np.sort(chosen, -1), top_biased)
    np.testing.assert_allclose(
        out.reshape(-1, 32), want, rtol=0, atol=TOL)
    # A bias added to the weights as well would fail this by far.
    s = jax.nn.sigmoid(x.reshape(-1, 32) @ p["router"]["kernel"])
    w = np.take_along_axis(np.asarray(s), np.asarray(chosen), -1)
    wb = w + biased["expert_bias"][np.asarray(chosen)]
    assert np.abs(w / w.sum(-1, keepdims=True)
                  - wb / wb.sum(-1, keepdims=True)).max() > 0.02


def test_the_eight_shares_add_up_to_the_uncut_layer(whole_layer):
    p, x = whole_layer
    rng = np.random.default_rng(7)
    p = {**p, "expert_bias": (
        0.1 * rng.standard_normal(16)).astype(np.float32)}
    want, _, _ = REF._moe(
        jnp.asarray(x.reshape(-1, 32)), p, top_k=4, first=0, scaling=1.0,
        routing=None)
    total = np.zeros_like(np.asarray(want))
    rows = 0
    for share in range(8):
        first = 2 * share
        held = {
            k: (v[first:first + 2] if k.startswith("experts_") else v)
            for k, v in p.items()}
        out, sown = _apply(_layer(2, first), held, x)
        total += out.reshape(-1, 32)
        rows += int(sown["counters"]["moe_rows"][0].sum())
        assert int(sown["counters"]["moe_rows_overflowed"][0]) == 0
    np.testing.assert_allclose(total, want, rtol=0, atol=TOL)
    assert rows == 96 * 4  # every routed row is some share's


def test_no_row_is_dropped_when_one_held_expert_takes_half_the_rows(
        whole_layer):
    p, x = whole_layer
    tokens = x.reshape(-1, 32)
    # Expert 5's score is ~1 for the tokens on one side of a hyperplane
    # and ~0 for the others: it is chosen by half of them.
    u = np.random.default_rng(8).standard_normal(32).astype(np.float32)
    kernel = np.array(p["router"]["kernel"])
    kernel[:, 5] = 4.0 * u
    held = {
        k: (v[4:8] if k.startswith("experts_") else v) for k, v in p.items()}
    held["router"] = {"kernel": kernel}
    out, sown = _apply(_layer(4, 4), held, x)
    rows = sown["counters"]["moe_rows"][0]
    half = int((tokens @ u > 0).sum())
    assert abs(int(rows[1]) - half) <= 4 and 40 <= half <= 56
    # Twice the uniform mean at 16 experts (eight times at 64).
    assert rows[1] > 1.8 * float(sown["counters"]["moe_rows_uniform"][0])
    assert int(sown["counters"]["moe_rows_overflowed"][0]) == 0
    want, _, _ = REF._moe(
        jnp.asarray(tokens), held, top_k=4, first=4, scaling=1.0,
        routing=None)
    np.testing.assert_allclose(out.reshape(-1, 32), want, rtol=0, atol=TOL)


def test_rows_past_the_bound_are_counted_not_hidden(whole_layer):
    p, x = whole_layer
    held = {
        k: (v[:4] if k.startswith("experts_") else v) for k, v in p.items()}
    _, full = _apply(_layer(4, 0), held, x)
    routed = int(full["counters"]["moe_rows"][0].sum())
    assert int(full["counters"]["moe_rows_overflowed"][0]) == 0
    # The layer's bound is every row that can come (96 x 4); the grouped
    # engine under a tighter one counts what it leaves out.
    topi = full["intermediates"]["topk"][0]
    _, rows, overflow = _grouped_moe(
        jnp.asarray(x.reshape(-1, 32)), topi,
        jnp.full(topi.shape, 0.25, jnp.float32),
        held["experts_gate_kernel"], held["experts_in_kernel"],
        held["experts_out_kernel"], first_expert=0, row_bound=48)
    assert int(rows.sum()) == routed
    assert int(overflow) == routed - 48


def _held_share(p, first, held):
    return {
        k: (v[first:first + held] if k.startswith("experts_") else v)
        for k, v in p.items()}


def _pulled(p, first, favoured, held=4):
    """``p`` with a selection bias that sends EVERY token to the first
    ``favoured`` of the held experts and leaves the other held ones to the
    router; ``favoured`` == ``held`` is every row that can come."""
    bias = np.zeros(16, np.float32)
    bias[first:first + favoured] = 10.0
    return {**_held_share(p, first, held), "expert_bias": bias}


def _layer_loss(layer, x, cot):
    def loss(p, x):
        out = layer.apply({"params": p}, x)
        return (out * cot).sum()
    return loss


def _reference_loss(first, cot):
    def loss(p, x):
        out, _, _ = REF._moe(
            x.reshape(-1, 32), p, top_k=4, first=first, scaling=1.0,
            routing=None)
        return (out.reshape(cot.shape) * cot).sum()
    return loss


def _equations(jaxpr, in_loop=False):
    """Every equation of ``jaxpr`` and of what it calls, with whether a
    ``while`` holds it."""
    for eqn in jaxpr.eqns:
        yield in_loop, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(
                sub, in_loop or eqn.primitive.name == "while")


def _gradients_against_the_reference(layer, share, x, first, seed):
    """The layer's gradients for parameters and tokens under a seeded
    cotangent, held to the reference's; returns the tokens' [N, 32]."""
    cot = np.random.default_rng(seed).standard_normal(x.shape).astype(
        np.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(_layer_loss(layer, x, cot), (0, 1))(share, x)
        ref = jax.grad(_reference_loss(first, cot), (0, 1))(
            share, jnp.asarray(x))
    for name in ("experts_gate_kernel", "experts_in_kernel",
                 "experts_out_kernel"):
        np.testing.assert_allclose(
            got[0][name], ref[0][name], rtol=0, atol=TOL, err_msg=name)
    # The weights' gradient reaches the router through the scores.
    assert np.abs(ref[0]["router"]["kernel"]).max() > 10 * TOL
    np.testing.assert_allclose(
        got[0]["router"]["kernel"], ref[0]["router"]["kernel"], rtol=0,
        atol=TOL)
    np.testing.assert_allclose(got[1], ref[1], rtol=0, atol=TOL)
    if "expert_bias" in share:
        assert not np.asarray(got[0]["expert_bias"]).any()
    return np.asarray(got[1]).reshape(-1, 32)


@pytest.mark.parametrize("held, favoured, low, high, bound", [
    (4, 0, 40, 96, 120),     # the router's own load: under the even share
    (4, 1, 121, 192, 240),   # one held expert takes every token: two chunks
    (4, 2, 193, 240, 240),   # two, full but for a few rows
    (4, 3, 241, 360, 360),   # three
    (4, 4, 384, 384, 480),   # every row that can come: the last passes them
    (5, 4, 384, 384, 450),   # chunks of 150: the last passes the 384 rows
    (2, 0, 10, 60, 60),      # chunks of 60, 192 rows can come
    (2, 2, 192, 192, 240),
    (3, 1, 97, 180, 180),    # chunks of 90
    (3, 3, 288, 288, 360),
])
def test_each_load_gives_the_reference_and_drops_no_row(
        whole_layer, held, favoured, low, high, bound):
    p, x = whole_layer
    share = _pulled(p, 4, favoured, held)
    layer = _layer(held, 4)
    out, sown = _apply(layer, share, x)
    counters = sown["counters"]
    routed = int(counters["moe_rows"][0].sum())
    chunk = -(-5 * 96 * 4 * held // (4 * 16))
    assert low <= routed <= high
    assert int(counters["moe_rows_bound"][0]) == bound == (
        -(-routed // chunk) * chunk)
    assert int(counters["moe_rows_overflowed"][0]) == 0
    want, _, _ = REF._moe(
        jnp.asarray(x.reshape(-1, 32)), share, top_k=4, first=4, scaling=1.0,
        routing=None)
    np.testing.assert_allclose(out.reshape(-1, 32), want, rtol=0, atol=TOL)
    _gradients_against_the_reference(layer, share, x, 4, 9)


@pytest.mark.parametrize("chunk, favoured, bound", [
    (384, 1, 384),   # one chunk of every row that can come
    (100, 0, 100),   # the router's own load in one chunk of four
    (100, 3, 300),   # three of four
    (100, 4, 400),   # four chunks, the last past the 384 rows
    (40, 2, 240),    # six of ten
    (32, 4, 384),    # every row that can come, twelve chunks
])
def test_the_chunks_equal_the_engine_under_one_bound(
        whole_layer, chunk, favoured, bound):
    """The loop over chunks against the engine under the one bound the
    layer ran under before, differentiated as it stands: the same routing,
    gates and weights, output and every gradient."""
    p, x = whole_layer
    held = _pulled(p, 4, favoured)
    _, sown = _apply(_layer(4, 4), held, x)
    topi = sown["intermediates"]["topk"][0]
    routed = int(sown["counters"]["moe_rows"][0].sum())
    rng = np.random.default_rng(10)
    cot = rng.standard_normal((96, 32)).astype(np.float32)
    operands = (
        jnp.asarray(x.reshape(-1, 32)),
        jnp.asarray(rng.random((96, 4)), jnp.float32),
        *(held[f"experts_{name}_kernel"] for name in ("gate", "in", "out")))

    def chunked(*operands):
        out, rows, ran, overflow = _chunked_moe(
            operands[0], topi, *operands[1:], first_expert=4, chunk=chunk)
        return (out * cot).sum(), (rows.sum(), ran, overflow)

    def one_bound(*operands):
        out, rows, overflow = _grouped_moe(
            operands[0], topi, *operands[1:], first_expert=4, row_bound=384)
        return (out * cot).sum(), (rows.sum(), overflow)

    with jax.default_matmul_precision("highest"):
        (got, counted), g_got = jax.value_and_grad(
            chunked, range(5), has_aux=True)(*operands)
        (want, _), g_want = jax.value_and_grad(
            one_bound, range(5), has_aux=True)(*operands)
    assert [int(c) for c in counted] == [routed, bound, 0]
    assert bound == -(-routed // chunk) * chunk
    assert abs(float(got) - float(want)) < TOL * max(1.0, abs(float(want)))
    for name, a, b in zip(
            ("tokens", "gates", "gate", "in", "out"), g_got, g_want):
        assert np.abs(np.asarray(b)).max() > 10 * TOL, name
        np.testing.assert_allclose(a, b, rtol=0, atol=TOL, err_msg=name)


def test_a_token_with_every_choice_held_beside_one_with_none(whole_layer):
    """Held experts 4-7 score ~1 on one side of a hyperplane and ~0 on the
    other: a token far from it sends all its four choices here or none of
    them, so the combine adds four rows to the one and no row to the
    other, and the routed rows take a second chunk."""
    p, x = whole_layer
    tokens = x.reshape(-1, 32)
    u = np.random.default_rng(12).standard_normal(32).astype(np.float32)
    kernel = np.array(p["router"]["kernel"])
    kernel[:, 4:8] = 4.0 * u[:, None]
    share = {**_held_share(p, 4, 4), "router": {"kernel": kernel}}
    layer = _layer(4, 4)
    out, sown = _apply(layer, share, x)
    here = ((sown["intermediates"]["topk"][0] >= 4)
            & (sown["intermediates"]["topk"][0] < 8)).sum(-1)
    assert int((here == 4).sum()) >= 24 and int((here == 0).sum()) >= 24
    counters = sown["counters"]
    assert int(counters["moe_rows"][0].sum()) == int(here.sum()) > 120
    assert int(counters["moe_rows_bound"][0]) == 240
    assert int(counters["moe_rows_overflowed"][0]) == 0
    assert not out.reshape(-1, 32)[here == 0].any()
    want, _, _ = REF._moe(
        jnp.asarray(tokens), share, top_k=4, first=4, scaling=1.0,
        routing=None)
    np.testing.assert_allclose(out.reshape(-1, 32), want, rtol=0, atol=TOL)
    # Nothing this share computes depends on a token with no choice here.
    d_tokens = _gradients_against_the_reference(layer, share, x, 4, 13)
    assert not d_tokens[here == 0].any()
    assert (np.abs(d_tokens[here == 4]).max(-1) > 10 * TOL).all()


@pytest.mark.parametrize("d, f, k, held, chunk, top", [
    (2048, 1536, 4, 8, 5120, 35840),     # lfm2_24b_a2b_ep8
    (2048, 1408, 6, 8, 7680, 53760),     # moonlight_16b_a3b_ep8
    (2304, 896, 8, 16, 20480, 81920),    # mellum2_12b_cut
])
def test_the_cells_chunk_is_a_quarter_over_the_even_share(
        d, f, k, held, chunk, top):
    """The three routed cells' layers at their published shapes, traced on
    shapes alone: a chunk of the loop is 1.25 x ``8192 k held / 64`` rows,
    and the stacks' gradients run over the whole chunks that hold every
    row that can come."""
    layer = MoEFFN(
        d_model=d, d_ff=f, n_experts=64, aux_weight=0.0,
        dtype=jnp.bfloat16, dispatch="grouped", top_k=k, experts_held=held)
    x = jax.ShapeDtypeStruct((1, 8192, d), jnp.bfloat16)
    params = jax.eval_shape(
        layer.init, jax.random.PRNGKey(0), jnp.zeros((1, 8, d)))["params"]
    assert chunk == 5 * 8192 * k * held // (4 * 64)

    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, x: layer.apply({"params": p}, x).astype(
            jnp.float32).sum()))(params, x).jaxpr
    assert {(in_loop, eqn.invars[0].aval.shape[0])
            for in_loop, eqn in _equations(jaxpr)
            if eqn.primitive.name == "ragged_dot_general"} == {
                (True, chunk), (False, top)}


def test_the_backward_keeps_no_chunk_of_rows(whole_layer):
    """What the layer saves for its backward: its inputs, and nothing
    with a chunk's rows (it computes each chunk again)."""
    p, x = whole_layer
    held = _pulled(p, 4, 1, held=3)
    chunks = {90, 180, 270, 360}  # none is a token or a weight extent

    def saved_rows(fn, *args):
        _, vjp = jax.vjp(fn, *args)
        return {a.shape[0] for a in jax.tree.leaves(vjp)
                if hasattr(a, "shape") and a.ndim == 2} & chunks

    layer = _layer(3, 4)
    assert saved_rows(
        lambda p, x: layer.apply({"params": p}, x), held, x) == set()
    # The scan sees row-bounded residuals where there are some: the
    # engine under one bound, differentiated as it stands.
    topi = jnp.asarray(np.random.default_rng(11).integers(0, 16, (96, 4)))
    assert saved_rows(
        lambda t, *w: _grouped_moe(
            t, topi, jnp.full((96, 4), 0.25), *w, first_expert=4,
            row_bound=270)[0],
        jnp.asarray(x.reshape(-1, 32)), held["experts_gate_kernel"],
        held["experts_in_kernel"], held["experts_out_kernel"]) == {270}


def test_each_stack_has_one_gradient_product_after_the_chunks(whole_layer):
    """Where the backward forms the stacks' gradients: no chunk's loop
    holds a value of a stack's shape, and the three grouped products that
    do come after it, over the rows of every chunk that can run."""
    p, x = whole_layer
    held = _pulled(p, 4, 1, held=3)
    stacks = {(3, 32, 24), (3, 24, 32)}

    layer = _layer(3, 4)
    found = [
        (in_loop, eqn.primitive.name, tuple(
            v.aval.shape for v in eqn.invars))
        for in_loop, eqn in _equations(jax.make_jaxpr(jax.grad(
            lambda p, x: layer.apply({"params": p}, x).sum()))(held, x).jaxpr)
        if {getattr(v.aval, "shape", None) for v in eqn.outvars} & stacks]
    assert not [f for f in found if f[0]]
    products = [f for f in found if f[1] == "ragged_dot_general"]
    assert sorted(shapes[:2] for _, _, shapes in products) == [
        ((360, 24), (360, 32)), ((360, 32), (360, 24)),
        ((360, 32), (360, 24))]


def test_the_bound_is_counted_with_the_rows(whole_layer):
    """``moe_rows_bound`` through the trainer's flattening, two layers
    under different loads."""
    from dct_tpu.train.steps import counter_metrics

    p, x = whole_layer
    sown = {
        f"block_{i}": {"moe": jax.tree.map(
            lambda leaf: leaf[0],
            _apply(_layer(4, 4), _pulled(p, 4, favoured), x)[1]["counters"],
            is_leaf=lambda leaf: isinstance(leaf, tuple))}
        for i, favoured in enumerate((1, 4))}
    flat = counter_metrics(sown)
    assert flat["moe_rows_bound"] == 240 + 480
    assert flat["moe_rows_overflowed"] == 0
    assert 97 + 384 <= flat["moe_rows"] <= 192 + 384


def test_the_two_reference_copies_are_byte_identical():
    bench = os.path.join(
        os.path.dirname(HERE), "benchmark", "reference", "lfm2_moe.py")
    with open(bench, "rb") as a, open(REF.__file__, "rb") as b:
        assert a.read() == b.read()


def test_sc2_block_environment_builds_the_same_tree_and_logits_as_before():
    """``weather_transformer_causal`` at sc2_3b_block's environment (small
    widths): the parameters tree and the logits the parent commit gave,
    recorded there. The block's new fields default to that block."""
    cfg = ModelConfig(
        name="weather_transformer_causal", d_model=48, n_heads=6,
        n_kv_heads=2, d_ff=96, n_layers=2, attn_window=16, pos_embed="rope",
        dropout=0.1, num_classes=2, seq_len=32)
    model = get_model(cfg, input_dim=5, compute_dtype=jnp.float32)
    x = np.random.default_rng(7).standard_normal((2, 32, 5)).astype(
        np.float32)
    v = jax.jit(model.init)(jax.random.PRNGKey(3), jnp.asarray(x[:1]))
    block = {
        "attn": {"o_proj": {"bias": (48,), "kernel": (48, 48)},
                 "qkv_proj": {"bias": (80,), "kernel": (48, 80)}},
        "ffn_in": {"bias": (96,), "kernel": (48, 96)},
        "ffn_out": {"bias": (48,), "kernel": (96, 48)},
        "ln_attn": {"bias": (48,), "scale": (48,)},
        "ln_ffn": {"bias": (48,), "scale": (48,)},
    }
    assert jax.tree.map(lambda a: a.shape, jax.device_get(v["params"])) == {
        "block_0": block, "block_1": block,
        "head": {"bias": (2,), "kernel": (48, 2)},
        "in_proj": {"bias": (48,), "kernel": (5, 48)},
        "ln_out": {"bias": (48,), "scale": (48,)},
    }
    with jax.default_matmul_precision("highest"):
        out = np.asarray(model.apply(v, x, train=False))
    np.testing.assert_allclose(out[0, :3], [
        [-0.95113057, -0.31314194], [-0.2711224, -0.593053],
        [-0.6395803, -0.34496617]], rtol=0, atol=2e-6)
    np.testing.assert_allclose(out[1, -2:], [
        [-0.7167114, -0.68504643], [-0.42777747, -0.17218004]],
        rtol=0, atol=2e-6)
    assert abs(float(np.abs(out).sum()) - 56.353355407714844) < 1e-3

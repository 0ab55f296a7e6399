"""The hybrid family as the mellum layer (three ``sliding_attention`` layers
to one ``full_attention`` layer with the YaRN rotation, a head width that is
not ``d_model / heads``, softmax-routed SwiGLU experts with the balance loss
and no selection bias) against its plain float32 reference, at small widths
on the CPU with seeded weights; the YaRN tables against a hand-worked case;
the four shares of the experts against the uncut layer; and the accepted
configurations' parameter trees and program texts, unchanged."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_mellum2_moe as REF
from dct_tpu.config import ModelConfig
from dct_tpu.models.moe import MoEFFN
from dct_tpu.models.registry import get_model
from dct_tpu.models.transformer import rope_tables, yarn_tables
from dct_tpu.ops.pallas_attention import flash_tiles
from dct_tpu.train.state import create_train_state
from dct_tpu.train.steps import counter_metrics, make_train_step

TOL = 2e-5
COEF = 0.1
HERE = os.path.dirname(os.path.abspath(__file__))
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
YARN = {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
        "original_max_position_embeddings": 32, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.2772588722239782}

#: A small mellum: one whole period (three layers under a window of 16 of
#: 64 positions, one full layer), 8 query / 2 key-value heads of 12 on a
#: hidden size of 64 (so heads x head is 96, not 64), 16 experts of which 4
#: are held, top-8, no dense layer.
REF_CONFIG = {
    "num_hidden_layers": 4, "layer_types": PERIOD * 2,
    "mlp_layer_types": ["sparse"] * 8, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 12,
    "sliding_window": 16, "rms_norm_eps": 1e-6, "num_experts_per_tok": 8,
    "num_experts": 4, "first_expert": 4, "router_aux_loss_coef": COEF,
    "rope_parameters": {
        "full_attention": YARN,
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000.0}},
}
ENV = {
    "DCT_MODEL": "weather_hybrid_moe_causal", "DCT_D_MODEL": "64",
    "DCT_N_HEADS": "8", "DCT_N_KV_HEADS": "2", "DCT_HEAD_DIM": "12",
    "DCT_N_LAYERS": "4", "DCT_D_FF": "96", "DCT_SEQ_LEN": "64",
    "DCT_POS_EMBED": "rope", "DCT_ROPE_THETA": "500000",
    "DCT_ROPE_SCALING": "yarn", "DCT_ROPE_FACTOR": "16",
    "DCT_ROPE_ORIGINAL_LEN": "32", "DCT_ROPE_BETA_FAST": "32",
    "DCT_ROPE_BETA_SLOW": "1",
    "DCT_ROPE_ATTENTION_FACTOR": "1.2772588722239782",
    "DCT_DROPOUT": "0", "DCT_NORM": "rmsnorm", "DCT_NORM_EPS": "1e-6",
    "DCT_MLP": "swiglu", "DCT_USE_BIAS": "0", "DCT_ATTN_WINDOW": "16",
    "DCT_LAYER_TYPES": ",".join(PERIOD), "DCT_NUM_DENSE_LAYERS": "0",
    "DCT_N_EXPERTS": "16", "DCT_ROUTER_TOP_K": "8", "DCT_MOE_D_FF": "24",
    "DCT_EXPERTS_HELD": "4", "DCT_FIRST_EXPERT": "4",
    "DCT_ROUTER_SCORING": "softmax", "DCT_ROUTER_AUX_WEIGHT": str(COEF),
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
    """(model, seeded params, x, y), the model built from the environment
    the way ``RunConfig.from_env`` builds it."""
    model = _model()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 64, 5)).astype(np.float32)
    y = rng.integers(0, 2, (2, 64)).astype(np.int32)
    params = jax.device_get(
        jax.jit(model.init)(jax.random.PRNGKey(1), jnp.asarray(x[:1]))
    )["params"]
    return model, params, x, y


def _ce(logits, y):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], -1).mean()


def _objective(model, params, x, y):
    """What the train step differentiates: the cross-entropy plus every
    sown ``aux_loss`` leaf."""
    logits, sown = model.apply(
        {"params": params}, x, train=True, mutable=["aux_loss", "counters"])
    return _ce(logits, y) + sum(jax.tree.leaves(sown["aux_loss"]))


def test_family_reads_the_pattern_the_head_and_the_router_from_env(family):
    model, params, _, _ = family
    assert model.layer_types == tuple(PERIOD)
    assert model.head_dim == 12 and model.num_dense_layers == 0
    assert dict(model.rope_scaling) == {
        "factor": 16.0, "original_len": 32, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.2772588722239782}
    # The window binds the sliding layers; the full ones get none.
    assert model.sliding_attn_fn is not None
    assert model.sliding_attn_fn is not model.attn_fn
    for i in range(4):
        block = params[f"block_{i}"]
        assert {k: v["kernel"].shape for k, v in block["attn"].items()} == {
            "qkv_proj": (64, (8 + 2 * 2) * 12), "o_proj": (8 * 12, 64)}
        # A softmax router: no selection bias in the tree, no dense MLP.
        assert sorted(block["moe"]) == [
            "experts_gate_kernel", "experts_in_kernel", "experts_out_kernel",
            "router"]
        assert block["moe"]["experts_in_kernel"].shape == (4, 64, 24)
        assert block["moe"]["router"]["kernel"].shape == (64, 16)
        assert "ffn_in" not in block
    for changed, match in [
        ({"DCT_ATTN_WINDOW": "0"}, "attn_window"),
        ({"DCT_ROPE_SCALING": "ntk"}, "rope_scaling"),
        ({"DCT_ROUTER_SCORING": "tanh"}, "scoring"),
        ({"DCT_BIAS_UPDATE_SPEED": "0.01"}, "selection bias"),
        ({"DCT_LAYER_TYPES": "a,b,c,d"}, "sliding_attention"),
    ]:
        with pytest.raises(ValueError, match=match):
            _model(**changed).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 64, 5)))


@pytest.mark.parametrize("what", ["logits", "loss", "gradients", "step"])
def test_program_matches_the_reference_in_float32(family, what):
    model, params, x, y = family
    kw = REF.settings(REF_CONFIG)
    with jax.default_matmul_precision("highest"):
        if what == "logits":
            got = np.asarray(model.apply({"params": params}, x, train=False))
            want = REF.forward(params, x, REF_CONFIG)["logits"]
            assert got.shape == want.shape == (2, 64, 2)
            np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
        elif what == "loss":
            # The balance term included: four layers at about COEF each.
            got = float(_objective(model, params, x, y))
            _, want = REF.forward_and_loss(params, x, y, REF_CONFIG)
            plain = REF.cross_entropy(
                REF.forward(params, x, REF_CONFIG)["logits"], y)
            assert abs(got - want) < TOL
            assert 3.5 * COEF < want - plain < 6 * COEF
            assert abs(want - float(REF.objective(params, x, y, **kw))) < TOL
        elif what == "gradients":
            got = jax.grad(lambda p: _objective(model, p, x, y))(params)
            want = jax.grad(lambda p: REF.objective(p, x, y, **kw))(params)
            flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
            flat_got = jax.tree_util.tree_leaves_with_path(got)
            assert len(flat_got) == len(flat_want) == 4 * 8 + 3
            for path, g in flat_got:
                np.testing.assert_allclose(
                    g, flat_want[path], rtol=0, atol=TOL, err_msg=str(path))
            # The balance term reaches the router: without it the router's
            # gradient is another one.
            bare = jax.grad(lambda p: _ce(
                model.apply({"params": p}, x, train=False), y))(params)
            assert np.abs(
                got["block_0"]["moe"]["router"]["kernel"]
                - bare["block_0"]["moe"]["router"]["kernel"]).max() > 10 * TOL
        else:
            # One optimizer step of the program reports the reference's
            # objective as its loss and moves no parameter but by Adam.
            state = create_train_state(
                model, input_dim=5, lr=1e-3, seed=1,
                example_shape=(1, 64, 5), grad_clip_norm=1.0)
            state = state.replace(params={"params": params})
            new, metrics = make_train_step(donate=False)(
                state, jnp.asarray(x), jnp.asarray(y), jnp.ones(2))
            _, want = REF.forward_and_loss(params, x, y, REF_CONFIG)
            assert abs(float(metrics["train_loss"]) - want) < TOL
            moved = jax.tree.map(
                lambda a, b: float(np.abs(a - b).max()),
                new.params["params"], params)
            assert 0 < max(jax.tree.leaves(moved)) <= 1e-3 * 1.01


def test_a_window_of_the_sequences_length_is_full_causal(family):
    """The band ``0 <= q - k < window`` cuts nothing once the window holds
    the sequence; a shorter one does. The same parameters serve all three
    models: the kinds differ in mask and rotation, not in their weights."""
    _, params, x, _ = family
    sliding = ",".join(["sliding_attention"] * 4)
    plain = {"DCT_ROPE_SCALING": ""}
    with jax.default_matmul_precision("highest"):
        whole = _model(DCT_LAYER_TYPES=sliding, DCT_ATTN_WINDOW="64", **plain)
        full = _model(
            DCT_LAYER_TYPES=",".join(["full_attention"] * 4),
            DCT_ATTN_WINDOW="0", **plain)
        short = _model(DCT_LAYER_TYPES=sliding, DCT_ATTN_WINDOW="16", **plain)
        a, b, c = (
            np.asarray(m.apply({"params": params}, x, train=False))
            for m in (whole, full, short))
    np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    assert np.abs(c - b)[:, 16:].max() > 100 * TOL
    np.testing.assert_allclose(c[:, :16], b[:, :16], rtol=0, atol=TOL)


def test_yarn_tables_against_a_hand_worked_case():
    """The published full layers: heads of 128, base 500,000, factor 16,
    trained length 8,192, beta_fast 32, beta_slow 1. The pair that turns
    32 times in 8,192 positions is 128 ln(8192 / 64 pi) / (2 ln 500000) =
    18.08, floored 18; the one that turns once 128 ln(8192 / 2 pi) / (2 ln
    500000) = 34.98, ceiled 35."""
    kw = dict(factor=16.0, original_len=8192, beta_fast=32.0, beta_slow=1.0)
    cos, sin = yarn_tables(8192, 128, 500000.0, **kw)
    assert cos.shape == sin.shape == (8192, 64) and cos.dtype == np.float32
    # The attention factor, 0.1 ln 16 + 1 where none is stated, on both.
    factor = 0.1 * np.log(16.0) + 1.0
    assert factor == pytest.approx(1.2772588722239782, abs=1e-15)
    np.testing.assert_allclose(cos[0], factor, rtol=1e-7)
    np.testing.assert_array_equal(sin[0], 0.0)
    np.testing.assert_allclose(cos ** 2 + sin ** 2, factor ** 2, rtol=1e-5)
    stated, _ = yarn_tables(
        8192, 128, 500000.0, attention_factor=1.2772588722239782, **kw)
    np.testing.assert_array_equal(stated, cos)
    # Position 1 gives back the inverse frequencies.
    inv = np.arctan2(sin[1].astype(np.float64), cos[1].astype(np.float64))
    plain = 500000.0 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-5)     # kept
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-3)
    # Half way up the ramp, pair 26: (26 - 18) / (35 - 18) interpolated.
    ramp = 8.0 / 17.0
    np.testing.assert_allclose(
        inv[26], plain[26] * (ramp / 16 + 1 - ramp), rtol=1e-5)
    assert np.all(np.diff(inv) < 0)
    # The two ends of the ramp, and the plain tables it leaves behind:
    # the first pairs are the plain rotation times the factor, at every
    # length (8,192 too: the blend is no function of the sequence).
    plain_cos, plain_sin = rope_tables(64, 128, 500000.0)
    short_cos, short_sin = yarn_tables(64, 128, 500000.0, **kw)
    np.testing.assert_allclose(
        short_cos[:, :19], factor * plain_cos[:, :19], rtol=0, atol=2e-5)
    np.testing.assert_allclose(
        short_sin[:, :19], factor * plain_sin[:, :19], rtol=0, atol=2e-5)
    assert np.abs(short_sin[:, 35:] - factor * plain_sin[:, 35:]).max() > 1e-3
    # The reference's own tables are the same numbers.
    ref_cos, ref_sin = REF.rotation(64, 128, {
        **YARN, "original_max_position_embeddings": 8192})
    np.testing.assert_allclose(ref_cos, short_cos, rtol=0, atol=1e-6)
    np.testing.assert_allclose(ref_sin, short_sin, rtol=0, atol=1e-6)


def _layer(held, first, coef=COEF, e=16):
    return MoEFFN(
        d_model=32, d_ff=24, n_experts=e, aux_weight=coef,
        dispatch="grouped", top_k=8, experts_held=held, first_expert=first,
        scoring="softmax")


def _apply(layer, p, x):
    with jax.default_matmul_precision("highest"):
        out, sown = layer.apply(
            {"params": p}, x,
            mutable=["aux_loss", "counters", "intermediates", "param_steps"])
    return np.asarray(out), jax.device_get(sown)


def test_the_four_shares_add_up_to_the_uncut_layer():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((2, 48, 32)).astype(np.float32)
    p = jax.device_get(_layer(0, 0).init(jax.random.PRNGKey(5), x))["params"]
    assert "expert_bias" not in p
    want, *_ = REF._moe(
        jnp.asarray(x.reshape(-1, 32)), p, top_k=8, first=0, routing=None)
    uncut, _ = _apply(_layer(0, 0), p, x)
    np.testing.assert_allclose(uncut.reshape(-1, 32), want, rtol=0, atol=TOL)
    total, rows = np.zeros_like(np.asarray(want)), 0
    for share in range(4):
        first = 4 * share
        held = {
            k: (v[first:first + 4] if k.startswith("experts_") else v)
            for k, v in p.items()}
        out, sown = _apply(_layer(4, first), held, x)
        assert np.abs(out).max() > 100 * TOL
        total += out.reshape(-1, 32)
        rows += int(sown["counters"]["moe_rows"][0].sum())
        assert int(sown["counters"]["moe_rows_overflowed"][0]) == 0
        # Every share routes over all 16 and counts all 16 alike.
        assert int(sown["counters"]["moe_rows_all"][0].sum()) == 96 * 8
        assert "param_steps" not in sown
    assert rows == 96 * 8  # every routed row is some share's
    np.testing.assert_allclose(total, want, rtol=0, atol=TOL)


def test_the_balance_term_and_its_counters_by_hand():
    rng = np.random.default_rng(6)
    x = rng.standard_normal((2, 48, 32)).astype(np.float32)
    layer = _layer(4, 4)
    p = jax.device_get(layer.init(jax.random.PRNGKey(7), x))["params"]
    # A router pulled towards three experts: an uneven load.
    p["router"]["kernel"] = p["router"]["kernel"] + np.where(
        np.arange(16) < 3, 0.5, 0.0).astype(np.float32)
    _, sown = _apply(layer, p, x)
    logits = x.reshape(-1, 32).astype(np.float64) @ p["router"]["kernel"]
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    topk = np.argsort(-probs, -1)[:, :8]
    np.testing.assert_array_equal(
        np.sort(sown["intermediates"]["topk"][0], -1), np.sort(topk, -1))
    load = np.bincount(topk.reshape(-1), minlength=16)
    want = COEF * 16 * float(((load / (96 * 8)) * probs.mean(0)).sum())
    assert want > 1.02 * COEF  # 1 x COEF is the even load
    assert float(sown["aux_loss"]["load_balance"][0]) == pytest.approx(
        want, rel=1e-5)
    counters = sown["counters"]
    assert float(counters["moe_aux_loss"][0]) == pytest.approx(want, rel=1e-5)
    np.testing.assert_array_equal(counters["moe_rows_all"][0], load)
    np.testing.assert_array_equal(counters["moe_rows"][0], load[4:8])
    flat = counter_metrics({"block_0": {"moe": {
        k: v[0] for k, v in counters.items()}}})
    assert flat["moe_rows_all"] == 96 * 8
    assert flat["moe_rows_all_max_over_mean"] == pytest.approx(
        load.max() / 48.0)
    assert flat["moe_rows_max_over_mean"] == pytest.approx(
        load[4:8].max() / 48.0)
    assert flat["moe_rows_all_max_over_mean"] > flat["moe_rows_max_over_mean"]
    # Coefficient 0: the counters stay, no term joins the objective.
    _, off = _apply(_layer(4, 4, coef=0.0), p, x)
    assert "aux_loss" not in off
    assert float(off["counters"]["moe_aux_loss"][0]) == 0.0
    # The term's gradient pushes the overloaded experts' logits down.
    grad = jax.grad(lambda r: layer.apply(
        {"params": {**p, "router": {"kernel": r}}}, x,
        mutable=["aux_loss", "counters"])[1]["aux_loss"]["load_balance"][0]
    )(jnp.asarray(p["router"]["kernel"]))
    push = np.asarray((jnp.asarray(x.reshape(-1, 32)).mean(0) @ grad))
    assert np.isfinite(push).all() and np.abs(np.asarray(grad)).max() > 0


def test_the_two_reference_copies_are_byte_identical():
    bench = os.path.join(
        os.path.dirname(HERE), "benchmark", "reference", "mellum2_moe.py")
    with open(bench, "rb") as a, open(REF.__file__, "rb") as b:
        assert a.read() == b.read()


def test_the_accepted_cells_tiles_are_what_they_were():
    # Under the 1,024 window the rule's 1024 x 1024 won on the chip in all
    # three kernels (PERF.md section 6, PR 35), so the rule does not take
    # the window, and every cell's tiles stay.
    assert flash_tiles(8192, 8192, 128, jnp.bfloat16) == (1024, 1024)
    assert flash_tiles(4096, 4096, 128, jnp.bfloat16) == (1024, 1024)
    assert flash_tiles(512, 512, 128, jnp.bfloat16) == (512, 512)
    assert flash_tiles(8192, 8192, 64, jnp.bfloat16) == (1024, 1024)
    assert flash_tiles(8192, 8192, 192, jnp.bfloat16, 128) == (1024, 1024)


#: The accepted configurations' ``program.env`` with the widths divided so
#: the CPU builds them at once, and what each builds: the parameter tree's
#: shapes and the StableHLO text of the objective's value and gradient, as
#: digests. The trees and ``sc2_3b_block``'s text are what 9b9390d built
#: (PR 35's parent; ``mellum2_12b_cut``'s tree what a54b777 built); the
#: three routed configurations' texts are recorded anew whenever the
#: grouped layer's program changes: last when its products went through
#: ``ops/grouped_matmul.py`` (the products against the transposed stacks
#: read the stacks in place) and it began to count ``moe_rows_tiled``.
PRESETS = {
    "sc2_3b_block": (
        dict(DCT_D_MODEL="96", DCT_D_FF="384", DCT_N_HEADS="24",
             DCT_N_KV_HEADS="2"),
        "ee8cc5ee3bbd5641", "9e2c314abf323051"),
    "lfm2_24b_a2b_ep8": (
        dict(DCT_D_MODEL="64", DCT_D_FF="368", DCT_MOE_D_FF="48"),
        "29bdf51521177ce8", "7a07948292150674"),
    "moonlight_16b_a3b_ep8": (
        dict(DCT_D_MODEL="64", DCT_D_FF="96", DCT_N_HEADS="4",
             DCT_N_KV_HEADS="4", DCT_MOE_D_FF="24", DCT_MOE_SHARED_D_FF="48",
             DCT_KV_LORA_RANK="16", DCT_QK_NOPE_HEAD_DIM="8",
             DCT_QK_ROPE_HEAD_DIM="4", DCT_V_HEAD_DIM="8"),
        "5eae93a7bbcc20cc", "1a97cadd4e1eb404"),
    "mellum2_12b_cut": (
        dict(DCT_D_MODEL="64", DCT_D_FF="96", DCT_N_HEADS="8",
             DCT_N_KV_HEADS="2", DCT_HEAD_DIM="16", DCT_MOE_D_FF="24",
             DCT_ATTN_WINDOW="16", DCT_ROPE_ORIGINAL_LEN="32"),
        "92ba8f2ae08a3289", "49574ee973ba8d34"),
}


def preset_digests(preset: str, small: dict) -> tuple[str, str]:
    """(digest of the parameter tree's paths and shapes, digest of the
    lowered text of the training objective's value and gradient) of one
    accepted configuration at small widths, 2 sequences of 64 positions."""
    with open(os.path.join(os.path.dirname(HERE), "benchmark", "configs",
                           preset + ".json")) as f:
        env = {k: str(v) for k, v in json.load(f)["program"]["env"].items()}
    cfg = _from_env({**env, **small, "DCT_SEQ_LEN": "64"})
    model = get_model(cfg, input_dim=5, compute_dtype=jnp.float32)
    x = jnp.zeros((2, 64, 5), jnp.float32)
    y = jnp.zeros((2, 64), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x[:1])["params"]
    tree = sorted(
        (jax.tree_util.keystr(path), leaf.shape)
        for path, leaf in jax.tree_util.tree_leaves_with_path(params))

    def objective(p, x, y, key):
        logits, sown = model.apply(
            {"params": p}, x, train=True, rngs={"dropout": key},
            mutable=["aux_loss", "counters", "param_steps"])
        loss = _ce(logits, y) + sum(jax.tree.leaves(sown.get("aux_loss", {})))
        return loss, (sown.get("counters", {}), sown.get("param_steps", {}))

    text = jax.jit(jax.value_and_grad(objective, has_aux=True)).lower(
        params, x, y, jax.random.PRNGKey(0)).as_text()
    return tuple(
        hashlib.sha256(repr(part).encode()).hexdigest()[:16]
        for part in (tree, text))


@pytest.mark.parametrize("preset", list(PRESETS))
def test_accepted_presets_build_the_tree_and_the_program_of_the_parent(preset):
    small, tree, text = PRESETS[preset]
    assert preset_digests(preset, small) == (tree, text)

"""The hybrid family as the deepseek_v3 layer (``latent_attention`` with
queries and keys wider than values, sigmoid-routed SwiGLU experts beside a
shared expert, the routed scaling factor and the selection bias's balancing
update) against its plain float32 reference, at small widths on the CPU
with seeded weights; the flash kernels with unequal widths in the
interpreter; and the accepted configurations' parameter trees, unchanged."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_moonlight_moe as REF
from dct_tpu.checkpoint.manager import (
    TrainStateCheckpointer,
    load_checkpoint,
    save_checkpoint,
)
from dct_tpu.config import ModelConfig
from dct_tpu.models.moe import MoEFFN
from dct_tpu.models.registry import get_model
from dct_tpu.ops.attention import (
    blockwise_attention,
    dense_attention,
    ring_attention,
)
from dct_tpu.ops.pallas_attention import flash_attention, flash_tiles
from dct_tpu.train.state import create_train_state
from dct_tpu.train.steps import counter_metrics, make_train_step

TOL = 2e-5
SPEED = 0.001
HERE = os.path.dirname(os.path.abspath(__file__))

#: A small deepseek_v3: three latent-attention layers, one dense, 16 experts
#: of which 4 are held, top-6, a shared expert of twice an expert's width.
REF_CONFIG = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "q_lora_rank": None,
    "rope_theta": 50000.0, "rms_norm_eps": 1e-5, "num_experts_per_tok": 6,
    "n_routed_experts": 4, "first_expert": 4, "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2.446,
}
ENV = {
    "DCT_MODEL": "weather_hybrid_moe_causal", "DCT_D_MODEL": "32",
    "DCT_N_HEADS": "4", "DCT_N_LAYERS": "3", "DCT_D_FF": "96",
    "DCT_SEQ_LEN": "48", "DCT_POS_EMBED": "rope", "DCT_ROPE_THETA": "50000",
    "DCT_DROPOUT": "0", "DCT_NORM": "rmsnorm", "DCT_NORM_EPS": "1e-5",
    "DCT_MLP": "swiglu", "DCT_USE_BIAS": "0",
    "DCT_LAYER_TYPES": "latent_attention,latent_attention,latent_attention",
    "DCT_NUM_DENSE_LAYERS": "1", "DCT_KV_LORA_RANK": "16",
    "DCT_QK_NOPE_HEAD_DIM": "8", "DCT_QK_ROPE_HEAD_DIM": "4",
    "DCT_V_HEAD_DIM": "8", "DCT_N_EXPERTS": "16", "DCT_ROUTER_TOP_K": "6",
    "DCT_MOE_D_FF": "24", "DCT_MOE_SHARED_D_FF": "48",
    "DCT_EXPERTS_HELD": "4", "DCT_FIRST_EXPERT": "4",
    "DCT_ROUTED_SCALING": "2.446", "DCT_ROUTER_GATE_EPS": "1e-20",
    "DCT_BIAS_UPDATE_SPEED": str(SPEED),
}


def _from_env(env):
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        return ModelConfig.from_env()
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.update({k: v})


@pytest.fixture(scope="module")
def family():
    """(model, params with a seeded non-zero selection bias, x, y), the
    model built from the environment the way ``RunConfig.from_env`` builds
    it."""
    model = get_model(_from_env(ENV), input_dim=5, compute_dtype=jnp.float32)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 48, 5)).astype(np.float32)
    y = rng.integers(0, 2, (2, 48)).astype(np.int32)
    params = jax.device_get(
        jax.jit(model.init)(jax.random.PRNGKey(1), jnp.asarray(x[:1]))
    )["params"]
    for block in params.values():
        if "moe" in block:
            block["moe"]["expert_bias"] = (
                0.05 * rng.standard_normal(16)).astype(np.float32)
    return model, params, x, y


def _ce(logits, y):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], -1).mean()


def test_family_reads_the_latent_layer_and_the_shared_expert_from_env(family):
    model, params, _, _ = family
    assert model.layer_types == ("latent_attention",) * 3
    attn = params["block_1"]["attn"]
    assert {k: v["kernel"].shape for k, v in attn.items() if "proj" in k} == {
        "q_proj": (32, 4 * 12), "kv_a_proj": (32, 16 + 4),
        "kv_b_proj": (16, 4 * 16), "o_proj": (4 * 8, 32)}
    assert attn["kv_norm"]["scale"].shape == (16,)
    assert "ffn_gate" in params["block_0"] and "moe" not in params["block_0"]
    moe = params["block_2"]["moe"]
    assert moe["experts_in_kernel"].shape == (4, 32, 24)
    assert moe["shared_gate"]["kernel"].shape == (32, 48)
    assert moe["shared_out"]["kernel"].shape == (48, 32)
    assert moe["router"]["kernel"].shape == (32, 16)
    with pytest.raises(ValueError, match="latent_attention"):
        get_model(_from_env({**ENV, "DCT_LAYER_TYPES": "a,b,c"}), input_dim=5
                  ).init(jax.random.PRNGKey(0), jnp.zeros((1, 48, 5)))


@pytest.mark.parametrize("what", ["logits", "loss", "gradients", "bias"])
def test_program_matches_the_reference_in_float32(family, what):
    model, params, x, y = family
    kw = REF.settings(REF_CONFIG)
    with jax.default_matmul_precision("highest"):
        if what in ("logits", "loss"):
            got = np.asarray(model.apply({"params": params}, x, train=False))
            want, want_loss = REF.forward_and_loss(params, x, y, REF_CONFIG)
            assert got.shape == want.shape == (2, 48, 2)
            if what == "logits":
                np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
            else:
                assert abs(REF.cross_entropy(got, y) - want_loss) < TOL
        elif what == "gradients":
            got = jax.grad(lambda p: _ce(
                model.apply({"params": p}, x, train=False), y))(params)
            want = jax.grad(lambda p: _ce(jnp.stack(
                [REF.forward_one(p, xi, None, **kw)[0] for xi in x]), y)
            )(params)
            flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
            flat_got = jax.tree_util.tree_leaves_with_path(got)
            assert len(flat_got) == len(flat_want)
            for path, g in flat_got:
                np.testing.assert_allclose(
                    g, flat_want[path], rtol=0, atol=TOL, err_msg=str(path))
            # The shared expert and the scaled gates carry gradient; the
            # selection bias is under stop_gradient: Adam sees zero.
            moe = got["block_1"]["moe"]
            assert np.abs(moe["shared_in"]["kernel"]).max() > 10 * TOL
            assert np.abs(moe["router"]["kernel"]).max() > 10 * TOL
            assert not np.asarray(moe["expert_bias"]).any()
        else:
            # One optimizer step of the program: every MoE layer's bias
            # moves by the reference's balancing update of the routing
            # that step saw, and by nothing else.
            state = create_train_state(
                model, input_dim=5, lr=1e-3, seed=1,
                example_shape=(1, 48, 5), grad_clip_norm=1.0)
            state = state.replace(params={"params": params})
            new, _ = make_train_step(donate=False)(
                state, jnp.asarray(x), jnp.asarray(y), jnp.ones(2))
            topk = REF.forward(params, x, REF_CONFIG)["topk"]  # [N, M, T, k]
            for m, name in enumerate(("block_1", "block_2")):
                bias = params[name]["moe"]["expert_bias"]
                want = REF.bias_step(bias, topk[:, m], SPEED)
                assert 0 < np.abs(want - bias).max() <= SPEED * 1.001
                np.testing.assert_allclose(
                    new.params["params"][name]["moe"]["expert_bias"], want,
                    rtol=0, atol=1e-7)
            # A parameter nobody sowed a step for moved by Adam alone.
            assert np.abs(
                new.params["params"]["block_1"]["moe"]["router"]["kernel"]
                - params["block_1"]["moe"]["router"]["kernel"]).max() > 1e-5


def _layer(held, first, shared=48, speed=0.0, e=16):
    return MoEFFN(
        d_model=32, d_ff=24, n_experts=e, aux_weight=0.0,
        dispatch="grouped", top_k=6, experts_held=held, first_expert=first,
        routed_scale=2.446, gate_eps=1e-20, shared_d_ff=shared,
        bias_update_speed=speed)


def _apply(layer, p, x):
    with jax.default_matmul_precision("highest"):
        out, sown = layer.apply(
            {"params": p}, x,
            mutable=["counters", "intermediates", "param_steps"])
    return np.asarray(out), jax.device_get(sown)


def test_eight_shares_routed_parts_and_the_shared_expert_once_add_up():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((2, 48, 32)).astype(np.float32)
    p = jax.device_get(
        _layer(0, 0).init(jax.random.PRNGKey(5), x))["params"]
    p = {**p, "expert_bias": (
        0.1 * rng.standard_normal(16)).astype(np.float32)}
    want, _, _ = REF._moe(
        jnp.asarray(x.reshape(-1, 32)), p, top_k=6, first=0, scaling=2.446,
        routing=None)
    uncut, _ = _apply(_layer(0, 0), p, x)
    np.testing.assert_allclose(uncut.reshape(-1, 32), want, rtol=0, atol=TOL)
    routed = {k: v for k, v in p.items() if not k.startswith("shared_")}
    total = np.zeros_like(np.asarray(want))
    rows = 0
    for share in range(8):
        first = 2 * share
        held = {
            k: (v[first:first + 2] if k.startswith("experts_") else v)
            for k, v in routed.items()}
        out, sown = _apply(_layer(2, first, shared=0), held, x)
        total += out.reshape(-1, 32)
        rows += int(sown["counters"]["moe_rows"][0].sum())
        assert int(sown["counters"]["moe_rows_overflowed"][0]) == 0
    assert rows == 96 * 6  # every routed row is some share's
    t = jnp.asarray(x.reshape(-1, 32))
    shared = REF._gated_mlp(
        t, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
        p["shared_out"]["kernel"])
    assert np.abs(np.asarray(shared)).max() > 100 * TOL
    np.testing.assert_allclose(total + shared, want, rtol=0, atol=TOL)
    # A share that holds the shared expert computes it whole: eight such
    # shares would count it eight times.
    with_shared, _ = _apply(
        _layer(2, 0), {**p, **{k: v[:2] for k, v in p.items()
                               if k.startswith("experts_")}}, x)
    without, _ = _apply(
        _layer(2, 0, shared=0),
        {k: (v[:2] if k.startswith("experts_") else v)
         for k, v in routed.items()}, x)
    np.testing.assert_allclose(
        with_shared.reshape(-1, 32) - without.reshape(-1, 32), shared,
        rtol=0, atol=TOL)


def test_the_balancing_step_pushes_the_load_towards_the_mean():
    """The update as the model sows it: down by the speed where an expert
    drew more rows than the mean, up where fewer, over ALL experts (the 12
    held elsewhere too); repeated, it evens a load that a skewed bias
    made."""
    rng = np.random.default_rng(6)
    x = rng.standard_normal((2, 48, 32)).astype(np.float32)
    layer = _layer(4, 4, speed=0.01)
    p = jax.device_get(layer.init(jax.random.PRNGKey(7), x))["params"]
    p["expert_bias"] = np.where(np.arange(16) < 3, 0.3, 0.0).astype(
        np.float32)
    _, sown = _apply(layer, p, x)
    topk = sown["intermediates"]["topk"][0]
    load = np.bincount(np.asarray(topk).reshape(-1), minlength=16)
    step = np.asarray(sown["param_steps"]["expert_bias"])
    np.testing.assert_array_equal(
        step, np.float32(0.01) * np.sign(load.mean() - load))
    assert (step[:3] < 0).all() and load[:3].min() > 1.5 * load.mean()
    np.testing.assert_allclose(
        p["expert_bias"] + step, REF.bias_step(p["expert_bias"], topk, 0.01),
        rtol=0, atol=1e-7)
    assert float(sown["counters"]["moe_bias_abs_max"][0]) == pytest.approx(0.3)
    first = load.max() / load.mean()
    for _ in range(60):
        _, sown = _apply(layer, p, x)
        p["expert_bias"] = p["expert_bias"] + np.asarray(
            sown["param_steps"]["expert_bias"])
    load = np.bincount(np.asarray(
        sown["intermediates"]["topk"][0]).reshape(-1), minlength=16)
    assert first > 2.0 and load.max() / load.mean() < 1.5
    # Speed 0 (the lfm2 configuration): nothing sown, no counter.
    _, off = _apply(_layer(4, 4), p, x)
    assert "param_steps" not in off
    assert "moe_bias_abs_max" not in off["counters"]


def test_a_max_counter_is_the_largest_not_the_sum():
    sown = {
        f"block_{i}": {"moe": {"moe_bias_abs_max": (np.float32(v),),
                               "moe_rows": (np.array([1, 2], np.int32),)}}
        for i, v in enumerate((0.25, 0.75, 0.5))}
    flat = counter_metrics(sown)
    assert flat["moe_bias_abs_max"] == 0.75
    assert flat["moe_rows"] == 9 and flat["moe_rows_1"] == 6


def _flash(q, k, v):
    return flash_attention(q, k, v, 128, 128, True, None, True, None)


@pytest.mark.parametrize("what", ["o", "dq", "dk", "dv"])
@pytest.mark.parametrize("h_kv, d, d_v", [
    (2, 48, 32),     # under one lane tile: the operands as they come
    (1, 48, 32),     # grouped keys and values
    (2, 160, 128),   # over one: a row that ends inside a lane tile
])
def test_flash_with_unequal_widths_matches_the_plain_path(what, h_kv, d, d_v):
    """Queries and keys wider than the values, two tiles a side, in the
    interpreter: the forward and each gradient against dense attention
    (and the blockwise path the policy falls back to)."""
    rng = np.random.default_rng(0)
    q, k, v, ct = (
        jnp.asarray(rng.standard_normal((1, heads, 256, width)), jnp.float32)
        for heads, width in ((2, d), (h_kv, d), (h_kv, d_v), (2, d_v)))

    def both(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return dict(zip(("o", "dq", "dk", "dv"), (out, *vjp(ct))))

    want = both(lambda q, k, v: dense_attention(q, k, v, causal=True))[what]
    got = both(_flash)[what]
    block = both(lambda q, k, v: blockwise_attention(
        q, k, v, block_size=64, causal=True))[what]
    assert got.shape == want.shape == {
        "o": (1, 2, 256, d_v), "dq": (1, 2, 256, d),
        "dk": (1, h_kv, 256, d), "dv": (1, h_kv, 256, d_v)}[what]
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    np.testing.assert_allclose(block, want, rtol=0, atol=TOL)


def test_tiles_follow_the_wider_row_and_the_ring_refuses_unequal_widths():
    # Latent attention's kernels are sized by the mean of the two widths,
    # each in the whole lane tiles it takes in VMEM (192 -> 256): 1.5 x
    # the measured row keeps the cap, 2 x halves it. The accepted cells'
    # tiles are what they were.
    assert flash_tiles(8192, 8192, 192, jnp.bfloat16, 128) == (1024, 1024)
    assert flash_tiles(8192, 8192, 256, jnp.bfloat16, 128) == (1024, 1024)
    assert flash_tiles(8192, 8192, 256, jnp.bfloat16, 256) == (512, 512)
    assert flash_tiles(8192, 8192, 192, jnp.bfloat16) == (512, 512)
    assert flash_tiles(8192, 8192, 64, jnp.bfloat16) == (1024, 1024)
    assert flash_tiles(4096, 4096, 128, jnp.bfloat16) == (1024, 1024)
    assert flash_tiles(512, 512, 128, jnp.bfloat16) == (512, 512)
    from dct_tpu.parallel.mesh import make_mesh
    from dct_tpu.config import MeshConfig

    mesh = make_mesh(MeshConfig(data=4, model=1, seq=2))
    q = jnp.zeros((4, 2, 64, 48))
    with pytest.raises(ValueError, match="values as wide"):
        ring_attention(q, q, jnp.zeros((4, 2, 64, 32)), mesh=mesh, causal=True)


def test_both_checkpoint_tiers_keep_the_bias_and_a_resumed_run_moves_it_on(
        family, tmp_path):
    model, params, x, y = family
    step = make_train_step(donate=False)
    batch = (jnp.asarray(x), jnp.asarray(y), jnp.ones(2))

    def fresh():
        return create_train_state(
            model, input_dim=5, lr=1e-3, seed=1, example_shape=(1, 48, 5),
            grad_clip_norm=1.0)

    state = fresh()
    for _ in range(3):
        state, _ = step(state, *batch)
    bias = np.asarray(state.params["params"]["block_2"]["moe"]["expert_bias"])
    assert 0 < np.abs(bias).max() <= 3 * SPEED * 1.001
    # The deploy tier (the parameters alone) and the resume tier (the whole
    # train state) hold the bias like any parameter.
    path = save_checkpoint(
        str(tmp_path / "last.ckpt"), jax.device_get(state.params),
        {"input_dim": 5})
    deployed, _ = load_checkpoint(path)
    np.testing.assert_array_equal(
        deployed["params"]["block_2"]["moe"]["expert_bias"], bias)
    ckptr = TrainStateCheckpointer(str(tmp_path / "train_state"))
    ckptr.save(state)
    restored = ckptr.restore(fresh())
    assert int(restored.step) == 3
    np.testing.assert_array_equal(
        restored.params["params"]["block_2"]["moe"]["expert_bias"], bias)
    # The resumed run takes the next balancing step from the restored
    # bias, as the uninterrupted run does.
    on, _ = step(state, *batch)
    resumed, _ = step(restored, *batch)
    for name in ("block_1", "block_2"):
        a = np.asarray(on.params["params"][name]["moe"]["expert_bias"])
        b = np.asarray(resumed.params["params"][name]["moe"]["expert_bias"])
        np.testing.assert_array_equal(a, b)
    assert np.abs(
        np.asarray(on.params["params"]["block_2"]["moe"]["expert_bias"])
        - bias).max() == pytest.approx(SPEED, rel=1e-3)


def test_the_two_reference_copies_are_byte_identical():
    bench = os.path.join(
        os.path.dirname(HERE), "benchmark", "reference", "moonlight_moe.py")
    with open(bench, "rb") as a, open(REF.__file__, "rb") as b:
        assert a.read() == b.read()


def _tree(env):
    cfg = _from_env(env)
    model = get_model(cfg, input_dim=5, compute_dtype=jnp.float32)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.seq_len, 5), jnp.float32))["params"]
    return jax.tree.map(lambda a: a.shape, shapes)


@pytest.mark.parametrize("preset", ["sc2_3b_block", "lfm2_24b_a2b_ep8"])
def test_accepted_presets_build_the_parameter_tree_of_the_parent(preset):
    """The two accepted configurations' ``program.env`` (widths divided so
    the CPU builds them at once): the tree the parent commit built,
    recorded there. The block's and the layer's new fields default to what
    those configurations ran."""
    import json

    with open(os.path.join(os.path.dirname(HERE), "benchmark", "configs",
                           preset + ".json")) as f:
        env = {k: str(v) for k, v in json.load(f)["program"]["env"].items()}
    env["DCT_SEQ_LEN"] = "64"
    if preset == "sc2_3b_block":
        env.update(DCT_D_MODEL="96", DCT_D_FF="384", DCT_N_HEADS="24",
                   DCT_N_KV_HEADS="2")
        block = {
            "attn": {"o_proj": {"bias": (96,), "kernel": (96, 96)},
                     "qkv_proj": {"bias": (112,), "kernel": (96, 112)}},
            "ffn_in": {"bias": (384,), "kernel": (96, 384)},
            "ffn_out": {"bias": (96,), "kernel": (384, 96)},
            "ln_attn": {"bias": (96,), "scale": (96,)},
            "ln_ffn": {"bias": (96,), "scale": (96,)},
        }
        assert _tree(env) == {
            "block_0": block, "block_1": block, "block_2": block,
            "head": {"bias": (2,), "kernel": (96, 2)},
            "in_proj": {"bias": (96,), "kernel": (5, 96)},
            "ln_out": {"bias": (96,), "scale": (96,)},
        }
        return
    env.update(DCT_D_MODEL="64", DCT_D_FF="368", DCT_MOE_D_FF="48")
    norm = {"scale": (64,)}
    conv = {"conv": {"conv_kernel": (64, 3),
                     "in_proj": {"kernel": (64, 192)},
                     "out_proj": {"kernel": (64, 64)}}}
    moe = {"moe": {"expert_bias": (64,),
                   "experts_gate_kernel": (8, 64, 48),
                   "experts_in_kernel": (8, 64, 48),
                   "experts_out_kernel": (8, 48, 64),
                   "router": {"kernel": (64, 64)}}}
    ends = {"ln_attn": norm, "ln_ffn": norm}
    assert _tree(env) == {
        "block_0": {**conv, **ends,
                    "ffn_gate": {"kernel": (64, 368)},
                    "ffn_in": {"kernel": (64, 368)},
                    "ffn_out": {"kernel": (368, 64)}},
        "block_1": {"attn": {"k_norm": {"scale": (2,)},
                             "q_norm": {"scale": (2,)},
                             "o_proj": {"kernel": (64, 64)},
                             "qkv_proj": {"kernel": (64, 96)}},
                    **moe, **ends},
        "block_2": {**conv, **moe, **ends},
        "block_3": {**conv, **moe, **ends},
        "block_4": {**conv, **moe, **ends},
        "head": {"kernel": (64, 2)},
        "in_proj": {"kernel": (5, 64)},
        "ln_out": norm,
    }

"""What the ``laguna_xs2_ep16`` configuration adds: the cell in the manifest,
the configuration against the published widths, the operation counts
against a hand count of the 5-layer cut (each kind at its own head count),
the reader of ``attn.gate_share`` against a few hand-made events, and
driver ``fit_routed``'s two-part reference check on the CPU: passed by the
program against its reference, failed by each planted fault."""

import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest as mf
from benchmark.drivers import fit_routed
from benchmark.drivers.fit import Plan, _etl, env_overlay
from benchmark.reduce import trace as tr

CELL = "laguna_xs2_ep16.fit_seq8192"
CFG = mf.load_json(f"{mf.BENCH_DIR}/configs/laguna_xs2_ep16.json")
REF_PATH = f"{mf.BENCH_DIR}/reference/laguna_moe.py"
REF = mf.load_module(REF_PATH, "bench_reference_laguna")
LOAD_MODULE = mf.load_module
flops = mf.load_flops(CFG)
#: The accepted metrics the cell reports, and the one it adds.
READ = {
    "device.idle_share", "trainer.epoch_gap_ms", "trainer.goodput_share",
    "step.device_ms", "step.mfu", "kernels.flash_share",
    "checkpoint.stall_share", "trainer.host_epoch_s",
    "trainer.join_wait_share", "checkpoint.section_s",
    "checkpoint.disk_share", "device.idle_unexplained_share",
    "trainer.startup_s", "trainer.first_dispatch_s", "moe.ffn_share",
    "moe.expert_roofline", "moe.load_max_over_mean", "moe.bound_over_routed",
    "moe.shared_share", "attn.sliding_share", "attn.full_share",
    "attn.sliding_roofline", "attn.full_roofline", "step.optimizer_share",
    "step.backward_share", "step.eval_share", "dense_mlp.share",
    "kernels.flash_fwd_share", "kernels.flash_bwd_share", "attn.gate_share"}


def test_the_cell_is_in_the_manifest_with_its_metrics():
    manifest = mf.load_manifest()
    assert mf.lint(manifest) == []
    cell, config, traffic = mf.load_cell(manifest, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "laguna_xs2_ep16", "fit_gated_swa_moe_seq8192")
    assert config == CFG and traffic["driver"] == "fit_routed"
    assert (traffic["seq_len"], traffic["batch_per_chip"],
            traffic["val_batches"]) == (8192, 1, 2)
    assert {m["name"] for m in mf.metrics_of(
        manifest, "end_to_end", CELL)} == {"fit_tokens_per_s", "setup_s"}
    assert {m["name"] for m in mf.metrics_of(
        manifest, "per_layer", CELL)} == READ
    gate, = [m for m in manifest["per_layer"]
             if m["name"] == "attn.gate_share"]
    reader = mf.load_layer_metric("attn.gate_share")
    assert gate == {
        "name": "attn.gate_share", "unit": reader.UNIT, "better": "lower",
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES, "workloads": [CELL]}
    assert manifest["per_layer"][-1] is gate
    assert manifest["workloads"][-1] is cell
    assert manifest["configs"][-1]["name"] == "laguna_xs2_ep16"


def test_configuration_keeps_the_published_widths_and_states_the_cut():
    entry = next(c for c in mf.load_manifest()["configs"]
                 if c["name"] == CFG["name"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    assert entry["source"] == CFG["source"] == (
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")
    assert (CFG["hidden_size"], CFG["intermediate_size"],
            CFG["moe_intermediate_size"],
            CFG["shared_expert_intermediate_size"], CFG["num_experts_per_tok"],
            CFG["sliding_window"], CFG["rms_norm_eps"]) == (
        2048, 8192, 512, 512, 8, 512, 1e-6)
    assert CFG["published"] == {"num_hidden_layers": 40, "num_experts": 256}
    assert (CFG["num_hidden_layers"], CFG["num_experts"]) == (5, 16)
    run = CFG["layers_run"]
    assert run == [0, 1, 2, 3, 4]
    kinds = ["full_attention"] + ["sliding_attention"] * 3 + [
        "full_attention"]
    assert [CFG["layer_types"][i] for i in run] == kinds
    assert [CFG["mlp_layer_types"][i] for i in run] == ["dense"] + [
        "sparse"] * 4
    assert [CFG["num_attention_heads_per_layer"][i] for i in run] == [
        48, 64, 64, 64, 48]
    env = CFG["program"]["env"]
    assert env["DCT_LAYER_TYPES"].split(",") == kinds
    assert (env["DCT_D_MODEL"], env["DCT_HEAD_DIM"], env["DCT_N_HEADS"],
            env["DCT_N_KV_HEADS"], env["DCT_ATTN_WINDOW"], env["DCT_D_FF"]
            ) == (2048, 128, 48, 8, 512, 8192)
    assert env["DCT_ATTN_KINDS"] == (
        "full_attention:n_heads=48,rope_theta=500000,rotary_fraction=0.5;"
        "sliding_attention:n_heads=64,rope_theta=10000,rotary_fraction=1")
    assert env["DCT_ATTN_GATE"] == "per_head" and CFG["gating"] is True
    full = CFG["rope_parameters"]["full_attention"]
    assert (env["DCT_ROPE_SCALING"], env["DCT_ROPE_THETA"],
            env["DCT_ROPE_FACTOR"], env["DCT_ROPE_ORIGINAL_LEN"],
            env["DCT_ROPE_BETA_FAST"], env["DCT_ROPE_BETA_SLOW"],
            env["DCT_ROPE_ATTENTION_FACTOR"]) == (
        full["rope_type"], full["rope_theta"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"],
        full["beta_slow"], full["attention_factor"])
    assert full["partial_rotary_factor"] == 0.5
    assert CFG["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert (env["DCT_N_EXPERTS"], env["DCT_EXPERTS_HELD"],
            env["DCT_FIRST_EXPERT"], env["DCT_ROUTER_TOP_K"],
            env["DCT_MOE_D_FF"], env["DCT_MOE_SHARED_D_FF"],
            env["DCT_NUM_DENSE_LAYERS"], env["DCT_ROUTED_SCALING"]) == (
        256, 16, 0, 8, 512, 512, 1, CFG["moe_routed_scaling_factor"])
    assert env["DCT_ROUTER_SCORING"] == "sigmoid"
    assert env["DCT_BIAS_UPDATE_SPEED"] == CFG["bias_update_speed"] > 0
    for key in ("deployment", "guarantees", "assumed", "departures",
                "left_out", "reduced_why", "expect"):
        assert CFG[key], key
    assert CFG["expect"]["attention_path"] == "flash"
    assert CFG["expect"]["flash_interpret"] is False


def test_operation_counts_of_the_five_layer_cut_by_hand():
    def attention(heads):  # q/k/v, the gate, the output projection
        return 2048 * (heads + 16) * 128 + 2048 * heads + heads * 128 * 2048
    assert attention(48) == 29_458_432 and attention(64) == 37_879_808
    assert flops.attention_weights(CFG, 48) == attention(48)
    expert = 3 * 2048 * 512                                    # 3,145,728
    assert flops.expert_weights(CFG) == expert
    # 8 of 256 experts a token, 16 of them here: half an expert; the
    # router and the shared expert every token.
    routed = 2048 * 256 + expert + 0.5 * expert
    dense = 3 * 2048 * 8192
    weights = 5 * 2048 + 2048 * 2 + 2 * attention(48) + 3 * attention(64) \
        + dense + 4 * routed
    assert flops.gemm_weights_per_token(CFG) == weights
    assert weights == pytest.approx(243.87e6, rel=1e-4)
    # A row at position t sees min(t + 1, 512) keys under the window.
    band = 512 * 513 // 2 + (8192 - 512) * 512
    assert band == sum(min(t + 1, 512) for t in range(8192)) == 4_063_488
    triangle = 8192 * 8193 // 2
    # Forward and backward, 2 x (128 + 128) a pair and head, each layer at
    # its own heads: three windowed layers of 64, two full layers of 48.
    assert flops.sliding_train_flops(CFG, 8192) == 3 * 512 * band * 3 * 64
    assert flops.full_train_flops(CFG, 8192) == 3 * 512 * triangle * 2 * 48
    assert flops.full_train_flops(CFG, 8192) / 8192 == pytest.approx(
        0.604e9, rel=1e-3)
    assert flops.sliding_train_flops(CFG, 8192) / 8192 == pytest.approx(
        0.1463e9, rel=1e-3)
    per_token = flops.train_flops_per_token(CFG, 8192)
    assert per_token == pytest.approx(6 * weights + (
        3 * 512 * band * 3 * 64 + 3 * 512 * triangle * 2 * 48) / 8192)
    assert per_token == pytest.approx(2.213e9, rel=1e-3)
    # Attention of both kinds with its gates: about 80% of the operations.
    both = (2 * attention(48) + 3 * attention(64)) * 6 + (
        flops.sliding_train_flops(CFG, 8192)
        + flops.full_train_flops(CFG, 8192)) / 8192
    assert 0.78 < both / per_token < 0.82
    # 256 rows an expert and layer at the even load, 16 experts, 4 layers.
    assert flops.expert_train_flops(CFG, 16384) == pytest.approx(
        3 * 3 * 2 * 2048 * 512 * 16384)


HLO = '''
HloModule jit_epoch_fused
  %fusion.1 = bf16[8,8] fusion(%a), kind=kOutput, calls=%fc, metadata={op_name="jit(epoch_fused)/while/body/block_1/attn/attn.sliding/qkv_proj/dot_general"}
  %attn.sliding.2 = bf16[8,8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(epoch_fused)/while/body/block_1/attn/attn.sliding/pallas_call"}
  %fusion.3 = bf16[8,8] fusion(%a), kind=kOutput, calls=%fc, metadata={op_name="jit(epoch_fused)/while/body/block_1/attn/attn.sliding/attn.gate/gate_proj/dot_general"}
  %fusion.4 = bf16[8,8] fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(epoch_fused)/while/body/transpose(jvp(block_0))/attn/attn.full/attn.gate/mul"}
  %fusion.5 = bf16[8,8] fusion(%a), kind=kLoop, calls=%fc
'''


def _art(hlo=HLO):
    def op(name, body, start_ms, dur_ms):
        return (f"%{name} = bf16[8,8] {body}", start_ms * 1e6, dur_ms * 1e6)

    call = 'custom-call(%a), custom_call_target="tpu_custom_call"'
    events = []
    for step in range(4):  # four steps of 100 ms
        t = step * 100
        events += [
            op("fusion.1", "fusion(%a), kind=kOutput", t, 10),
            op("attn.sliding.2", call, t + 10, 20),
            op("fusion.3", "fusion(%a), kind=kOutput", t + 30, 6),
            op("fusion.4", "fusion(%a), kind=kLoop", t + 36, 4),
            op("fusion.5", "fusion(%a), kind=kLoop", t + 40, 60),
        ]
    trace = tr.Trace(
        [tr.Device(0, tr.Line(events), tr.Line([]))], [], 0, 4e8)
    return dict(trace=trace, hlo_text=hlo)


def test_the_gate_share_on_hand_made_events():
    read = mf.load_layer_metric("attn.gate_share").read
    # 10 of a step's 100 ms under attn.gate, forward and backward; the
    # layer's other ops are attn.sliding's and attn.full's alone.
    assert read(_art()) == pytest.approx(10.0)
    assert mf.load_layer_metric("attn.sliding_share").read(
        _art()) == pytest.approx(36.0)
    # A program without the scope (no gate, or the parent), a run without
    # the text, no device plane: nothing.
    assert read(_art(HLO.replace("attn.gate/", ""))) is None
    assert read(_art(hlo=None)) is None
    assert read({**_art(), "trace": None}) is None


# -- driver fit_routed's reference check at a tiny size on the CPU ---------

#: The cell's environment at small widths: 64 wide, 6 full / 8 windowed
#: query heads over 2 of 16 (the full ones rotating 8 dims), window 32 of
#: 128 positions, 32 experts of which 8 are held, top-8; float32.
TINY = {
    "DCT_D_MODEL": 64, "DCT_N_HEADS": 6, "DCT_N_KV_HEADS": 2,
    "DCT_HEAD_DIM": 16, "DCT_D_FF": 96, "DCT_ATTN_WINDOW": 32,
    "DCT_ATTN_KINDS": "full_attention:n_heads=6,rope_theta=500000,"
                      "rotary_fraction=0.5;sliding_attention:n_heads=8,"
                      "rope_theta=10000,rotary_fraction=1",
    "DCT_ROPE_ORIGINAL_LEN": 64, "DCT_N_EXPERTS": 32, "DCT_EXPERTS_HELD": 8,
    "DCT_MOE_D_FF": 24, "DCT_MOE_SHARED_D_FF": 24, "DCT_BF16_COMPUTE": 0,
}
TINY_CFG = {
    **CFG, "hidden_size": 64, "intermediate_size": 96, "head_dim": 16,
    "num_key_value_heads": 2, "sliding_window": 32, "num_experts": 8,
    "moe_intermediate_size": 24, "shared_expert_intermediate_size": 24,
    "num_attention_heads_per_layer": [
        6 if h == 48 else 8 for h in CFG["num_attention_heads_per_layer"]],
    "rope_parameters": {
        **CFG["rope_parameters"],
        "full_attention": {**CFG["rope_parameters"]["full_attention"],
                           "original_max_position_embeddings": 64}},
    "program": {"env": {**CFG["program"]["env"], **TINY}},
}


def _faulty_moe(fault):
    """The reference's routed layer with one planted fault."""
    def moe(x, p, *, top_k, first, scaling, routing):
        logits = x @ p["router"]["kernel"]
        s = (jax.nn.softmax(logits, -1) if fault == "softmax"
             else jax.nn.sigmoid(logits))
        sel = s if fault == "bias_left_out" else s + p["expert_bias"]
        ranked, own = jax.lax.top_k(sel, top_k + 1)
        margin = ranked[:, top_k - 1] - ranked[:, top_k]
        topi = own[:, :top_k] if routing is None else routing
        if fault == "top7":
            topi = topi[:, :7]
        w = jnp.take_along_axis(s, topi, axis=-1)
        w = w / w.sum(-1, keepdims=True) * scaling
        out = REF._gated_mlp(x, p["shared_gate"]["kernel"],
                             p["shared_in"]["kernel"],
                             p["shared_out"]["kernel"])
        for j in range(p["experts_in_kernel"].shape[0]):
            wj = jnp.where(topi == first + j, w, 0.0).sum(-1, keepdims=True)
            out = out + wj * REF._gated_mlp(
                x, p["experts_gate_kernel"][j], p["experts_in_kernel"][j],
                p["experts_out_kernel"][j])
        return out, own[:, :top_k], margin
    return moe


#: Faults of the reference's routed layer, and of the configuration it is
#: given; the program is the shipped one throughout.
FAULTS = {
    "softmax": ({}, "softmax"),
    "bias_left_out": ({}, "bias_left_out"),
    "top7": ({}, "top7"),
    "routed_scale_left_out": ({"moe_routed_scaling_factor": 1.0}, None),
    "gate_left_out": ({"gating": False}, None),
    "full_rotation_on_full_layers": ({"rope_parameters": {
        **TINY_CFG["rope_parameters"],
        "full_attention": {**TINY_CFG["rope_parameters"]["full_attention"],
                           "partial_rotary_factor": 1}}}, None),
}


@pytest.fixture(scope="module")
def check(tmp_path_factory):
    """``fit_routed._reference_check`` on the tiny cell, as its driver
    calls it before ``fit``: the trainer's seeded parameters, the selection
    bias seeded, the first sequence of 128 positions; ``fault`` names a
    planted fault of the reference's routed layer."""
    work = str(tmp_path_factory.mktemp("laguna_check"))
    traffic = {"seq_len": 128, "batch_per_chip": 1, "steps_per_epoch": 3,
               "val_batches": 1}
    plan = Plan(TINY_CFG, traffic, 1)
    env = plan.env(work, _etl(work, plan.rows, 2147483999), work)
    env["DCT_SEED"] = 2147483999
    with env_overlay(env):
        from dct_tpu.config import RunConfig

        cfg = RunConfig.from_env()
    trainer = types.SimpleNamespace(mesh=None)

    def run(config=TINY_CFG, fault=None):
        def load_module(path, name):
            mod = LOAD_MODULE(path, f"{name}_{fault}")
            if fault:
                mod._moe = _faulty_moe(fault)
            return mod

        # The check loads the reference through the manifest's loader.
        mf.load_module = load_module
        try:
            return fit_routed._reference_check(cfg, trainer, plan, config)
        finally:
            mf.load_module = LOAD_MODULE
    return run


def test_the_program_passes_its_reference_check(check):
    got = check()
    assert got["ok"], got
    # float32 on the CPU: the logits are the reference's and the choice is
    # its own almost everywhere.
    assert got["logit_rel_err"] < 1e-4 and got["loss_rel_err"] < 1e-5
    assert got["routing_pairs"] == 4 * 128
    assert got["routing_disagree_share"] <= 0.01


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_planted_fault_fails_the_check(check, fault):
    changed, moe_fault = FAULTS[fault]
    got = check({**TINY_CFG, **changed}, moe_fault)
    assert not got["ok"], got

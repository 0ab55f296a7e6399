"""What PR 32 added for ``moonlight_16b_a3b_ep8``: the cell in the manifest,
the configuration against the catalog's widths, the operation counts against
a hand count of the 5-layer cut, the plain reference against the program at
a small size (logits, and one step of the balancing update), the three new
readers against a few hand-made events, and the routed driver's two-part
reference check on the CPU."""

import json
import os
import shutil
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.reduce import trace as tr

CELL = "moonlight_16b_ep8.fit_seq8192"
CFG = mf.load_json(f"{mf.BENCH_DIR}/configs/moonlight_16b_a3b_ep8.json")
REF = mf.load_module(
    f"{mf.BENCH_DIR}/reference/moonlight_moe.py", "bench_reference_moonlight")
flops = mf.load_flops(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_cell_is_in_the_manifest_with_its_metrics():
    manifest = mf.load_manifest()
    cell, config, traffic = mf.load_cell(manifest, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "moonlight_16b_a3b_ep8", "fit_mla_moe_seq8192")
    assert config == CFG and traffic["driver"] == "fit_routed"
    assert (traffic["seq_len"], traffic["batch_per_chip"],
            traffic["val_batches"]) == (8192, 1, 2)
    assert [m["name"] for m in mf.metrics_of(
        manifest, "end_to_end", CELL)] == ["fit_tokens_per_s", "setup_s"]
    layer = {m["name"] for m in mf.metrics_of(manifest, "per_layer", CELL)}
    assert {"mla.share", "mla.flash_roofline", "moe.shared_share",
            "moe.ffn_share", "moe.expert_roofline", "moe.load_max_over_mean",
            "moe.bound_over_routed", "step.mfu", "kernels.flash_share",
            "trainer.host_epoch_s", "checkpoint.section_s"} <= layer
    assert "shortconv.share" not in layer
    # New entries stand at the end of their lists.
    assert [m["name"] for m in manifest["per_layer"]][-3:] == [
        "mla.share", "mla.flash_roofline", "moe.shared_share"]
    assert manifest["workloads"][-1]["name"] == CELL
    for m in manifest["per_layer"][-3:]:
        assert m["workloads"] == [CELL]


def test_configuration_keeps_the_published_widths_and_states_the_cut():
    entry = next(c for c in mf.load_manifest()["configs"]
                 if c["name"] == CFG["name"])
    if os.path.exists(CATALOG):  # every number of the catalog's row
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Moonlight-16B-A3B")
        assert entry["source"] == CFG["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if CFG[k] != v}
        assert differs == set(entry["reduced"])
    assert (CFG["hidden_size"], CFG["intermediate_size"],
            CFG["moe_intermediate_size"]) == (2048, 11264, 1408)
    assert (CFG["kv_lora_rank"], CFG["qk_nope_head_dim"],
            CFG["qk_rope_head_dim"], CFG["v_head_dim"]) == (512, 128, 64, 128)
    assert (CFG["num_attention_heads"], CFG["num_experts_per_tok"],
            CFG["n_shared_experts"]) == (16, 6, 2)
    assert CFG["routed_scaling_factor"] == 2.446 and CFG["q_lora_rank"] is None
    assert CFG["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64}
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"]) == (5, 8)
    env = CFG["program"]["env"]
    assert env["DCT_LAYER_TYPES"].split(",") == ["latent_attention"] * 5
    assert (env["DCT_N_EXPERTS"], env["DCT_EXPERTS_HELD"],
            env["DCT_ROUTER_TOP_K"], env["DCT_MOE_D_FF"],
            env["DCT_MOE_SHARED_D_FF"]) == (64, 8, 6, 1408, 2 * 1408)
    assert (env["DCT_KV_LORA_RANK"], env["DCT_QK_NOPE_HEAD_DIM"],
            env["DCT_QK_ROPE_HEAD_DIM"], env["DCT_V_HEAD_DIM"]) == (
        512, 128, 64, 128)
    assert (env["DCT_ROUTED_SCALING"], env["DCT_ROPE_THETA"],
            env["DCT_NUM_DENSE_LAYERS"]) == (2.446, 50000, 1)
    assert env["DCT_BIAS_UPDATE_SPEED"] == CFG["bias_update_speed"] == 0.01


def test_operation_counts_of_the_five_layer_cut_by_hand():
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048  # 13,762,560
    dense = 3 * 2048 * 11264                                    # 69,206,016
    # 6 of 64 experts a token, 8 of them here: three quarters of an expert.
    moe = 2048 * 64 + 0.75 * 3 * 2048 * 1408 + 3 * 2048 * 2816
    ends = 5 * 2048 + 2048 * 2
    weights = ends + 5 * attn + dense + 4 * moe
    assert attn == 13_762_560 and weights == pytest.approx(233.7e6, rel=1e-3)
    assert flops.attention_weights(CFG) == attn
    assert flops.gemm_weights_per_token(CFG) == weights
    # One sequence, one layer, forward: 16 heads x T(T+1)/2 pairs x 2 x
    # (192 + 128) = 0.34 TFLOP; five layers with the backward 5.15.
    one = 16 * 8192 * 8193 / 2 * 2 * (192 + 128)
    assert one == pytest.approx(0.3436e12, rel=1e-3)
    assert flops.attention_train_flops(CFG, 8192) == pytest.approx(15 * one)
    step = flops.train_flops_per_token(CFG, 8192) * 8192
    assert step == pytest.approx(6 * weights * 8192 + 15 * one)
    assert step == pytest.approx(16.64e12, rel=1e-3)
    # 768 rows an expert and layer, 8 experts, 4 layers.
    assert flops.expert_train_flops(CFG, 24576) == pytest.approx(
        3 * 3 * 2 * 2048 * 1408 * 24576)


SMALL = {
    **CFG, "num_hidden_layers": 3, "num_attention_heads": 4,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "n_routed_experts": 2, "first_expert": 6}


def _small_model():
    from dct_tpu.config import ModelConfig
    from dct_tpu.models.registry import get_model

    cfg = ModelConfig(
        name="weather_hybrid_moe_causal", d_model=32, n_heads=4, n_layers=3,
        d_ff=96, seq_len=40, pos_embed="rope", rope_theta=5e4, dropout=0.0,
        norm="rmsnorm", norm_eps=1e-5, mlp="swiglu", use_bias=False,
        layer_types=",".join(["latent_attention"] * 3), num_dense_layers=1,
        n_experts=16, router_top_k=6, moe_d_ff=24, experts_held=2,
        first_expert=6, routed_scaling=2.446, router_gate_eps=1e-20,
        moe_shared_d_ff=48, bias_update_speed=0.001, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
    return get_model(cfg, input_dim=5, compute_dtype=jnp.float32)


def test_reference_matches_the_program_in_float32():
    model = _small_model()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 40, 5)).astype(np.float32)
    y = rng.integers(0, 2, (2, 40)).astype(np.int32)
    params = jax.device_get(
        jax.jit(model.init)(jax.random.PRNGKey(1), jnp.asarray(x[:1]))
    )["params"]
    for block in params.values():
        if "moe" in block:
            block["moe"]["expert_bias"] = (
                0.1 * rng.standard_normal(16)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got, sown = model.apply(
            {"params": params}, x, train=False,
            mutable=["intermediates", "param_steps"])
    want, loss = REF.forward_and_loss(params, x, y, SMALL)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=2e-5)
    assert np.isfinite(loss)
    # The share is part of the result: another first expert is visible.
    other, _ = REF.forward_and_loss(
        params, x, y, {**SMALL, "first_expert": 0})
    assert np.abs(other - want).max() > 1e-3
    chosen = np.stack([
        np.asarray(sown["intermediates"][f"block_{i}"]["moe"]["topk"][0])
        .reshape(2, 40, 6) for i in (1, 2)], axis=1)
    out = REF.forward(params, x, SMALL, routing=chosen)
    np.testing.assert_array_equal(
        np.sort(out["topk"], -1), np.sort(chosen, -1))
    np.testing.assert_allclose(out["logits"], want, rtol=0, atol=2e-5)
    # One balancing update: the step the program sows is the reference's.
    for i in (1, 2):
        bias = params[f"block_{i}"]["moe"]["expert_bias"]
        step = np.asarray(
            sown["param_steps"][f"block_{i}"]["moe"]["expert_bias"])
        assert set(np.unique(np.abs(step))) <= {0.0, np.float32(0.001)}
        np.testing.assert_allclose(
            bias + step, REF.bias_step(bias, chosen[:, i - 1], 0.001),
            rtol=0, atol=1e-7)
    with pytest.raises(ValueError, match="experts a layer"):
        REF.forward(params, x, {**SMALL, "n_routed_experts": 8})
    with pytest.raises(ValueError, match="compressed query"):
        REF.settings({**SMALL, "q_lora_rank": 1536})


HLO = '''
HloModule jit_epoch_fused
  %fusion.1 = bf16[8,8] fusion(%a), kind=kOutput, calls=%fc, metadata={op_name="jit(epoch_fused)/while/body/block_0/attn/mla.project/dot_general"}
  %mla.attend.2 = bf16[8,8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(epoch_fused)/while/body/block_1/attn/mla.attend/pallas_call"}
  %mla.attend.3 = bf16[8,8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(epoch_fused)/while/body/transpose(jvp(block_1))/attn/mla.attend/pallas_call"}
  %fusion.4 = bf16[8,8] fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(epoch_fused)/while/body/block_1/attn/mla.out/dot_general"}
  %fusion.5 = bf16[8,8] fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(epoch_fused)/while/body/block_1/moe/moe.shared/dot_general"}
  %ragged-dot-none.6 = bf16[8,8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.7 = bf16[8,8] fusion(%a), kind=kLoop, calls=%fc
'''


def _art(hlo=HLO, config=CFG):
    def op(name, body, start_ms, dur_ms):
        return (f"%{name} = bf16[8,8] {body}", start_ms * 1e6, dur_ms * 1e6)

    call = 'custom-call(%a), custom_call_target="tpu_custom_call"'
    events = []
    for step in range(4):  # four steps of 100 ms
        t = step * 100
        events += [
            op("fusion.1", "fusion(%a), kind=kOutput", t, 10),
            op("mla.attend.2", call, t + 10, 8),
            op("mla.attend.3", call, t + 18, 12),
            op("fusion.4", "fusion(%a), kind=kLoop", t + 30, 5),
            op("fusion.5", "fusion(%a), kind=kLoop", t + 35, 15),
            op("ragged-dot-none.6", call, t + 50, 10),
            op("fusion.7", "fusion(%a), kind=kLoop", t + 60, 40),
        ]
    trace = tr.Trace(
        [tr.Device(0, tr.Line(events), tr.Line([]))], [], 0, 4e8)
    return dict(
        trace=trace, hlo_text=hlo, config=config,
        window=types.SimpleNamespace(counters=[]),
        plan=types.SimpleNamespace(
            steps=10, seq_len=8192, batch_per_chip=1, val_batches=2),
        end_to_end={"epochs": 0}, device=dict(kind="TPU v5 lite"))


def test_the_three_readers_on_hand_made_events():
    art = _art()
    read = lambda name, a=art: mf.load_layer_metric(name).read(a)  # noqa: E731
    assert read("mla.share") == pytest.approx(100 * 35 / 100)
    assert read("moe.shared_share") == pytest.approx(100 * 15 / 100)
    # 20 ms a step in the two mla.attend.-named Mosaic calls; the grouped
    # product's call is not one of them.
    useful = flops.attention_train_flops(CFG, 8192)
    assert read("mla.flash_roofline") == pytest.approx(
        100 * useful / 0.020 / 197e12)
    # One epoch's two validation batches add two thirds of a step's
    # operations.
    with_eval = {**art, "end_to_end": {"epochs": 1}}
    assert read("mla.flash_roofline", with_eval) == pytest.approx(
        100 * useful * (4 + 2 / 3) / 0.080 / 197e12)
    # A program without the scopes or the kernels (the parent, or another
    # family's cell), a run without the text, no device plane: nothing.
    bare = _art(hlo=HLO.replace("mla.", "x.").replace("moe.shared", "y"))
    assert read("mla.share", bare) is None
    assert read("moe.shared_share", bare) is None
    assert read("mla.share", _art(hlo=None)) is None
    lfm2 = mf.load_json(f"{mf.BENCH_DIR}/configs/lfm2_24b_a2b_ep8.json")
    assert read("mla.flash_roofline", _art(config=lfm2)) is None
    assert read("mla.flash_roofline", {**art, "trace": None}) is None


# -- the routed driver, end to end at a tiny size on the CPU ---------------

CALL = """
import json, sys
sys.path.insert(0, {root!r} + "/benchmark"); sys.path.insert(0, {root!r})
import run
rc, out, notes = run.run_cell(run.parse(sys.argv[1:]), require_tpu=False)
print(json.dumps({{"rc": rc, "out": out, "notes": notes}}))
"""


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The new cell at a tiny size, added beside the accepted files the way
    this PR added the real one: a configuration file, a traffic file and
    entries appended to the manifest."""
    root = str(tmp_path_factory.mktemp("co_mla"))
    shutil.copytree(mf.BENCH_DIR, root + "/benchmark")
    os.symlink(mf.ROOT + "/dct_tpu", root + "/dct_tpu")
    manifest = mf.load_manifest()
    cfg = json.loads(json.dumps(CFG))
    cfg.update(
        name="tiny_mla", hidden_size=32, intermediate_size=96,
        moe_intermediate_size=24, num_attention_heads=4,
        num_key_value_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, n_routed_experts=4)
    cfg["published"]["n_routed_experts"] = 16
    cfg["program"]["env"].update(
        DCT_D_MODEL=32, DCT_N_HEADS=4, DCT_N_KV_HEADS=4, DCT_D_FF=96,
        DCT_MOE_D_FF=24, DCT_MOE_SHARED_D_FF=48, DCT_N_EXPERTS=16,
        DCT_EXPERTS_HELD=4, DCT_KV_LORA_RANK=16, DCT_QK_NOPE_HEAD_DIM=8,
        DCT_QK_ROPE_HEAD_DIM=4, DCT_V_HEAD_DIM=8, DCT_LR=0.001,
        DCT_BF16_COMPUTE=0)
    with open(root + "/benchmark/configs/tiny_mla.json", "w") as f:
        json.dump(cfg, f)
    with open(root + "/benchmark/traffic/tiny_mla_fit.json", "w") as f:
        json.dump({"driver": "fit_routed", "seq_len": 128,
                   "batch_per_chip": 1, "steps_per_epoch": 3,
                   "val_batches": 1,
                   "expect": {"attention_path": "dense",
                              "flash_interpret": None}}, f)
    manifest["configs"].append({
        "name": "tiny_mla", "source": "test",
        "reduced": list(cfg["published"]),
        "file": "benchmark/configs/tiny_mla.json", "why": "test"})
    manifest["workloads"].append({
        "name": "tiny.mla", "config": "tiny_mla",
        "traffic": "tiny_mla_fit", "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny.mla")
    with open(root + "/BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    return root


def _run(root, *argv):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": root + "/.jax_cache"}
    r = subprocess.run(
        [sys.executable, "-c", CALL.format(root=root), *argv],
        env=env, capture_output=True, text=True, cwd=root, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cell_traced_is_correct_counts_its_rows_and_moves_its_bias(checkout):
    got = _run(checkout, "--workload", "tiny.mla", "--seed", "2147483999",
               "--seconds", "2", "--trace", "1")
    out, ref = got["out"], got["notes"]["reference"]
    assert got["rc"] == 0 and out["correct"], got["notes"]
    # float32 on the CPU: the choice is the reference's own almost
    # everywhere, and the logits are the reference's.
    assert ref["routing_pairs"] == 4 * 128
    assert ref["routing_disagree_share"] <= 0.01
    assert ref["logit_rel_err"] < 1e-4 and ref["loss_rel_err"] < 1e-5
    # No device plane on the CPU: the trace readers return nothing; the
    # counter metrics and the HLO text beside the trace are there.
    assert {"moe.load_max_over_mean", "moe.bound_over_routed"} <= set(
        out["metrics"])
    assert not {"mla.share", "mla.flash_roofline", "moe.shared_share",
                "moe.ffn_share"} & set(out["metrics"])
    text = open(os.path.join(
        checkout, "build/benchmark/tiny.mla/trace/epoch_program.hlo.txt"
    )).read()
    for scope in ("mla.project", "mla.attend", "mla.out", "moe.shared",
                  "moe.experts", "dense_mlp"):
        assert scope in text, scope
    # The balancing update ran: the epochs' metrics carry the counter, and
    # the bias left zero by whole steps of the configuration's speed.
    events = [json.loads(line) for line in open(os.path.join(
        checkout, "build/benchmark/tiny.mla/events/events.jsonl"))]
    ends = [e for e in events if e.get("event") == "epoch_end"]
    peaks = [e["moe_bias_abs_max"] for e in ends]
    speed = CFG["bias_update_speed"]
    assert ends and all(0 <= p <= speed * 3 * len(ends) + 1e-9 for p in peaks)
    assert peaks[-1] >= speed - 1e-9

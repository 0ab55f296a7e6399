"""What PR 27 added for ``lfm2_24b_a2b_ep8``: the plain reference against the
program at a small size, the operation counts against a hand count of the
5-layer cut, the scope reducer and the four readers against a few hand-made
events, and the routed driver's two-part reference check on the CPU. Since
PR 33 the configuration runs the expert bias's balancing update: the cell
under its new name, the speed in the environment, one update against the
plain rule, and the held experts' load with and without it."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.reduce import scopes
from benchmark.reduce import trace as tr

CFG = mf.load_json(f"{mf.BENCH_DIR}/configs/lfm2_24b_a2b_ep8.json")
REF = mf.load_module(
    f"{mf.BENCH_DIR}/reference/lfm2_moe.py", "bench_reference_lfm2_test")
flops = mf.load_flops(CFG)
CELL = "lfm2_24b_ep8.fit_seq8192_balanced"
SPEED = 0.01
#: The balancing rule in plain numpy. The update is the program's one rule
#: for both routed configurations, and ``lfm2_moe.py`` has a byte-identical
#: copy under ``tests/`` that PR 33 could not touch: the rule is read from
#: the other reference.
RULE = mf.load_module(
    f"{mf.BENCH_DIR}/reference/moonlight_moe.py",
    "bench_reference_bias_rule_test").bias_step


def test_the_cell_runs_under_its_new_name_only():
    manifest = mf.load_manifest()
    cell, config, traffic = mf.load_cell(manifest, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "lfm2_24b_a2b_ep8", "fit_moe_balanced_seq8192")
    assert config == CFG and traffic["driver"] == "fit_routed"
    assert (traffic["seq_len"], traffic["batch_per_chip"],
            traffic["val_batches"]) == (8192, 1, 2)
    # The cell that ran without the update is retired: no entry names it,
    # so no line of the ledger compares the two.
    retired = "lfm2_24b_ep8.fit_seq8192"
    assert retired not in [c["name"] for c in manifest["workloads"]]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert retired not in m.get("workloads", ())
    layer = {m["name"] for m in mf.metrics_of(manifest, "per_layer", CELL)}
    assert {"moe.ffn_share", "moe.expert_roofline", "shortconv.share",
            "moe.load_max_over_mean", "moe.bound_over_routed", "step.mfu",
            "trainer.host_epoch_s", "checkpoint.section_s"} <= layer
    assert not {"mla.share", "mla.flash_roofline", "moe.shared_share"} & layer


def test_configuration_keeps_the_published_widths_and_states_the_cut():
    assert (CFG["hidden_size"], CFG["intermediate_size"],
            CFG["moe_intermediate_size"]) == (2048, 11776, 1536)
    assert (CFG["num_attention_heads"], CFG["num_key_value_heads"]) == (32, 8)
    assert CFG["num_experts_per_tok"] == 4 and CFG["conv_L_cache"] == 3
    assert CFG["rope_parameters"]["rope_theta"] == 1000000
    assert CFG["norm_eps"] == 1e-5 and len(CFG["layer_types"]) == 40
    assert CFG["published"] == {
        "num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64}
    assert (CFG["num_hidden_layers"], CFG["num_dense_layers"],
            CFG["num_experts"]) == (5, 1, 8)
    entry = next(c for c in mf.load_manifest()["configs"]
                 if c["name"] == CFG["name"])
    assert sorted(entry["reduced"]) == sorted(CFG["published"])
    env = CFG["program"]["env"]
    assert [CFG["layer_types"][i] for i in CFG["layers_run"]] == \
        env["DCT_LAYER_TYPES"].split(",")
    assert (env["DCT_N_EXPERTS"], env["DCT_EXPERTS_HELD"],
            env["DCT_ROUTER_TOP_K"]) == (64, 8, 4)
    # The published file carries the bias and no speed: the one chosen on
    # the chip is stated once and handed to the program as it is.
    assert CFG["use_expert_bias"] is True
    assert env["DCT_BIAS_UPDATE_SPEED"] == CFG["bias_update_speed"] == SPEED
    assert not any("update is not run" in d for d in CFG["departures"])
    assert any("bias_update_speed" in a for a in CFG["assumed"])


def test_operation_counts_of_the_five_layer_cut_by_hand():
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048          # 16,783,360
    attn = 2048 * (32 + 2 * 8) * 64 + 2048 * 2048        # 10,485,760
    dense = 3 * 2048 * 11776                             # 72,351,744
    # 4 of 64 experts a token, 8 of them here: half an expert a token.
    moe = 2048 * 64 + 0.5 * 3 * 2048 * 1536              # 4,849,664
    ends = 5 * 2048 + 2048 * 2
    weights = ends + (conv + dense) + (attn + moe) + 3 * (conv + moe)
    assert weights == 169_383_936
    assert flops.gemm_weights_per_token(CFG) == weights
    attention = 3 * 4 * (8193 / 2) * 2048  # one attention layer
    assert flops.train_flops_per_token(CFG, 8192) == pytest.approx(
        6 * weights + attention)
    assert flops.train_flops_per_token(CFG, 8192) * 8192 == pytest.approx(
        9.150e12, rel=1e-3)
    # 512 rows an expert and layer, 8 experts, 4 layers, three GEMMs, three
    # passes: 16,384 rows a step.
    assert flops.expert_train_flops(CFG, 16384) == pytest.approx(
        3 * 3 * 2 * 2048 * 1536 * 16384)


def _small_model(seq_len, *, held, first, speed):
    """The configuration's five layers at toy widths, 16 experts."""
    from dct_tpu.config import ModelConfig
    from dct_tpu.models.registry import get_model

    cfg = ModelConfig(
        name="weather_hybrid_moe_causal", d_model=32, n_heads=4,
        n_kv_heads=2, n_layers=5, d_ff=96, seq_len=seq_len, pos_embed="rope",
        rope_theta=1e6, dropout=0.0, norm="rmsnorm", norm_eps=1e-5,
        mlp="swiglu", use_bias=False, qk_norm=True,
        layer_types="conv,full_attention,conv,conv,conv",
        num_dense_layers=1, n_experts=16, router_top_k=4, moe_d_ff=24,
        experts_held=held, first_expert=first, bias_update_speed=speed)
    return get_model(cfg, input_dim=5, compute_dtype=jnp.float32)


def test_reference_matches_the_program_in_float32():
    model = _small_model(40, held=2, first=6, speed=SPEED)
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
    config = {
        **CFG, "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_experts": 2, "first_expert": 6}
    with jax.default_matmul_precision("highest"):
        got, sown = model.apply(
            {"params": params}, x, train=False,
            mutable=["intermediates", "param_steps"])
    want, loss = REF.forward_and_loss(params, x, y, config)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=2e-5)
    assert np.isfinite(loss)
    # The share is part of the result: another first expert is visible.
    other, _ = REF.forward_and_loss(
        params, x, y, {**config, "first_expert": 0})
    assert np.abs(other - want).max() > 1e-3
    # Teacher-forced with the system's choice, the reference still reports
    # its own, and the two agree in float32.
    chosen = np.stack([
        np.asarray(sown["intermediates"][f"block_{i}"]["moe"]["topk"][0])
        .reshape(2, 40, 4) for i in range(1, 5)], axis=1)
    out = REF.forward(params, x, config, routing=chosen)
    np.testing.assert_array_equal(
        np.sort(out["topk"], -1), np.sort(chosen, -1))
    np.testing.assert_allclose(out["logits"], want, rtol=0, atol=2e-5)
    # One balancing update: the step the program sows is the plain rule's,
    # over all 16 experts from the routing the reference itself chose.
    for i in range(1, 5):
        bias = params[f"block_{i}"]["moe"]["expert_bias"]
        step = np.asarray(
            sown["param_steps"][f"block_{i}"]["moe"]["expert_bias"])
        assert set(np.unique(np.abs(step))) <= {0.0, np.float32(SPEED)}
        assert np.abs(step).max() > 0
        np.testing.assert_allclose(
            bias + step, RULE(bias, out["topk"][:, i - 1], SPEED),
            rtol=0, atol=1e-7)
    with pytest.raises(ValueError, match="experts a layer"):
        REF.forward(params, x, {**config, "num_experts": 8})


def _held_share_after_training(speed, steps=50):
    """Rows the four MoE layers route to the held quarter of 16 experts,
    over the even share, after ``steps`` Adam steps at toy widths."""
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import make_train_step

    batch, seq = 4, 64
    model = _small_model(seq, held=4, first=0, speed=speed)
    state = create_train_state(
        model, input_dim=5, lr=3e-3, seed=3, example_shape=(1, seq, 5),
        grad_clip_norm=1.0)
    step = make_train_step(donate=False)
    rng = np.random.default_rng(0)
    for _ in range(steps):
        x = rng.standard_normal((batch, seq, 5)).astype(np.float32)
        y = (x[..., 0] + 0.5 * x[..., 1] > 0).astype(np.int32)
        state, _ = step(state, jnp.asarray(x), jnp.asarray(y), jnp.ones(batch))
    _, sown = jax.jit(lambda p, bx: model.apply(
        p, bx, train=False, mutable=["counters"]))(state.params, x)
    even = batch * seq * 4 * 4 / 16
    return np.array([
        float(np.sum(sown["counters"][f"block_{i}"]["moe"]["moe_rows"][0]))
        for i in range(1, 5)]) / even


def test_the_update_holds_the_held_experts_near_the_even_share():
    """What the cell's new name stands for, at toy widths: the 5-feature
    rows make the router drift, and without the update some layer's held
    experts end far from their even share (below it or above, by seed: on
    the chip below, PERF.md section 6); with it every layer stays near."""
    drifted = _held_share_after_training(0.0)
    held = _held_share_after_training(SPEED)
    assert np.abs(drifted - 1).max() > 0.3, drifted
    assert np.abs(held - 1).max() < 0.2, held


HLO = '''
HloModule jit_epoch_fused
%fused_computation.1 { ROOT %x = f32[] parameter(0) }
  %fusion.1 = bf16[8,8] fusion(%a), kind=kOutput, calls=%fc, metadata={op_name="jit(epoch_fused)/while/body/block_0/conv/shortconv/dot_general" source_file="x.py"}
  %fusion.2 = bf16[8,8] fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(epoch_fused)/while/body/transpose(jvp(block_1))/moe/moe.route/dot_general"}
  %ragged-dot-none.3 = bf16[8,8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.4 = bf16[8,8] fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(epoch_fused)/while/body/block_1/moe/moe._grouped/moe.combine/scatter-add"}
  ROOT %fusion.5 = bf16[8,8] fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(epoch_fused)/while/body/block_1/attn/dot_general"}
  %fusion.6 = bf16[8,8] fusion(%a), kind=kLoop, calls=%fc
'''


def _art(hlo=HLO, counters=None):
    def op(name, body, start_ms, dur_ms):
        return (f"%{name} = bf16[8,8] {body}", start_ms * 1e6, dur_ms * 1e6)

    events = []
    for step in range(4):  # four steps of 100 ms
        t = step * 100
        events += [
            op("fusion.1", "fusion(%a), kind=kOutput", t, 20),
            op("fusion.2", "fusion(%a), kind=kLoop", t + 20, 4),
            op("ragged-dot-none.3", 'custom-call(%a), '
               'custom_call_target="tpu_custom_call"', t + 24, 10),
            op("fusion.4", "fusion(%a), kind=kLoop", t + 34, 6),
            op("fusion.5", "fusion(%a), kind=kLoop", t + 40, 40),
            op("fusion.6", "fusion(%a), kind=kLoop", t + 80, 20),
        ]
    trace = tr.Trace(
        [tr.Device(0, tr.Line(events), tr.Line([]))], [], 0, 4e8)
    window = types.SimpleNamespace(counters=counters or [])
    return dict(
        trace=trace, hlo_text=hlo, window=window, config=CFG,
        plan=types.SimpleNamespace(steps=10),
        device=dict(kind="TPU v5 lite"))


def test_instruction_names_join_the_trace_to_the_scopes():
    names = scopes.instruction_scopes(HLO)
    assert set(names) == {
        "fusion.1", "fusion.2", "ragged-dot-none.3", "fusion.4", "fusion.5"}
    assert scopes.in_scope(names["fusion.2"], "moe.route")
    assert not scopes.in_scope(names["fusion.4"], "moe.experts")
    assert not scopes.in_scope("a/moe.routes/b", "moe.route")
    # XLA's rewrite of a grouped product drops the scope: joined by name.
    assert scopes.in_scope(names["ragged-dot-none.3"], "moe.experts")
    art = _art()
    assert scopes.scope_seconds(art, "moe.experts") == pytest.approx(0.040)
    assert scopes.scope_seconds(art, "shortconv") == pytest.approx(0.080)
    # A program without the scope, a run without the text, no device plane.
    assert scopes.scope_seconds(art, "dense_mlp") is None
    assert scopes.scope_seconds(_art(hlo=None), "shortconv") is None
    assert scopes.scope_share({"trace": None, "hlo_text": HLO}, "moe.") is None


def test_the_four_readers_on_hand_made_events():
    counters = [
        {"moe_rows": 1.0, "moe_rows_max_over_mean": 9.0},  # the warm-up
        {"moe_rows": 160000.0, "moe_rows_max_over_mean": 1.25},
        {"moe_rows": 168000.0, "moe_rows_max_over_mean": 1.75},
    ]
    art = _art(counters=counters)
    read = lambda name: mf.load_layer_metric(name).read(art)  # noqa: E731
    assert read("moe.ffn_share") == pytest.approx(100 * 20 / 100)
    assert read("shortconv.share") == pytest.approx(100 * 20 / 100)
    assert read("moe.load_max_over_mean") == pytest.approx(1.5)
    # 16,400 rows a step, 10 ms a step under moe.experts.
    want = 100 * (18 * 2048 * 1536 * 16400 / 0.010) / 197e12
    assert read("moe.expert_roofline") == pytest.approx(want)
    # The accepted cells' driver keeps no counters and saves no text.
    bare = {**_art(hlo=None), "window": types.SimpleNamespace()}
    for name in ("moe.ffn_share", "shortconv.share", "moe.expert_roofline",
                 "moe.load_max_over_mean"):
        assert mf.load_layer_metric(name).read(bare) is None


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
    """A throw-away cell of the routed driver, added as a later PR adds
    one: a configuration file beside its reference and operation counts, a
    traffic file, entries in the manifest. Nothing that is there is
    edited."""
    import json
    import os
    import shutil

    root = str(tmp_path_factory.mktemp("co_routed"))
    shutil.copytree(mf.BENCH_DIR, root + "/benchmark")
    os.symlink(mf.ROOT + "/dct_tpu", root + "/dct_tpu")
    manifest = mf.load_manifest()
    cfg = json.loads(json.dumps(CFG))
    cfg.update(
        name="tiny_routed", hidden_size=32, intermediate_size=96,
        moe_intermediate_size=24, num_attention_heads=4,
        num_key_value_heads=2, num_experts=4)
    cfg["published"]["num_experts"] = 16
    cfg["program"]["env"].update(
        DCT_D_MODEL=32, DCT_N_HEADS=4, DCT_N_KV_HEADS=2, DCT_D_FF=96,
        DCT_MOE_D_FF=24, DCT_N_EXPERTS=16, DCT_EXPERTS_HELD=4,
        DCT_LR=0.001, DCT_BF16_COMPUTE=0)
    with open(root + "/benchmark/configs/tiny_routed.json", "w") as f:
        json.dump(cfg, f)
    with open(root + "/benchmark/traffic/tiny_routed_fit.json", "w") as f:
        json.dump({"driver": "fit_routed", "seq_len": 128,
                   "batch_per_chip": 1, "steps_per_epoch": 3,
                   "val_batches": 1,
                   "expect": {"attention_path": "dense",
                              "flash_interpret": None}}, f)
    manifest["configs"].append({
        "name": "tiny_routed", "source": "test",
        "reduced": list(cfg["published"]),
        "file": "benchmark/configs/tiny_routed.json", "why": "test"})
    manifest["workloads"].append({
        "name": "tiny.routed", "config": "tiny_routed",
        "traffic": "tiny_routed_fit", "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        if m["name"].startswith(("moe.", "shortconv.")):
            m["workloads"].append("tiny.routed")
    with open(root + "/BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    return root


def _run(root, *argv):
    import json
    import os
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": root + "/.jax_cache"}
    r = subprocess.run(
        [sys.executable, "-c", CALL.format(root=root), *argv],
        env=env, capture_output=True, text=True, cwd=root, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_routed_cell_is_correct_and_counts_its_rows(checkout):
    got = _run(checkout, "--workload", "tiny.routed", "--seed", "2147483999",
               "--seconds", "2", "--trace", "0")
    out, ref = got["out"], got["notes"]["reference"]
    assert got["rc"] == 0 and out["correct"], got["notes"]
    assert set(out["metrics"]) == {"fit_tokens_per_s", "setup_s"}
    # float32 on the CPU: the choice is the reference's own almost
    # everywhere, and the logits are the reference's.
    assert ref["routing_pairs"] == 4 * 128
    assert ref["routing_disagree_share"] <= 0.01
    assert ref["logit_rel_err"] < 1e-4 and ref["loss_rel_err"] < 1e-5


def test_routed_cell_traced_reports_the_counter_metric(checkout):
    import os

    got = _run(checkout, "--workload", "tiny.routed", "--seed", "5",
               "--seconds", "2", "--trace", "1")
    out = got["out"]
    assert out["correct"], got["notes"]
    # No device plane on the CPU: the three trace readers return nothing;
    # the counter metric and the HLO text beside the trace are there.
    assert "moe.load_max_over_mean" in out["metrics"]
    assert not {"moe.ffn_share", "moe.expert_roofline",
                "shortconv.share"} & set(out["metrics"])
    assert 1.0 <= out["metrics"]["moe.load_max_over_mean"]["value"] < 4.0
    text = open(os.path.join(
        checkout, "build/benchmark/tiny.routed/trace/epoch_program.hlo.txt"
    )).read()
    assert "moe.experts" in text and "shortconv" in text
    # The balancing update ran inside the epoch program: the epochs' events
    # carry its counter, and the bias left zero by whole steps of the
    # configuration's speed.
    import json

    with open(os.path.join(
            checkout, "build/benchmark/tiny.routed/events/events.jsonl")) as f:
        ends = [e for e in map(json.loads, f)
                if e.get("event") == "epoch_end"]
    peaks = [e["moe_bias_abs_max"] for e in ends]
    assert ends and all(0 <= p <= SPEED * 3 * len(ends) + 1e-9 for p in peaks)
    assert peaks[-1] >= SPEED - 1e-9

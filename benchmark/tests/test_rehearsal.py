"""End to end at tiny size on the CPU, through a throw-away cell added the
way a later PR adds one: one configuration file with its reference and its
operation counts beside it, one traffic file, one reader file, one entry
for each in BENCHMARK.json, and the new cell's name on the per-layer lists
that name every cell. No file that is there is edited. The result says
``"platform": "cpu"``, carries no device metric, and is never printed by
``run.py`` itself (its ``main`` exits 2)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest as mf

READER = '''
"""Epochs counted inside the window: a program counter."""
LAYER = "trainer"
UNIT = "epochs"
SOURCE = "program_counter"
MOVES = "fit_tokens_per_s"


def read(art):
    return float(art["end_to_end"]["epochs"])
'''

OPS = '''
"""Operation counts of the throw-away family: a round number."""


def train_flops_per_token(config, seq_len):
    return 1e6 * seq_len
'''

MFU = """
import json, sys
sys.path.insert(0, {root!r})
from benchmark import manifest as mf
from benchmark.reduce import trace as tr
assert mf.BENCH_DIR.startswith({root!r})
_cell, config, traffic = mf.load_cell(mf.load_manifest(), "tiny.fit")
# Four runs of one op, 100 ms apart: a made-up step of 100 ms.
ops = tr.Line([("%fusion.1 = f32[] fusion()", i * 1e8, 5e7) for i in range(4)])
class plan: seq_len, global_batch, data_parallel = 128, 2, 1
art = dict(trace=tr.Trace([tr.Device(0, ops, tr.Line([]))], [], 0, 4e8),
           config=config, plan=plan, device=dict(kind="TPU v5 lite"))
print(json.dumps(mf.load_layer_metric("step.mfu").read(art)))
"""

CALL = """
import json, sys
sys.path.insert(0, {root!r} + "/benchmark"); sys.path.insert(0, {root!r})
import run
rc, out, notes = run.run_cell(run.parse(sys.argv[1:]), require_tpu=False)
print(json.dumps({{"rc": rc, "out": out, "notes": notes}}))
"""


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("co"))
    shutil.copytree(mf.BENCH_DIR, root + "/benchmark")
    os.symlink(mf.ROOT + "/dct_tpu", root + "/dct_tpu")
    manifest = mf.load_manifest()
    cfg = mf.load_json(mf.BENCH_DIR + "/configs/sc2_3b_block.json")
    cfg.update(
        name="tiny_block", hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_key_value_heads=1, num_hidden_layers=1,
        sliding_window=64, reference="tiny_ref", flops="tiny_ops")
    shutil.copy(mf.BENCH_DIR + "/reference/block.py",
                root + "/benchmark/reference/tiny_ref.py")
    with open(root + "/benchmark/flops/tiny_ops.py", "w") as f:
        f.write(OPS)
    cfg["program"]["env"].update(
        DCT_D_MODEL=32, DCT_N_HEADS=2, DCT_N_KV_HEADS=1, DCT_D_FF=64,
        DCT_N_LAYERS=1, DCT_ATTN_WINDOW=64, DCT_LR=0.001)
    with open(root + "/benchmark/configs/tiny_block.json", "w") as f:
        json.dump(cfg, f)
    with open(root + "/benchmark/traffic/tiny_fit.json", "w") as f:
        # The CPU has no Mosaic, and 128 positions are under the policy's
        # flash_min_len anyway: this traffic expects the dense path.
        json.dump({"driver": "fit", "seq_len": 128, "batch_per_chip": 2,
                   "steps_per_epoch": 3, "val_batches": 1,
                   "expect": {"attention_path": "dense",
                              "flash_interpret": None}}, f)
    with open(root + "/benchmark/traffic/tiny_dp4.json", "w") as f:
        json.dump({"driver": "fit", "seq_len": 128, "batch_per_chip": 2,
                   "steps_per_epoch": 3, "val_batches": 1,
                   "mesh": {"data": 4},
                   "expect": {"attention_path": "dense",
                              "flash_interpret": None}}, f)
    with open(root + "/benchmark/layer_metrics/tiny.epochs.py", "w") as f:
        f.write(READER)
    manifest["configs"].append({
        "name": "tiny_block", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_block.json", "why": "test"})
    # As any later fit cell does, it joins every list that names every cell.
    cells = [c["name"] for c in manifest["workloads"]]
    for m in manifest["per_layer"]:
        if m["workloads"] == cells:
            m["workloads"].append("tiny.fit")
    manifest["workloads"].append({
        "name": "tiny.fit", "config": "tiny_block", "traffic": "tiny_fit",
        "chips": 1, "why": "test"})
    manifest["workloads"].append({
        "name": "tiny.dp4", "config": "tiny_block", "traffic": "tiny_dp4",
        "chips": 4, "why": "test"})
    manifest["per_layer"].append({
        "name": "tiny.epochs", "unit": "epochs", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "fit_tokens_per_s", "workloads": ["tiny.fit"]})
    with open(root + "/BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    return root


def _run(root, *argv, devices=1):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": root + "/.jax_cache",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    r = subprocess.run(
        [sys.executable, "-c", CALL.format(root=root), *argv],
        env=env, capture_output=True, text=True, cwd=root, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_added_cell_lints_and_runs_untraced(checkout):
    got = _run(checkout, "--workload", "tiny.fit", "--seed", "3",
               "--seconds", "2", "--trace", "0")
    out = got["out"]
    assert got["rc"] == 0 and out["correct"], got["notes"]
    assert out["device"]["platform"] == "cpu"
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(out["metrics"]) == {"fit_tokens_per_s", "setup_s"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert got["notes"]["stopped"] == "window"
    work = checkout + "/build/benchmark/tiny.fit"
    assert os.path.isdir(work + "/events")
    assert not os.path.exists(work + "/models")  # gigabytes at full size


def test_added_cell_runs_traced_without_device_metrics(checkout):
    got = _run(checkout, "--workload", "tiny.fit", "--seed", "4",
               "--seconds", "2", "--trace", "1")
    out = got["out"]
    assert out["correct"], got["notes"]
    # The CPU has no device plane: every reader of it returned nothing and
    # was left out; the counters and spans of the program are there.
    assert "busy_s" not in out["device"] and "breakdown" not in out
    assert set(out["metrics"]) == {
        "trainer.goodput_share", "checkpoint.stall_share", "tiny.epochs",
        "trainer.host_epoch_s", "trainer.join_wait_share",
        "checkpoint.section_s", "checkpoint.disk_share", "trainer.startup_s",
        "trainer.first_dispatch_s"}
    assert out["metrics"]["tiny.epochs"]["value"] == out["attempted"]


def test_a_data_parallel_cell_runs_on_four_devices_and_only_there(checkout):
    argv = ("--workload", "tiny.dp4", "--seed", "5", "--seconds", "2",
            "--trace", "0")
    got = _run(checkout, *argv, devices=4)
    assert got["out"]["correct"], got["notes"]
    assert got["out"]["device"]["count"] == 4
    assert got["notes"]["mesh"]["data"] == 4
    r = subprocess.run(
        [sys.executable, "-c", CALL.format(root=checkout), *argv],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, cwd=checkout, timeout=300)
    assert r.returncode != 0 and "found 1 devices" in r.stderr


def test_step_mfu_takes_the_added_family_s_operation_counts(checkout):
    r = subprocess.run(
        [sys.executable, "-c", MFU.format(root=checkout)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, cwd=checkout, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    # 2 x 128 tokens in 0.1 s at 1e6 x 128 operations a token, over 197e12.
    assert json.loads(r.stdout) == pytest.approx(
        100 * (2 * 128 / 0.1) * (1e6 * 128) / 197e12)


def test_the_command_itself_refuses_the_cpu(checkout):
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny.fit",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, cwd=checkout, timeout=300)
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    assert "nothing was run" in r.stderr


def test_no_result_without_the_program(tmp_path):
    shutil.copytree(mf.BENCH_DIR, tmp_path / "benchmark")
    shutil.copy(mf.MANIFEST, tmp_path / "BENCHMARK.json")
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "sc2_3b.fit_seq512", "--seed", "1", "--seconds", "1"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, cwd=tmp_path, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""

"""The program's host timeline end to end at tiny size on the CPU, the way
``test_rehearsal.py`` runs a throw-away cell: the CPU profiler records the
recorder's ``TraceAnnotation``s, so a traced run of a tiny fit cell carries
every reader of ``reduce/host.py`` but the one that needs a device plane.
The result says ``"platform": "cpu"``; its numbers are checked for shape
and against each other, never as times of anything."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest as mf

HOST_METRICS = (
    "trainer.host_epoch_s", "trainer.join_wait_share",
    "checkpoint.section_s", "checkpoint.disk_share",
    "device.idle_unexplained_share", "trainer.startup_s",
    "trainer.first_dispatch_s",
)

CALL = """
import json, sys
sys.path.insert(0, {root!r} + "/benchmark"); sys.path.insert(0, {root!r})
import run
from benchmark.reduce import host as hr, trace as tr
rc, out, notes = run.run_cell(run.parse(sys.argv[1:]), require_tpu=False)
host = hr.load(tr.find_xplane({root!r} + "/build/benchmark/tiny.host/trace"))
trainer = host.trainer
print(json.dumps({{
    "rc": rc, "out": out, "notes": notes, "window_s": host.window_s,
    "epochs": len(host.epochs()),
    "top": sorted({{s.name for s in trainer.top()}}),
    "under": {{p: sorted({{
        c.name for c in trainer.spans if c.depth == d + 1
        and any(q.name == p and q.depth == d and q.start <= c.start
                and c.end <= q.end for q in trainer.spans)}})
        for p, d in (("trainer.checkpoint", 0),
                     ("checkpoint.deploy_write", 1))}},
    "other_threads": sorted({{
        s.name for t in host.threads if t is not trainer for s in t.spans}}),
    "file_write_bytes": sorted({{
        s.stats["bytes"] for s in trainer.named("checkpoint.file_write")}}),
}}))
"""


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("co_host"))
    shutil.copytree(mf.BENCH_DIR, root + "/benchmark")
    os.symlink(mf.ROOT + "/dct_tpu", root + "/dct_tpu")
    manifest = mf.load_manifest()
    cfg = mf.load_json(mf.BENCH_DIR + "/configs/sc2_3b_block.json")
    cfg.update(
        name="tiny_host_block", hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_key_value_heads=1, num_hidden_layers=1,
        sliding_window=64)
    cfg["program"]["env"].update(
        DCT_D_MODEL=32, DCT_N_HEADS=2, DCT_N_KV_HEADS=1, DCT_D_FF=64,
        DCT_N_LAYERS=1, DCT_ATTN_WINDOW=64, DCT_LR=0.001)
    with open(root + "/benchmark/configs/tiny_host_block.json", "w") as f:
        json.dump(cfg, f)
    with open(root + "/benchmark/traffic/tiny_host.json", "w") as f:
        json.dump({"driver": "fit", "seq_len": 128, "batch_per_chip": 2,
                   "steps_per_epoch": 3, "val_batches": 1,
                   "expect": {"attention_path": "dense",
                              "flash_interpret": None}}, f)
    manifest["configs"].append({
        "name": "tiny_host_block", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_host_block.json", "why": "test"})
    manifest["workloads"].append({
        "name": "tiny.host", "config": "tiny_host_block",
        "traffic": "tiny_host", "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        if m["name"] in HOST_METRICS:
            m["workloads"].append("tiny.host")
    with open(root + "/BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": root + "/.jax_cache",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    r = subprocess.run(
        [sys.executable, "-c", CALL.format(root=root), "--workload",
         "tiny.host", "--seed", "2147483659", "--seconds", "2", "--trace",
         "1"],
        env=env, capture_output=True, text=True, cwd=root, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_the_seven_entries_name_their_readers_and_lint():
    manifest = mf.load_manifest()
    assert mf.lint(manifest) == []
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = [c["name"] for c in manifest["workloads"]]
    for name in HOST_METRICS:
        mod, entry = mf.load_layer_metric(name), entries[name]
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"])
        # Where the seven stand in the list is not pinned: later PRs
        # append their entries after them.
        assert entry["workloads"] == cells


def test_a_traced_cpu_run_reports_every_reader_that_needs_no_device(traced):
    out = traced["out"]
    assert traced["rc"] == 0 and out["correct"], traced["notes"]
    assert out["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in out["metrics"].items()}
    # No device plane on the CPU: that one reader returns nothing.
    assert set(got) == (set(HOST_METRICS) - {"device.idle_unexplained_share"}
                        ) | {"trainer.goodput_share", "checkpoint.stall_share"}
    assert all(v >= 0 for v in got.values())
    assert 0 < got["trainer.join_wait_share"] < 100
    assert 0 < got["checkpoint.disk_share"] < 100
    assert got["checkpoint.section_s"] <= got["trainer.host_epoch_s"]
    assert got["trainer.startup_s"] > 0 and got["trainer.first_dispatch_s"] > 0
    # The same interval on two clocks: every whole section of the window
    # over the window, against the reader on time.time().
    assert traced["epochs"] == out["attempted"] >= 2


def test_the_trainer_s_thread_carries_the_table_of_perf_md(traced):
    # bookkeep holds both ends of the session (the harness starts and stops
    # it inside the tracker call), so the whole ones are the middle epochs'.
    assert traced["top"] == [
        "trainer.bookkeep", "trainer.checkpoint", "trainer.data_wait",
        "trainer.dispatch_call", "trainer.join"]
    assert traced["under"]["trainer.checkpoint"] == [
        "checkpoint.deploy_write", "checkpoint.resume_snapshot",
        "checkpoint.resume_wait_prev", "trainer.gather_params"]
    assert traced["under"]["checkpoint.deploy_write"] == [
        "checkpoint.file_write", "checkpoint.lineage_hash",
        "checkpoint.serialize"]
    assert {"checkpoint.resume_save", "data.assemble"} <= set(
        traced["other_threads"])
    assert len(traced["file_write_bytes"]) == 1
    assert traced["file_write_bytes"][0] > 10_000

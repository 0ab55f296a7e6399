"""End-to-end smoke of ``bench.main()`` — the exact artifact the driver
runs at end of round. The unit tests in test_val_parity.py /
test_bench_record.py pin the pieces; this pins the WIRING: the one JSON
line must land with every section's digest and the val-parity numbers
present on a CPU run."""

import importlib
import json

import pytest


@pytest.mark.slow
def test_bench_main_cpu_record_carries_everything(
    tmp_path, monkeypatch, capsys
):
    monkeypatch.setenv("DCT_BENCH_ROWS", "2000")
    monkeypatch.setenv("DCT_BENCH_EPOCHS", "1")
    monkeypatch.setenv("DCT_BENCH_TORCH_EPOCHS", "1")
    monkeypatch.setenv("DCT_VAL_PARITY_EPOCHS", "1")
    monkeypatch.setenv("DCT_BENCH_SCALED", "0")
    # The restart_spinup leg spawns two supervised subprocess worlds
    # (~a minute); the smoke gates the WIRING, and the null marker
    # below proves skipped-not-absent. scripts/compile_cache_smoke.py
    # (the compile-cache CI job) runs the leg's machinery for real.
    monkeypatch.setenv("DCT_BENCH_SPINUP", "0")
    # Likewise cycle_freshness: the serial-vs-loop comparison runs two
    # full continuous-training rigs (~40 s); tests/test_continuous.py
    # exercises the loop machinery for real, the smoke pins the null
    # marker wiring.
    monkeypatch.setenv("DCT_BENCH_FRESHNESS", "0")
    # Likewise multi_tenant: the 2-tenant scheduler session runs in
    # tests/test_scheduler.py and the scheduler CI smoke; the bench
    # smoke pins the null-marker wiring.
    monkeypatch.setenv("DCT_BENCH_TENANTS", "0")
    # And mpmd_pipeline: the MPMD machinery runs for real in
    # tests/test_mpmd.py and the mpmd-pipeline CI smoke; the bench
    # smoke pins the null-marker wiring.
    monkeypatch.setenv("DCT_BENCH_MPMD", "0")
    # And elastic_serving: the overload A/B replay runs for real in
    # tests/test_serving_elastic.py and the elastic-serving CI smoke;
    # the bench smoke pins the null-marker wiring.
    monkeypatch.setenv("DCT_BENCH_ELASTIC", "0")
    monkeypatch.setenv(
        "DCT_BENCH_PARTIAL", str(tmp_path / "BENCH_PARTIAL.json")
    )
    import bench

    bench = importlib.reload(bench)
    try:
        bench.main()
    finally:
        out = capsys.readouterr().out
        monkeypatch.undo()
        importlib.reload(bench)

    record = json.loads(out.strip().splitlines()[-1])
    # The driver's contract: ONE JSON line, headline fields present, and
    # short enough to survive the driver's 2,000-byte stdout tail.
    line = out.strip().splitlines()[-1]
    assert len(line.encode()) <= 1800, len(line.encode())
    assert record["metric"] == "weather_parity_train_samples_per_sec_per_chip"
    assert record["platform"] == "cpu"
    assert record["value"] > 0
    # No chip, no headline MFU: nothing computed on the CPU stands in.
    assert record["mfu"] is None and "mfu_source" not in record
    assert "generated_utc" in record
    # Dispatch-gap tracker: the ratio rides every record. fused/fit
    # duplicate the top-level value / trainer_loop keys byte for byte,
    # so stdout carries the ratio + mode knob only (the partial keeps
    # the full stanza — asserted below).
    gap = record["trainer_gap"]
    assert gap["fused_over_fit"] > 0
    assert gap["prefetch_spans"] == 1
    assert "fused" not in gap
    # Serving under traffic (ISSUE 7): qps + tails at >= 2 concurrency
    # levels as the columnar stdout digest, knee + both throughput
    # ratios, and the live bit-identity parity check.
    sl = record["serving_load"]
    assert len(sl["levels"]["concurrency"]) >= 2
    assert all(q > 0 for q in sl["levels"]["qps"])
    assert all(p > 0 for p in sl["levels"]["p99_ms"])
    assert sl["knee_concurrency"] in sl["levels"]["concurrency"]
    # baseline_qps is derivable (saturated / batched_over_single) and
    # yielded to fund the elastic_serving series; the partial keeps it
    # verbatim (asserted below).
    assert sl["saturated_qps"] > 0 and "baseline_qps" not in sl
    assert sl["batched_over_single"] > 0
    assert sl["score_batched_over_single"] > 1
    assert sl["parity"] is True
    # Metrics-plane cost bound (ISSUE 8): the snapshot-publish p50
    # overhead is measured every round; the flat scalar rides stdout,
    # the per-variant p50 pair stays in the partial.
    assert isinstance(sl["publish_overhead_ms"], float)
    assert "snapshot_publish" not in sl
    # North-star val parity: both numbers in the driver record; the
    # protocol prose is trimmed to its BASELINE.md pointer on stdout.
    vp = record["val_parity"]
    assert vp["torch_val_loss"] > 0 and vp["jax_val_loss"] > 0
    assert vp["protocol"] == "BASELINE.md row 1"
    # The partial on disk is the VERBATIM record (the crash hedge),
    # matching stdout's digest.
    # Skipped-not-absent: the gated restart_spinup / cycle_freshness
    # legs leave their null markers (DCT_BENCH_SPINUP=0 /
    # DCT_BENCH_FRESHNESS=0 above), like every skippable section.
    assert record["restart_spinup"] is None
    assert record["cycle_freshness"] is None
    assert record["multi_tenant"] is None
    assert record["mpmd_pipeline"] is None
    assert record["elastic_serving"] is None
    with open(tmp_path / "BENCH_PARTIAL.json") as f:
        partial = json.load(f)
    assert partial["trainer_gap"]["fused"] == partial["value"]
    assert partial["trainer_gap"]["fit"] > 0
    assert isinstance(partial["serving_load"]["levels"], list)
    assert partial["serving_load"]["baseline_qps"] > 0
    assert partial["serving_load"]["snapshot_publish"]["plain_p50_ms"] > 0
    assert partial["serving_load"]["snapshot_publish"]["publish_p50_ms"] > 0
    assert "train_lightning_ddp" in partial["val_parity"]["protocol"]
    import bench as bench_now

    assert json.loads(json.dumps(
        bench_now._stdout_record(partial), default=bench_now._json_default
    )) == record

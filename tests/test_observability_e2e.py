"""ISSUE 1 acceptance rig: a real CPU-smoke training run
(``jobs/train_tpu.py`` under the LocalProcessLauncher) must produce an
``events.jsonl`` where EVERY record — launcher, trainer, checkpoint,
tracking — carries the launcher-minted run-correlation ID, plus a final
goodput summary whose category seconds sum to within 5% of total wall
time; and a running serving server must answer ``GET /metrics`` with
valid Prometheus text exposition including slot and request-latency
series."""

import json
import os
import sys
import threading
import urllib.request

import pytest

from dct_tpu.launch.launcher import LocalProcessLauncher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke_run(processed_dir, tmp_path_factory):
    """One launched 2-epoch CPU training run, shared by the assertions."""
    tmp = tmp_path_factory.mktemp("obs_e2e")
    events_dir = tmp / "events"
    hb_dir = tmp / "heartbeats"
    env = {
        # Neutralize the ambient TPU plugin and any minted run id of the
        # pytest process itself (the launcher must be the minter here).
        "JAX_PLATFORMS": "cpu",
        "DCT_RUN_ID": "",
        "DCT_PROCESSED_DIR": processed_dir,
        "DCT_MODELS_DIR": str(tmp / "models"),
        "DCT_TRACKING_DIR": str(tmp / "runs"),
        "DCT_EVENTS_DIR": str(events_dir),
        "DCT_HEARTBEAT_DIR": str(hb_dir),
        "DCT_EPOCHS": "2",
        "DCT_BATCH_SIZE": "8",
        "DCT_BF16_COMPUTE": "0",
    }
    launcher = LocalProcessLauncher(
        stagger_seconds=0.0, timeout=300.0, heartbeat_dir=str(hb_dir)
    )
    results = launcher.launch(
        [sys.executable, os.path.join(REPO, "jobs", "train_tpu.py")],
        world_size=1,
        env=env,
    )
    assert LocalProcessLauncher.all_succeeded(results), results
    recs = [
        json.loads(line)
        for line in (events_dir / "events.jsonl").read_text().splitlines()
    ]
    return {"tmp": tmp, "events_dir": events_dir, "hb_dir": hb_dir,
            "recs": recs}


def test_every_record_carries_the_launcher_run_id(smoke_run):
    recs = smoke_run["recs"]
    assert len(recs) >= 8
    run_ids = {r["run_id"] for r in recs}
    assert len(run_ids) == 1, run_ids
    rid = run_ids.pop()
    assert rid.startswith("dct-")
    # Orchestrator records are rank-null; rank records carry rank 0.
    launcher_recs = [r for r in recs if r["component"] == "launcher"]
    assert launcher_recs and all(r["rank"] is None for r in launcher_recs)
    # Every layer of the cycle is present in ONE file: the one-grep
    # reconstruction the event log exists for.
    components = {r["component"] for r in recs}
    assert {"launcher", "trainer", "checkpoint", "tracking"} <= components
    events = {(r["component"], r["event"]) for r in recs}
    for must in (
        ("launcher", "launch_start"),
        ("launcher", "launch_end"),
        ("trainer", "fit_start"),
        ("trainer", "epoch_end"),
        ("trainer", "goodput_summary"),
        ("trainer", "fit_end"),
        ("checkpoint", "resume_state_saved"),
        ("tracking", "run_start"),
        ("tracking", "run_end"),
    ):
        assert must in events, must


def test_goodput_summary_accounts_for_wall_time(smoke_run):
    [summary] = [
        r for r in smoke_run["recs"] if r["event"] == "goodput_summary"
    ]
    wall = summary["wall_seconds"]
    accounted = sum(summary["categories"].values())
    assert wall > 0
    # The acceptance bound: categories sum to within 5% of wall time.
    assert accounted >= 0.95 * wall, summary
    assert accounted <= wall * 1.01 + 0.05, summary
    assert summary["epochs"] == 2
    # A 2-epoch scan run: epoch 0's dispatch is the compile, epoch 1's
    # is a train_step — both categories must have real time in them.
    assert summary["categories"]["compile"] > 0
    assert summary["categories"]["train_step"] > 0
    assert summary["categories"]["startup_recovery"] > 0
    assert 0 < summary["goodput_fraction"] < 1


def test_goodput_logged_to_tracker_next_to_val_loss(smoke_run):
    import glob

    [metrics_path] = glob.glob(
        str(smoke_run["tmp"] / "runs" / "weather_forecasting" / "*" /
            "metrics.jsonl")
    )
    final = {}
    for line in open(metrics_path):
        final.update(json.loads(line))
    # The deploy-DAG query surface now answers goodput questions the
    # same way it answers accuracy ones.
    assert "val_loss" in final
    assert 0 < final["goodput_fraction"] < 1
    assert final["goodput_train_step_seconds"] > 0
    assert final["badput_compile_seconds"] > 0
    # And the tracking meta is stamped with the correlation id.
    meta = json.load(open(os.path.join(
        os.path.dirname(metrics_path), "meta.json"
    )))
    assert meta["run_correlation_id"] == smoke_run["recs"][0]["run_id"]


def test_rank_heartbeat_reaches_done(smoke_run):
    hb = json.load(open(smoke_run["hb_dir"] / "rank_00000.json"))
    assert hb["phase"] == "done"
    assert hb["rank"] == 0
    assert hb["run_id"] == smoke_run["recs"][0]["run_id"]


def test_train_metrics_prom_dump_written(smoke_run):
    from tests.test_observability import _parse_exposition

    text = (smoke_run["events_dir"] / "train_metrics.prom").read_text()
    samples = _parse_exposition(text)  # validates every line's grammar
    frac = [v for k, v in samples.items()
            if k.startswith("dct_train_goodput_fraction")]
    assert frac and 0 < frac[0] < 1
    assert any('category="compile"' in k for k in samples)


@pytest.fixture(scope="module")
def served(smoke_run):
    """Serve the checkpoint the smoke run just produced."""
    import glob

    from dct_tpu.serving.server import make_server

    [ckpt] = glob.glob(str(smoke_run["tmp"] / "models" / "weather-best-*.ckpt"))
    server = make_server(ckpt)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{server.server_address[1]}"
    server.shutdown()
    server.server_close()


def test_metrics_endpoint_is_valid_exposition(served):
    from tests.test_observability import _parse_exposition

    # Drive a couple of scores so the series are non-trivial.
    for _ in range(3):
        req = urllib.request.Request(
            served + "/score",
            data=json.dumps({"data": [[0.1, -0.2, 0.3, 0.0, 1.0]]}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
    with urllib.request.urlopen(served + "/metrics") as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        assert "version=0.0.4" in r.headers["Content-Type"]
        text = r.read().decode()
    samples = _parse_exposition(text)  # every line must parse
    assert samples['dct_requests_total{slot="default"}'] == 3
    assert samples['dct_request_errors_total{slot="default"}'] == 0
    assert samples[
        'dct_request_latency_seconds_bucket{slot="default",le="+Inf"}'
    ] == 3
    assert samples['dct_request_latency_seconds_count{slot="default"}'] == 3
    assert samples['dct_request_latency_seconds_sum{slot="default"}'] > 0
    assert "# TYPE dct_request_latency_seconds histogram" in text

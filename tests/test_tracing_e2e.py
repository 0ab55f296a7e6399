"""ISSUE 2 acceptance rig: a real launched 2-process CPU training run
(jobs/train_tpu.py under the LocalProcessLauncher, one device per
process — the same recipe as tests/test_multihost_tp.py) must yield a
``python -m dct_tpu.observability.inspect <run_dir>`` cycle report
naming BOTH ranks and a ``trace.json`` that is valid Chrome-trace-event
JSON containing spans from the launcher, the trainer's epochs, and the
checkpoint saves, all sharing one trace_id; and a forced-NaN training
run must emit a ``health.nan_loss`` event and, with ``halt_on_nan``,
stop before completing the epoch."""

import json
import os
import sys

import numpy as np
import pytest

from dct_tpu.launch.launcher import LocalProcessLauncher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def traced_run(processed_dir, tmp_path_factory):
    """One launched 2-process, 2-epoch CPU run, shared by the
    assertions."""
    tmp = tmp_path_factory.mktemp("trace_e2e")
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "DCT_RUN_ID": "",
        "DCT_SPAN_ID": "",
        "DCT_PROCESSED_DIR": processed_dir,
        "DCT_MODELS_DIR": str(tmp / "models"),
        "DCT_TRACKING_DIR": str(tmp / "runs"),
        "DCT_EVENTS_DIR": str(tmp / "events"),
        "DCT_HEARTBEAT_DIR": str(tmp / "heartbeats"),
        "DCT_EPOCHS": "2",
        "DCT_BATCH_SIZE": "8",
        "DCT_BF16_COMPUTE": "0",
        "DCT_RESUME": "0",
    }
    launcher = LocalProcessLauncher(
        coordinator_port=29541, stagger_seconds=1.0, timeout=300.0,
        heartbeat_dir=str(tmp / "heartbeats"),
    )
    results = launcher.launch(
        [sys.executable, os.path.join(REPO, "jobs", "train_tpu.py")],
        world_size=2,
        env=env,
    )
    assert LocalProcessLauncher.all_succeeded(results), results
    return tmp


@pytest.fixture(scope="module")
def inspected(traced_run):
    """Run the inspect CLI (in-process main) over the run dir once."""
    import contextlib
    import io

    from dct_tpu.observability.inspect import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main([str(traced_run)])
    assert rc == 0
    return {"out": buf.getvalue(), "tmp": traced_run}


def test_cycle_report_names_both_ranks(inspected):
    out = inspected["out"]
    assert "rank 0" in out
    assert "rank 1" in out
    # The report joins all four surfaces.
    assert "Goodput:" in out and "goodput_fraction" in out
    assert "launch_end" in out
    assert "Perfetto trace written" in out


def test_trace_json_is_valid_chrome_trace_with_one_trace_id(inspected):
    trace_path = inspected["tmp"] / "trace.json"
    assert trace_path.exists()
    # Strict JSON (json.load enforces the grammar; no NaN tokens).
    trace = json.loads(trace_path.read_text())
    events = trace["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    assert complete
    for e in complete:
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid", "args"}
        assert e["dur"] >= 0
    # Spans from launcher, trainer epochs, and checkpoint saves.
    names = {e["name"] for e in complete}
    assert "launcher.launch" in names
    assert "trainer.epoch" in names
    assert "trainer.fit" in names
    assert any(n.startswith("checkpoint.") for n in names)
    # All sharing ONE trace_id — the launcher-minted run-correlation ID.
    trace_ids = {e["args"]["trace_id"] for e in complete}
    assert len(trace_ids) == 1, trace_ids
    assert trace_ids.pop().startswith("dct-")
    # Both ranks produced spans (pid = rank for rank processes).
    assert {0, 1} <= {e["pid"] for e in complete}


def test_cross_process_span_parenting(traced_run):
    """Each rank's trainer.fit span is a CHILD of the launcher's launch
    span — the DCT_SPAN_ID env contract, across real processes."""
    from dct_tpu.observability.trace_export import read_spans

    spans = read_spans(str(traced_run))
    launches = [s for s in spans if s["name"] == "launcher.launch"]
    assert len(launches) == 1
    fits = [s for s in spans if s["name"] == "trainer.fit"]
    assert {s["rank"] for s in fits} == {0, 1}
    for s in fits:
        assert s["parent_id"] == launches[0]["span_id"]
    # The launcher also recorded one reaped span per rank.
    rank_spans = [s for s in spans if s["name"] == "launcher.rank"]
    assert len(rank_spans) == 2
    assert all(
        s["parent_id"] == launches[0]["span_id"] for s in rank_spans
    )
    # Epoch spans nest under their rank's fit span.
    fit_by_rank = {s["rank"]: s["span_id"] for s in fits}
    epochs = [s for s in spans if s["name"] == "trainer.epoch"]
    assert epochs
    for s in epochs:
        assert s["parent_id"] == fit_by_rank[s["rank"]]


# -- forced-NaN health runs (in-process: the detector is host-side) ----


def _nan_run(tmp_path, *, halt: bool, use_scan: bool, subdir: str):
    from dct_tpu.config import RunConfig
    from dct_tpu.data.dataset import WeatherArrays
    from dct_tpu.train.trainer import Trainer

    cfg = RunConfig()
    cfg.train.epochs = 2
    cfg.train.batch_size = 2
    cfg.train.bf16_compute = False
    cfg.train.use_scan = use_scan
    cfg.data.models_dir = str(tmp_path / subdir / "models")
    cfg.tracking.tracking_uri = None
    cfg.obs.events_dir = str(tmp_path / subdir / "events")
    cfg.obs.heartbeat_dir = str(tmp_path / subdir / "hb")
    cfg.obs.run_id = f"dct-nan-{subdir}"
    cfg.obs.halt_on_nan = halt
    rng = np.random.default_rng(0)
    n = 128
    feats = rng.standard_normal((n, 5)).astype(np.float32)
    feats[3, 1] = np.nan  # one poisoned row -> NaN loss from epoch 0
    data = WeatherArrays(
        features=feats,
        labels=(rng.random(n) > 0.5).astype(np.int32),
        feature_names=[f"f{i}" for i in range(5)],
    )
    os.environ["DCT_TRACKING_DIR"] = str(tmp_path / subdir / "runs")
    trainer = Trainer(cfg)
    result = None
    try:
        result = trainer.fit(data)
    finally:
        os.environ.pop("DCT_TRACKING_DIR", None)
    return result, [
        json.loads(line)
        for line in open(
            os.path.join(cfg.obs.events_dir, "events.jsonl")
        ).read().splitlines()
    ]


def test_forced_nan_halt_stops_before_completing_the_epoch(tmp_path):
    from dct_tpu.observability.health import TrainingHealthError

    with pytest.raises(TrainingHealthError, match="nan_loss"):
        _nan_run(tmp_path, halt=True, use_scan=True, subdir="halt")
    recs = [
        json.loads(line)
        for line in open(
            tmp_path / "halt" / "events" / "events.jsonl"
        ).read().splitlines()
    ]
    events = [(r["component"], r["event"]) for r in recs]
    assert ("health", "health.nan_loss") in events
    # Stopped BEFORE completing the epoch: no epoch_end bookkeeping, no
    # checkpoint of the diverged state — and the failure is named.
    assert not any(e == "epoch_end" for _, e in events)
    assert not any(c == "checkpoint" for c, _ in events)
    assert ("trainer", "fit_failed") in events
    fail = [r for r in recs if r["event"] == "fit_failed"][0]
    assert fail["health"]["nan_loss"] >= 1


def test_forced_nan_warn_policy_completes_with_events(tmp_path):
    """Default policy: the run completes its budget, but every rank of
    the incident is on the record."""
    result, recs = _nan_run(
        tmp_path, halt=False, use_scan=True, subdir="warn"
    )
    assert result is not None
    assert result.health["events"]["nan_loss"] >= 1
    events = [(r["component"], r["event"]) for r in recs]
    assert ("health", "health.nan_loss") in events
    assert ("trainer", "fit_end") in events
    fit_end = [r for r in recs if r["event"] == "fit_end"][0]
    assert fit_end["health"]["nan_loss"] >= 1

"""Distributed tracing + training-health layer: span runtime semantics
(nesting, env propagation across a spawned subprocess), Chrome-trace
export (determinism, schema validity), the health monitor's detectors
and halt policy, the inspect CLI on a fixture run dir, and the
validate_payload overflow fix (ISSUE 2 acceptance rig for the launched
2-process run lives in tests/test_tracing_e2e.py)."""

import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from dct_tpu.observability.health import (
    HealthMonitor,
    TrainingHealthError,
)
from dct_tpu.observability.spans import SpanRecorder
from dct_tpu.observability.trace_export import (
    read_spans,
    to_chrome_trace,
    write_trace,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- span runtime ------------------------------------------------------


def test_span_nesting_schema_and_trace_id(tmp_path):
    path = str(tmp_path / "spans" / "rank_00000.jsonl")
    rec = SpanRecorder(path, trace_id="dct-t1", rank=0)
    with rec.span("trainer.fit", epochs=2) as fit:
        with rec.span("trainer.epoch", epoch=0) as ep:
            with rec.span("trainer.data_wait"):
                pass
        assert ep.span_id != fit.span_id
    recs = [json.loads(line) for line in open(path).read().splitlines()]
    assert [r["name"] for r in recs] == [
        "trainer.data_wait", "trainer.epoch", "trainer.fit",
    ]  # spans record at END, innermost first
    by_name = {r["name"]: r for r in recs}
    # Implicit parenting follows the with-nesting.
    assert by_name["trainer.fit"]["parent_id"] is None
    assert (
        by_name["trainer.epoch"]["parent_id"]
        == by_name["trainer.fit"]["span_id"]
    )
    assert (
        by_name["trainer.data_wait"]["parent_id"]
        == by_name["trainer.epoch"]["span_id"]
    )
    for r in recs:
        # Fixed schema keys always present; one trace, wall-clock order.
        assert set(r) >= {
            "trace_id", "span_id", "parent_id", "name", "component",
            "rank", "pid", "tid", "t0", "t1",
        }
        assert r["trace_id"] == "dct-t1"
        assert r["rank"] == 0
        assert r["t1"] >= r["t0"]
    assert by_name["trainer.fit"]["attrs"]["epochs"] == 2
    # Component defaults to the name's prefix.
    assert by_name["trainer.epoch"]["component"] == "trainer"


def test_span_open_end_and_disabled_recorder(tmp_path):
    rec = SpanRecorder(str(tmp_path / "s.jsonl"), trace_id="dct-t2")
    root = rec.open("launcher.launch")
    assert rec.current_span_id() == root.span_id
    child = rec.start("launcher.rank", launched_rank=1)
    assert child.parent_id == root.span_id
    child.end(returncode=0)
    root.end()
    assert rec.current_span_id() is None
    root.end()  # idempotent: no double record
    recs = [
        json.loads(line)
        for line in open(tmp_path / "s.jsonl").read().splitlines()
    ]
    assert len(recs) == 2
    # Disabled recorder: IDs still mint (propagation keeps working),
    # nothing is written, nothing raises.
    off = SpanRecorder(None, trace_id="dct-t3")
    with off.span("x.y") as sp:
        assert sp.span_id
    assert not off.enabled
    assert off.child_env()["DCT_RUN_ID"] == "dct-t3"


def test_disabled_recorder_span_contract(monkeypatch):
    """The disabled recorder (path=None) must stay ID-transparent: spans
    still mint real 16-hex ids, the thread stack still parents them, and
    child_env still exports DCT_SPAN_ID — a rig that silenced telemetry
    must not silently break cross-process span parenting for children
    whose OWN recorder may be enabled."""
    import re

    monkeypatch.delenv("DCT_SPAN_ID", raising=False)
    off = SpanRecorder(None, trace_id="dct-off")
    assert not off.enabled
    with off.span("launcher.launch") as outer:
        assert re.fullmatch(r"[0-9a-f]{16}", outer.span_id)
        assert off.current_span_id() == outer.span_id
        with off.span("launcher.rank") as inner:
            assert inner.parent_id == outer.span_id
            env = off.child_env({"KEEP": "1"})
            assert env["DCT_SPAN_ID"] == inner.span_id
            assert env["DCT_RUN_ID"] == "dct-off"
            assert env["KEEP"] == "1"
    # Stack unwound; with no ambient parent there is nothing to export,
    # but the trace id still rides along.
    assert off.current_span_id() is None
    assert "DCT_SPAN_ID" not in off.child_env()
    assert off.child_env()["DCT_RUN_ID"] == "dct-off"


def test_span_recorder_failure_degrades_to_noop(tmp_path):
    blocker = tmp_path / "plainfile"
    blocker.write_text("x")
    rec = SpanRecorder(
        str(blocker / "s.jsonl"), trace_id="dct-x"
    )
    with rec.span("a.b"):
        pass  # OSError swallowed
    assert not rec.enabled


_CHILD_SCRIPT = (
    "import os\n"
    "from dct_tpu.observability import spans\n"
    "rec = spans.get_default()\n"
    "with rec.span('child.work'):\n"
    "    pass\n"
)


def test_parent_child_propagation_across_subprocess(tmp_path):
    """The env contract: a child process's top-level spans adopt the
    parent process's exported DCT_SPAN_ID — the cross-process edge the
    launcher/trainer trace depends on."""
    spans_dir = tmp_path / "ev" / "spans"
    rec = SpanRecorder(
        str(spans_dir / "host_parent.jsonl"), trace_id="dct-prop", rank=None
    )
    with rec.span("parent.launch") as parent:
        env = rec.child_env(
            {
                **os.environ,
                "PYTHONPATH": _REPO,
                "DCT_EVENTS_DIR": str(tmp_path / "ev"),
                "DCT_PROCESS_ID": "0",
            }
        )
        assert env["DCT_SPAN_ID"] == parent.span_id
        assert env["DCT_RUN_ID"] == "dct-prop"
        subprocess.run(
            [sys.executable, "-c", _CHILD_SCRIPT], env=env, check=True
        )
    merged = read_spans(str(tmp_path / "ev"))
    by_name = {r["name"]: r for r in merged}
    assert by_name["child.work"]["parent_id"] == parent.span_id
    assert by_name["child.work"]["trace_id"] == "dct-prop"
    assert by_name["child.work"]["rank"] == 0
    assert by_name["parent.launch"]["rank"] is None


# -- chrome trace export -----------------------------------------------


def _fixture_spans():
    mk = lambda i, **kw: {  # noqa: E731 — local record factory
        "trace_id": "dct-merge", "span_id": f"{i:016x}",
        "parent_id": None, "name": f"n{i}", "component": "trainer",
        "rank": i % 2, "pid": 100 + i, "tid": 0,
        "t0": 1000.0 + i, "t1": 1001.0 + i, **kw,
    }
    return [mk(0), mk(1), mk(2, rank=None, component="launcher")]


def test_trace_merge_is_deterministic(tmp_path):
    """Same span set -> byte-identical trace.json, regardless of file
    layout or input order (diffable artifacts, stable fixtures)."""
    a, b = tmp_path / "a" / "spans", tmp_path / "b" / "spans"
    recs = _fixture_spans()
    for d, split in ((a, 1), (b, 2)):
        d.mkdir(parents=True)
        (d / "f1.jsonl").write_text(
            "".join(json.dumps(r) + "\n" for r in recs[:split])
        )
        (d / "f2.jsonl").write_text(
            "".join(json.dumps(r) + "\n" for r in reversed(recs[split:]))
        )
    out_a = write_trace(
        to_chrome_trace(read_spans(str(a))), str(tmp_path / "ta.json")
    )
    out_b = write_trace(
        to_chrome_trace(read_spans(str(b))), str(tmp_path / "tb.json")
    )
    assert open(out_a, "rb").read() == open(out_b, "rb").read()


def test_chrome_trace_schema_is_valid(tmp_path):
    trace = to_chrome_trace(_fixture_spans())
    # Strict JSON round trip (Perfetto/chrome://tracing both parse it).
    text = json.dumps(trace, allow_nan=False)
    loaded = json.loads(text)
    events = loaded["traceEvents"]
    assert isinstance(events, list) and events
    complete = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert len(complete) == 3
    for e in complete:
        assert set(e) >= {"name", "cat", "ph", "ts", "pid", "tid", "dur"}
        assert isinstance(e["ts"], (int, float))
        assert e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["args"]["trace_id"] == "dct-merge"
    # Ranks map to pid=rank; the orchestrator process gets a named
    # high pid; every pid has a process_name metadata event.
    assert {e["pid"] for e in complete} == {0, 1, 100000}
    names = {e["pid"]: e["args"]["name"] for e in meta
             if e["name"] == "process_name"}
    assert names[0] == "rank 0" and names[1] == "rank 1"
    assert "launcher" in names[100000]


def test_read_spans_skips_torn_lines_and_filters_trace(tmp_path):
    d = tmp_path / "spans"
    d.mkdir()
    good = _fixture_spans()[0]
    (d / "r.jsonl").write_text(
        json.dumps(good) + "\n"
        + '{"torn": '  # crash mid-append
        + "\nnot json at all\n"
        + json.dumps({**good, "span_id": "ff", "trace_id": "dct-other"})
        + "\n"
    )
    assert [r["span_id"] for r in read_spans(str(d))] == [
        good["span_id"], "ff",
    ]
    assert [
        r["span_id"] for r in read_spans(str(d), trace_id="dct-merge")
    ] == [good["span_id"]]


# -- health monitor ----------------------------------------------------


def test_health_nan_guard_counts_and_emits():
    emitted = []
    mon = HealthMonitor(
        emit=lambda comp, ev, **f: emitted.append((comp, ev, f))
    )
    assert mon.observe_step(0.5, step=1) is None
    f = mon.observe_step(float("nan"), step=2, epoch=0)
    assert f is not None and f.kind == "nan_loss" and not f.halt
    assert mon.counts["nan_loss"] == 1
    comp, ev, fields = emitted[0]
    assert (comp, ev) == ("health", "health.nan_loss")
    assert fields["step"] == 2 and fields["halt"] is False
    # inf is just as dead as nan.
    assert mon.observe_step(float("inf"), step=3).kind == "nan_loss"


def test_health_loss_spike_zscore_detector():
    mon = HealthMonitor(spike_window=16, spike_zscore=6.0)
    rng = np.random.default_rng(0)
    for i in range(16):
        assert mon.observe_step(0.5 + 0.01 * rng.standard_normal()) is None
    f = mon.observe_step(5.0)  # ~450 sigma over the window
    assert f is not None and f.kind == "loss_spike"
    assert f.zscore > 6.0
    # Downward moves are the GOAL, never a spike.
    assert mon.observe_step(0.01) is None


def test_health_grad_norm_spike_detector():
    mon = HealthMonitor(spike_window=8, spike_zscore=6.0)
    for i in range(8):
        assert mon.observe_step(0.5, grad_norm=1.0 + 0.01 * i) is None
    f = mon.observe_step(0.5, grad_norm=1e4)
    assert f is not None and f.kind == "grad_norm_spike"
    assert mon.last_grad_norm == 1e4
    s = mon.summary()
    assert s["events"]["grad_norm_spike"] == 1
    assert s["last_loss"] == 0.5


def test_health_near_constant_history_no_false_spike():
    """std ~ 0 histories must not turn fp jitter into z-blowups."""
    mon = HealthMonitor(spike_window=16, spike_zscore=6.0)
    for _ in range(16):
        mon.observe_step(0.5)
    assert mon.observe_step(0.5 + 1e-9) is None


def test_health_halt_policy_raises():
    mon = HealthMonitor(halt_on_nan=True)
    f = mon.observe_step(float("nan"), step=7)
    assert f.halt
    with pytest.raises(TrainingHealthError, match="nan_loss"):
        HealthMonitor.raise_on(f)
    HealthMonitor.raise_on(None)  # no finding, no raise
    # Warn-only monitor never produces a halting finding.
    warn = HealthMonitor(halt_on_nan=False)
    HealthMonitor.raise_on(warn.observe_step(float("nan")))


def test_health_spike_only_policy_halts_on_nan_grad_norm():
    """With ONLY halt_on_spike set, a step whose loss went straight to
    NaN (grad norm Inf, no finite spike first) must still halt: the
    non-finite grad norm is its own halting finding."""
    mon = HealthMonitor(halt_on_nan=False, halt_on_spike=True)
    f = mon.observe_step(
        float("nan"), grad_norm=float("inf"), step=3, epoch=0
    )
    assert f is not None and f.halt
    assert f.kind == "grad_norm_spike"
    assert mon.counts["nan_loss"] == 1  # both findings counted
    with pytest.raises(TrainingHealthError):
        HealthMonitor.raise_on(f)


def test_health_event_cap_suppresses_spam():
    emitted = []
    mon = HealthMonitor(emit=lambda c, e, **f: emitted.append(f))
    for _ in range(50):
        mon.observe_step(float("nan"))
    assert mon.counts["nan_loss"] == 50
    from dct_tpu.observability.health import MAX_EVENTS_PER_KIND

    assert len(emitted) == MAX_EVENTS_PER_KIND
    assert "note" in emitted[-1]


def test_train_metrics_prom_includes_health():
    from dct_tpu.observability.dump import write_train_metrics_prom
    from dct_tpu.observability.goodput import GoodputLedger
    from tests.test_observability import FakeClock, _parse_exposition

    import tempfile

    led = GoodputLedger(clock=FakeClock())
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "m.prom")
        write_train_metrics_prom(
            path, led.summary(), run_id="dct-h",
            health={
                "events": {"nan_loss": 2, "loss_spike": 0,
                           "grad_norm_spike": 1},
                "last_loss": 0.4, "last_grad_norm": 3.5,
            },
        )
        samples = _parse_exposition(open(path).read())
    # Labels render in canonical sorted order since the ISSUE 8 registry
    # rebuild (a merge identity must not depend on insertion order).
    assert samples[
        'dct_train_health_events_total{kind="nan_loss",run_id="dct-h"}'
    ] == 2
    assert samples['dct_train_grad_norm{run_id="dct-h"}'] == 3.5


# -- train-step grad norm surface --------------------------------------


def test_train_step_exposes_grad_norm():
    import jax.numpy as jnp

    from dct_tpu.config import ModelConfig
    from dct_tpu.models.registry import get_model
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import (
        make_epoch_train_eval_step,
        make_train_step,
    )

    model = get_model(ModelConfig(hidden_dim=8), input_dim=5)
    state = create_train_state(model, input_dim=5, lr=1e-2, seed=0)
    x = jnp.ones((4, 5), jnp.float32)
    y = jnp.zeros((4,), jnp.int32)
    w = jnp.ones((4,), jnp.float32)
    _, metrics = make_train_step(donate=False, with_grad_norm=True)(
        state, x, y, w
    )
    gn = float(metrics["grad_norm"])
    assert np.isfinite(gn) and gn > 0
    # Default factory keeps the historical metrics surface (bench
    # consumers measure the exact prior program).
    _, plain = make_train_step(donate=False)(state, x, y, w)
    assert "grad_norm" not in plain
    # Scan path: with_grad_norms puts per-update norms before the
    # model's counters, which every signature ends with.
    xs, ys, ws = x[None], y[None], w[None]
    _, losses, sums, gnorms, _ = make_epoch_train_eval_step(
        donate=False, with_grad_norms=True
    )(state, xs, ys, ws, xs, ys, ws)
    assert gnorms.shape == losses.shape == (1,)
    assert float(gnorms[0]) == pytest.approx(gn, rel=1e-5)


# -- inspect CLI on a fixture run dir ----------------------------------


@pytest.fixture()
def fixture_run_dir(tmp_path):
    """A fabricated two-rank run dir: events + spans + heartbeats."""
    rid = "dct-fixture00001"
    ev_dir = tmp_path / "events"
    ev_dir.mkdir()
    events = [
        {"ts": 1000.0, "run_id": rid, "rank": None,
         "component": "launcher", "event": "launch_start",
         "world_size": 2},
        {"ts": 1001.0, "run_id": rid, "rank": 0, "component": "trainer",
         "event": "fit_start"},
        {"ts": 1005.0, "run_id": rid, "rank": 0, "component": "trainer",
         "event": "epoch_end", "epoch": 0, "train_loss": 0.7,
         "val_loss": 0.6, "val_acc": 0.7, "goodput_fraction": 0.8},
        {"ts": 1005.5, "run_id": rid, "rank": 0, "component": "health",
         "event": "health.loss_spike", "value": 9.0, "step": 5,
         "epoch": 0, "halt": False, "zscore": 8.2},
        {"ts": 1006.0, "run_id": rid, "rank": 0, "component": "trainer",
         "event": "goodput_summary", "wall_seconds": 6.0,
         "goodput_fraction": 0.75,
         "categories": {"train_step": 4.5, "compile": 1.0},
         "unattributed_seconds": 0.5, "epochs": 1},
        {"ts": 1007.0, "run_id": rid, "rank": None,
         "component": "launcher", "event": "launch_end",
         "returncodes": [0, 0], "success": True},
    ]
    with open(ev_dir / "events.jsonl", "w") as f:
        for r in events:
            f.write(json.dumps(r) + "\n")
    spans_dir = ev_dir / "spans"
    spans_dir.mkdir()
    span_recs = [
        {"trace_id": rid, "span_id": "aa" * 8, "parent_id": None,
         "name": "launcher.launch", "component": "launcher",
         "rank": None, "pid": 99, "tid": 0, "t0": 1000.0, "t1": 1007.0},
        {"trace_id": rid, "span_id": "bb" * 8, "parent_id": "aa" * 8,
         "name": "trainer.fit", "component": "trainer", "rank": 0,
         "pid": 100, "tid": 0, "t0": 1001.0, "t1": 1006.5},
        {"trace_id": rid, "span_id": "cc" * 8, "parent_id": "aa" * 8,
         "name": "trainer.fit", "component": "trainer", "rank": 1,
         "pid": 101, "tid": 0, "t0": 1001.2, "t1": 1006.4},
    ]
    for i, rec in enumerate(span_recs):
        fname = (
            f"rank_{rec['rank']:05d}.jsonl"
            if rec["rank"] is not None
            else "host_99.jsonl"
        )
        with open(spans_dir / fname, "a") as f:
            f.write(json.dumps(rec) + "\n")
    hb_dir = tmp_path / "heartbeats"
    hb_dir.mkdir()
    for r in (0, 1):
        with open(hb_dir / f"rank_{r:05d}.json", "w") as f:
            json.dump(
                {"rank": r, "run_id": rid, "pid": 100 + r,
                 "time": 1006.0, "step": 10, "epoch": 0,
                 "phase": "done"},
                f,
            )
    return tmp_path, rid


def test_inspect_cli_reports_cycle_and_writes_trace(
    fixture_run_dir, capsys
):
    from dct_tpu.observability.inspect import main

    run_dir, rid = fixture_run_dir
    assert main([str(run_dir)]) == 0
    out = capsys.readouterr().out
    assert rid in out
    # Both ranks are NAMED in the report.
    assert "rank 0" in out and "rank 1" in out
    assert "goodput_fraction 0.7500" in out
    assert "health.loss_spike" in out
    assert "launch_end" in out
    trace_path = run_dir / "trace.json"
    assert trace_path.exists()
    trace = json.loads(trace_path.read_text())
    names = {
        e["name"] for e in trace["traceEvents"] if e["ph"] == "X"
    }
    assert {"launcher.launch", "trainer.fit"} <= names
    assert trace["otherData"]["trace_ids"] == [rid]
    assert str(trace_path) in out  # Perfetto pointer printed


def test_inspect_cli_run_id_filter_and_missing_dir(
    fixture_run_dir, capsys
):
    from dct_tpu.observability.inspect import main

    run_dir, rid = fixture_run_dir
    # A foreign run id keeps the report working, with empty sections.
    assert main([str(run_dir), "--run-id", "dct-other", "--no-trace"]) == 0
    out = capsys.readouterr().out
    assert "dct-other" in out
    assert "(none found)" in out
    assert main(["/nonexistent/dir"]) == 2


# -- satellites --------------------------------------------------------


def test_validate_payload_overflow_is_clean_400_no_warning():
    """Float32 overflow of a huge JSON number must raise the client
    ValueError WITHOUT leaking a RuntimeWarning into server logs."""
    from dct_tpu.serving.runtime import validate_payload

    meta = {"input_dim": 5, "model": "weather_mlp"}
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any warning becomes a failure
        with pytest.raises(ValueError, match="finite"):
            validate_payload(meta, [[1e39, 0.0, 0.0, 0.0, 0.0]])
        # Ordinary payloads stay valid under the errstate guard.
        out = validate_payload(meta, [[0.1, 0.2, 0.3, 0.4, 0.5]])
    assert out.shape == (1, 5)

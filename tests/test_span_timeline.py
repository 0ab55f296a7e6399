"""ISSUE 25: the span recorder on the profiler's clock, and the one
bracket that feeds both the goodput ledger and the recorder.

A stack span (``open`` / ``span``) is a ``jax.profiler.TraceAnnotation``
on the thread that runs it; a ``start`` span and a process that never
imported jax emit nothing and still write JSONL. In ``Trainer.fit`` every
host category of the ledger is billed by the helper that also closes the
span, so the category's seconds ARE the summed ``seconds`` of its spans.
The CPU profiler records ``TraceAnnotation``s, so all of it is checked
here, off the chip."""

import glob
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from dct_tpu.config import (
    DataConfig,
    ObservabilityConfig,
    RunConfig,
    TrackingConfig,
    TrainConfig,
)
from dct_tpu.observability import lineage as _lineage
from dct_tpu.observability import spans as _spans
from dct_tpu.observability.goodput import GoodputLedger
from dct_tpu.observability.spans import SpanRecorder
from dct_tpu.tracking.client import LocalTracking
from dct_tpu.train.trainer import Trainer, _Timed

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Session:
    """A ``jax.profiler`` session as the benchmark's harness opens one:
    host spans on, the Python call tracer off."""

    def __init__(self, trace_dir):
        self.trace_dir = str(trace_dir)

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False

    def lines(self):
        """Per host thread line: [(name, start_ns, end_ns, stats)] of the
        events that carry a ``span_id`` (the recorder's)."""
        from jax.profiler import ProfileData

        path = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        out = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                evs = []
                for ev in line.events:
                    stats = dict(ev.stats)
                    if "span_id" in stats:
                        evs.append((
                            ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, stats,
                        ))
                if evs:
                    out.append(sorted(evs, key=lambda e: e[1]))
        return out


def _jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_stack_spans_reach_the_profiler_with_attrs_and_nesting(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    rec = SpanRecorder(path, trace_id="dct-tl")
    with _Session(tmp_path / "trace") as session:
        outer = rec.open("trainer.checkpoint", epoch=3, skipped=[1, 2])
        with rec.span("checkpoint.deploy_write", path="last.ckpt") as sp:
            time.sleep(0.002)
            sp.set(bytes=1234, improved=True)
        loose = rec.start("trainer.epoch", epoch=3)

        def work():
            with rec.span("checkpoint.resume_save", epochs_completed=4):
                time.sleep(0.002)

        worker = threading.Thread(target=work)
        worker.start()
        worker.join(timeout=30)
        assert not worker.is_alive()
        loose.end()
        outer.end(seconds=0.5)
    lines = session.lines()
    by_name = {e[0]: (i, e) for i, line in enumerate(lines) for e in line}
    # The start() span is JSONL-only; the three stack spans are events.
    assert set(by_name) == {
        "trainer.checkpoint", "checkpoint.deploy_write",
        "checkpoint.resume_save",
    }
    recs = {r["name"]: r for r in _jsonl(path)}
    assert set(recs) == set(by_name) | {"trainer.epoch"}
    (i_out, out), (i_in, inn) = (
        by_name["trainer.checkpoint"], by_name["checkpoint.deploy_write"])
    # Same thread line, nested as opened; the worker's span on its own.
    assert i_out == i_in != by_name["checkpoint.resume_save"][0]
    assert out[1] <= inn[1] and inn[2] <= out[2]
    assert inn[2] - inn[1] >= 2e6
    # Scalar attrs (given at start, set later, given at end) are stats;
    # span_id joins the event to its JSONL record.
    assert out[3]["epoch"] == 3 and out[3]["seconds"] == 0.5
    assert "skipped" not in out[3]
    assert inn[3]["path"] == "last.ckpt" and inn[3]["bytes"] == 1234
    assert inn[3]["improved"] == 1
    for name, (_, ev) in by_name.items():
        assert ev[3]["span_id"] == recs[name]["span_id"]
    assert recs["trainer.checkpoint"]["attrs"]["skipped"] == [1, 2]


def test_disabled_recorder_still_emits_and_spans_close_on_errors(tmp_path):
    off = SpanRecorder(None, trace_id="dct-off")
    with _Session(tmp_path / "trace") as session:
        with pytest.raises(ValueError):
            with off.span("trainer.bookkeep", epoch=1):
                with off.span("data.assemble"):
                    raise ValueError("boom")
        assert off.current_span_id() is None
    (line,) = session.lines()
    assert [e[0] for e in line] == ["trainer.bookkeep", "data.assemble"]
    assert all(e[3]["error"] == "ValueError" for e in line)


def test_a_process_without_jax_emits_nothing_and_still_writes_jsonl(tmp_path):
    path = str(tmp_path / "host.jsonl")
    code = (
        "import sys\n"
        "from dct_tpu.observability.spans import SpanRecorder\n"
        f"rec = SpanRecorder({path!r}, trace_id='dct-nojax')\n"
        "with rec.span('dag.task', task='etl') as sp:\n"
        "    assert sp._ann is None\n"
        "top = rec.open('launcher.launch'); top.end()\n"
        "rec.close()\n"
        "assert 'jax' not in sys.modules, 'spans imported jax'\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=_REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert [s["name"] for s in _jsonl(path)] == [
        "dag.task", "launcher.launch"]


def test_timed_bills_the_ledger_the_seconds_its_span_carries(tmp_path):
    ticks = iter([10.0, 10.25, 20.0, 23.5, 30.0, 30.125, 40.0, 41.0])
    ledger = GoodputLedger(clock=lambda: next(ticks))
    path = str(tmp_path / "s.jsonl")
    rec = SpanRecorder(path, trace_id="dct-timed")
    with _Timed(ledger, rec, "data_wait", "trainer.data_wait", epoch=2):
        pass
    # begin()/end() form; end is idempotent (the crash sweep calls it).
    ckpt = _Timed(ledger, rec, "checkpoint", "trainer.checkpoint").begin()
    assert rec.current_span_id() == ckpt.span.span_id
    ckpt.end(resumed=False)
    ckpt.end(error=True)
    # No category: nothing billed, the reads are the caller's to use.
    with _Timed(ledger, rec, None, "trainer.join") as join:
        pass
    assert (join.t0, join.t1, join.seconds) == (30.0, 30.125, 0.125)
    assert ledger.seconds["data_wait"] == 0.25
    assert ledger.seconds["checkpoint"] == 3.5
    assert ledger.accounted_seconds() == 3.75
    recs = {r["name"]: r for r in _jsonl(path)}
    assert len(recs) == 3 and rec.current_span_id() is None
    assert recs["trainer.data_wait"]["attrs"] == {"epoch": 2, "seconds": 0.25}
    assert recs["trainer.checkpoint"]["attrs"] == {
        "resumed": False, "seconds": 3.5}
    assert recs["trainer.join"]["attrs"]["seconds"] == 0.125
    with pytest.raises(RuntimeError):
        with _Timed(ledger, rec, None, "trainer.dispatch_call"):
            raise RuntimeError("compile failed")
    assert _jsonl(path)[-1]["attrs"]["error"] == "RuntimeError"


def test_save_checkpoint_splits_into_serialize_write_and_hash(
    tmp_path, monkeypatch
):
    import numpy as np

    from dct_tpu.checkpoint.manager import save_checkpoint

    rec = SpanRecorder(str(tmp_path / "s.jsonl"), trace_id="dct-ck")
    monkeypatch.setattr(_spans, "_explicit", rec)
    lin = _lineage.LineageLedger(
        str(tmp_path / "lineage.jsonl"), run_id="dct-ck")
    monkeypatch.setattr(_lineage, "_explicit", lin)
    with rec.span("checkpoint.deploy_write"):
        out = save_checkpoint(
            str(tmp_path / "models" / "last.ckpt"),
            {"w": np.ones((64, 64), np.float32)}, {"epoch": 1},
        )
    size = os.path.getsize(out)
    recs = _jsonl(str(tmp_path / "s.jsonl"))
    assert [r["name"] for r in recs] == [
        "checkpoint.serialize", "checkpoint.file_write",
        "checkpoint.lineage_hash", "checkpoint.deploy_write",
    ]
    parent = recs[-1]["span_id"]
    for r in recs[:3]:
        assert r["parent_id"] == parent
        assert r["attrs"] == {"path": "last.ckpt", "bytes": size}
    assert size > 64 * 64 * 4


def _fit(processed_dir, tmp_path, tag, **train_kw):
    train_kw.setdefault("epochs", 3)
    train_kw.setdefault("batch_size", 8)
    train_kw.setdefault("bf16_compute", False)
    cfg = RunConfig(
        data=DataConfig(
            processed_dir=processed_dir,
            models_dir=str(tmp_path / f"m_{tag}"),
        ),
        train=TrainConfig(**train_kw),
        tracking=TrackingConfig(experiment="tl"),
        obs=ObservabilityConfig(
            events_dir=str(tmp_path / f"ev_{tag}"),
            heartbeat_dir=str(tmp_path / f"hb_{tag}"),
        ),
    )
    tracker = LocalTracking(root=str(tmp_path / f"r_{tag}"), experiment="tl")
    result = Trainer(cfg, tracker=tracker).fit()
    spans = []
    for path in sorted(glob.glob(
            os.path.join(cfg.obs.events_dir, "spans", "*.jsonl"))):
        spans += _jsonl(path)
    return result, spans


def _seconds(spans, *names):
    return sum(s["attrs"]["seconds"] for s in spans if s["name"] in names)


#: Ledger category -> the spans whose bracket bills it.
HOST_CATEGORIES = {
    "startup_recovery": ("trainer.startup",),
    "data_wait": ("trainer.data_wait", "data.stage"),
    "checkpoint": ("trainer.checkpoint", "trainer.upload"),
}


@pytest.mark.parametrize("mode", ["pipelined", "serial", "eager"])
def test_fit_host_categories_are_the_summed_seconds_of_their_spans(
    processed_dir, tmp_path, mode
):
    kw = {
        "pipelined": dict(prefetch_spans=1),
        "serial": dict(prefetch_spans=0),
        "eager": dict(use_scan=False),
    }[mode]
    result, spans = _fit(processed_dir, tmp_path, mode, **kw)
    cats = result.goodput["categories"]
    for cat, names in HOST_CATEGORIES.items():
        assert cats[cat] == pytest.approx(
            _seconds(spans, *names), abs=1e-9), cat
        assert cats[cat] > 0
    names = {s["name"] for s in spans}
    if mode == "eager":
        assert "data.stage" in names and "trainer.join" not in names
        return
    # The dispatch windows go through add_dispatch's own arithmetic,
    # from the same reads: pipelined, the call plus the join.
    calls = [s for s in spans if s["name"] == "trainer.dispatch_call"]
    assert [c["attrs"]["first"] for c in calls] == [True, False, False]
    assert len([s for s in spans if s["name"] == "trainer.join"]) == 3
    if mode == "pipelined":
        assert cats["train_step"] + cats["compile"] == pytest.approx(
            _seconds(spans, "trainer.dispatch_call", "trainer.join"),
            abs=1e-9)
    # A span's wall-clock length is its bracket's, within the clocks.
    for s in spans:
        if "seconds" in s.get("attrs", {}):
            assert s["t1"] - s["t0"] == pytest.approx(
                s["attrs"]["seconds"], abs=0.05), s["name"]


def test_fit_under_a_profiler_session_shows_the_thread_s_timeline(
    processed_dir, tmp_path
):
    with _Session(tmp_path / "trace") as session:
        _fit(processed_dir, tmp_path, "prof", prefetch_spans=1)
    lines = session.lines()
    trainer = next(
        line for line in lines if any(e[0] == "trainer.fit" for e in line))
    fit = next(e for e in trainer if e[0] == "trainer.fit")
    # Depth by containment, on the trainer's thread.
    stack, depth, parent = [], {}, {}
    for ev in sorted(trainer, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= ev[1]:
            stack.pop()
        depth[id(ev)] = len(stack)
        parent[id(ev)] = stack[-1][0] if stack else None
        assert not stack or ev[2] <= stack[-1][2], (ev[0], stack[-1][0])
        stack.append(ev)
    under_fit = [e[0] for e in trainer if depth[id(e)] == 1]
    assert under_fit[0] == "trainer.startup"
    assert set(under_fit) == {
        "trainer.startup", "trainer.data_wait", "trainer.dispatch_call",
        "trainer.join", "trainer.bookkeep", "trainer.checkpoint",
    }
    assert under_fit.count("trainer.checkpoint") == 3
    children = {}
    for e in trainer:
        children.setdefault(parent[id(e)], set()).add(e[0])
    assert children["trainer.checkpoint"] == {
        "trainer.gather_params", "checkpoint.deploy_write",
        "checkpoint.resume_wait_prev", "checkpoint.resume_snapshot",
    }
    assert children["checkpoint.deploy_write"] == {
        "checkpoint.serialize", "checkpoint.file_write",
        "checkpoint.lineage_hash",
    }
    assert fit[1] <= min(e[1] for e in trainer)
    # The resume tier's write and the prefetched assembly: own threads.
    others = {e[0] for line in lines if line is not trainer for e in line}
    assert {"checkpoint.resume_save", "data.assemble"} <= others
    assert "checkpoint.resume_save" not in {e[0] for e in trainer}
    # trainer.upload follows the fit span on the same thread.
    assert [e[0] for e in trainer if depth[id(e)] == 0] == [
        "trainer.fit", "trainer.upload"]

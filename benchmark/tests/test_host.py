"""The host reduction against the recorded sample, with values computed by
hand from the event list in ``benchmark/reduce/sample/README_host_spans.txt``,
and every new reader on artefacts of a program that makes no such span."""

import os
import shutil

import pytest

from benchmark import manifest as mf
from benchmark.reduce import host as hr
from benchmark.reduce import trace as tr

SAMPLE = f"{mf.BENCH_DIR}/reduce/sample/tpu_v5e_host_spans.xplane.pb"
#: PR 23's sample: markers of the harness, no span of the program.
NO_SPANS = f"{mf.BENCH_DIR}/reduce/sample/tpu_v5e_three_calls.xplane.pb"
TRACE_READERS = (
    "trainer.host_epoch_s", "trainer.join_wait_share",
    "checkpoint.section_s", "checkpoint.disk_share",
    "device.idle_unexplained_share",
)
JSONL_READERS = ("trainer.startup_s", "trainer.first_dispatch_s")


@pytest.fixture(scope="module")
def host():
    return hr.load(SAMPLE)


def _art(tmp_path, sample, spans=()):
    """Artefacts as ``run.py`` hands them to a reader of a traced run."""
    run_dir = tmp_path / "trace" / "plugins" / "profile" / "run"
    os.makedirs(run_dir)
    shutil.copy(sample, run_dir / "vm.xplane.pb")
    return {"trace_dir": str(tmp_path / "trace"), "trace": tr.load(sample),
            "spans": list(spans)}


def test_threads_window_and_epochs_by_hand(host):
    trainer = host.trainer
    assert [len(t.spans) for t in host.threads] == [2, 24]
    assert trainer is host.threads[1] and trainer.name == "python"
    assert [m[0] for m in trainer.markers] == [
        "bench.trace_begin", "bench.epoch_end.1", "bench.epoch_end.2",
        "bench.trace_end"]
    assert host.window == (49073445.0, 103450099.0)
    assert host.epochs() == [
        (49073445.0, 72349192.0), (72349192.0, 103123749.0)]
    # The other line: the resume tier's worker.
    assert [(s.name, s.depth) for s in host.threads[0].spans] == [
        ("checkpoint.resume_save", 0)] * 2
    assert host.threads[0].spans[0].stats["epochs_completed"] == 1


def test_depth_is_by_containment_with_the_root_left_out(host):
    spans = host.trainer.spans
    assert "trainer.fit" not in {s.name for s in spans}
    assert [s.name for s in host.trainer.top()] == [
        "trainer.checkpoint", "trainer.data_wait", "trainer.dispatch_call",
        "trainer.join", "trainer.bookkeep"] * 2
    assert [(s.name, s.depth) for s in spans[:9]] == [
        ("trainer.checkpoint", 0), ("trainer.gather_params", 1),
        ("checkpoint.deploy_write", 1), ("checkpoint.serialize", 2),
        ("checkpoint.file_write", 2), ("checkpoint.lineage_hash", 2),
        ("checkpoint.resume_wait_prev", 1), ("checkpoint.resume_snapshot", 1),
        ("trainer.data_wait", 0)]
    write = host.trainer.named("checkpoint.file_write")[0]
    assert (write.start, write.end) == (55051824.0, 55051824.0 + 5197600)
    assert write.stats["bytes"] == 4096 and write.stats["path"] == "last.ckpt"
    assert write.stats["span_id"] == "abad9352dc704921"


def test_the_readers_numbers_by_hand(host):
    # Epoch 1: checkpoint + data_wait + dispatch_call + the part of bookkeep
    # before the stamp; epoch 2 also takes the 8,610 ns of it after.
    e1 = 16057258 + 1439060 + 282770 + (72349192 - 70883562)
    e2 = (72357802 - 72349192) + 19032448 + 592940 + 340750 + (
        103123749 - 101627469)
    assert hr.host_epoch_seconds(host) == pytest.approx(
        [e1 * 1e-9, e2 * 1e-9], abs=1e-12)
    assert hr.join_wait_share(host) == pytest.approx(
        (3518349 + 3373949) / 54376654, abs=1e-9)
    assert [s.seconds for s in hr.checkpoint_sections(host)] == pytest.approx(
        [0.016057258, 0.019032448], abs=1e-12)
    assert hr.disk_share(host) == pytest.approx(
        (5197600 + 8712869) / (16057258 + 19032448), abs=1e-9)


def test_idle_time_under_no_span_by_hand(host):
    # Device 0's recorded ops run from 65804885 to 99505336; between them it
    # idles outside a program from the last op of run 1 (68630298) to the
    # first of run 2 (96679897). What comes before the first op and after
    # the last was not seen by the device's tracer and is left out. The
    # spans, widened by 2 ms, leave only 74357802..75896312 bare: the sleep
    # between bookkeep and checkpoint.
    idle = 96679897 - 68630298
    bare = (77896312 - 2e6) - (72357802 + 2e6)
    assert hr.idle_unexplained_share(host, tr.load(SAMPLE)) == pytest.approx(
        bare / idle, abs=1e-9)
    assert bare / idle == pytest.approx(0.0548496, abs=1e-6)


def test_each_trace_reader_reads_the_sample(tmp_path):
    art = _art(tmp_path, SAMPLE)
    got = {n: mf.load_layer_metric(n).read(art) for n in TRACE_READERS}
    assert got == pytest.approx({
        "trainer.host_epoch_s": (0.019244718 + 0.021471028) / 2,
        "trainer.join_wait_share": 12.675105,
        "checkpoint.section_s": (0.016057258 + 0.019032448) / 2,
        "checkpoint.disk_share": 39.642592,
        "device.idle_unexplained_share": 5.48496,
    }, rel=1e-5)
    assert isinstance(art["host"], hr.Host)  # read once a run


@pytest.mark.parametrize("name", TRACE_READERS + JSONL_READERS)
def test_a_program_without_spans_reads_as_nothing(tmp_path, name):
    """A parent commit's artefacts: a trace with the harness's markers only,
    JSONL spans without the names this PR adds, or no trace at all."""
    old = [{"name": "trainer.checkpoint", "t0": 1.0, "t1": 2.0},
           {"name": "trainer.dispatch", "t0": 0.5, "t1": 3.0,
            "attrs": {"key": "scan_k1"}}]
    reader = mf.load_layer_metric(name)
    assert reader.read(_art(tmp_path, NO_SPANS, old)) is None
    assert reader.read({"trace_dir": None, "trace": None, "spans": []}) is None


def test_the_jsonl_readers_take_the_bracket_s_seconds(tmp_path):
    spans = [
        {"name": "trainer.startup", "t0": 10.0, "t1": 15.9,
         "attrs": {"seconds": 5.75, "resumed": False}},
        {"name": "trainer.dispatch_call", "t0": 40.0, "t1": 40.1,
         "attrs": {"first": False, "seconds": 0.03}},
        {"name": "trainer.dispatch_call", "t0": 16.0, "t1": 40.0,
         "attrs": {"first": True, "seconds": 24.25}},
    ]
    art = {"trace_dir": None, "trace": None, "spans": spans}
    assert mf.load_layer_metric("trainer.startup_s").read(art) == 5.75
    assert mf.load_layer_metric("trainer.first_dispatch_s").read(art) == 24.25
    # A parent's startup span (start(), no bracket): its wall-clock length.
    art["spans"] = [{"name": "trainer.startup", "t0": 10.0, "t1": 12.5}]
    assert mf.load_layer_metric("trainer.startup_s").read(art) == 2.5

"""The trace reduction against the recorded sample, with values computed by
hand from the event list in ``benchmark/reduce/sample/README.txt``."""

import numpy as np
import pytest

from benchmark.manifest import BENCH_DIR
from benchmark.reduce import trace as tr

SAMPLE = f"{BENCH_DIR}/reduce/sample/tpu_v5e_three_calls.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.load(SAMPLE)


def test_window_busy_and_idle_by_hand(trace):
    assert [d.ordinal for d in trace.devices] == [0]
    # First device event 43175360, end of the last marker 60903942 + 3360.
    assert trace.window_s == pytest.approx((60907302 - 43175360) * 1e-9, abs=2e-9)
    # 3 x (13 + 3 + ~4214 + ~2408) ns, no two ops overlap.
    by_hand = (13 + 3 + 4213 + 2407 + 13 + 3 + 4214 + 2410
               + 13 + 3 + 4214 + 2406) * 1e-9
    assert tr.busy_mean_s(trace) == pytest.approx(by_hand, abs=1.5e-8)
    assert tr.idle_share(trace) == pytest.approx(
        1 - by_hand / 0.017731942, abs=1e-6)


def test_program_runs_and_gaps_by_hand(trace):
    runs = tr.module_runs(trace.devices[0], "small_program")
    assert [round(b - a) for a, b, _ in runs] == [6648, 6652, 6647]
    waits = [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    assert waits == pytest.approx(
        [48979186 - 43182008, 54424112 - 48985838], abs=3)
    gaps = tr.gaps(trace, trace.devices[0])
    long = sorted(b - a for a, b in gaps)[-3:]
    # After run 3 (ends 54430758) to the window's end; run 1 -> run 2; run 2 -> 3.
    assert long == pytest.approx(
        sorted([60907302 - 54430758, 48979193 - 43182007,
                54424118 - 48985837]), abs=5)
    assert sum(b - a for a, b in gaps) * 1e-9 == pytest.approx(
        trace.window_s - tr.busy_mean_s(trace), abs=1e-9)


def test_ops_are_named_short_and_ranked(trace):
    top = tr.top_ops(trace)
    assert [n for n, _ in top] == ["fusion.1", "fusion", "copy-start", "copy-done"]
    assert top[0][1] == pytest.approx(3 * 4214e-9, rel=1e-3)
    assert tr.share_of_ops(
        trace.devices[0], lambda n: n.startswith("fusion")
    ) == pytest.approx((3 * 4214 + 2407 + 2410 + 2406) / 19912, abs=1e-3)
    assert tr.exposed_collective_s(trace.devices[0]) == 0.0


def test_gaps_take_the_last_marker_the_host_had_passed(trace):
    labels = dict(tr.attribute_gaps(trace))
    # Each long gap's midpoint lies in the sleep that follows a call.
    assert labels["after_call"] == pytest.approx(
        (5797186 + 5438281 + 6476544) * 1e-9, abs=1e-7)
    # The nanoseconds before and between the ops of one run (7 + 1 + 2 in
    # run 1, and so on) are inside the program.
    assert set(labels) == {"after_call", "inside_program"}
    assert labels["inside_program"] == pytest.approx(19e-9, abs=5e-9)


def test_short_names():
    assert tr.short_name(
        "%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}"
    ) == "all-reduce.7"
    assert tr.short_name(
        '%custom-call.2 = (bf16[2,4]{1,0}, f32[2]{0}) custom-call(bf16[2,4]{1,0} %q), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    ) == "custom-call.2:tpu_custom_call"
    assert tr.short_name("jit_epoch_fused(123)") == "jit_epoch_fused(123)"
    assert tr.COLLECTIVE.match("all-reduce-start.3")
    assert not tr.COLLECTIVE.match("fusion.3")


def test_union_and_exposed_time_on_made_up_intervals():
    s, e = tr.union(np.array([0.0, 5, 6, 20]), np.array([10.0, 7, 12, 30]))
    assert s.tolist() == [0, 20] and e.tolist() == [12, 30]
    # A collective 100..200 with compute 150..180 under it: 70 exposed.
    ops = tr.Line([
        ("%all-reduce.1 = f32[] all-reduce(f32[] %x)", 100.0, 100.0),
        ("%fusion.9 = f32[] fusion(f32[] %x)", 150.0, 30.0),
        ("%while.1 = () while(() %t)", 0.0, 400.0),
    ])
    dev = tr.Device(0, ops, tr.Line([]))
    assert tr.exposed_collective_s(dev) == pytest.approx(70e-9)
    assert set(tr.op_seconds(dev)) == {"all-reduce.1", "fusion.9"}

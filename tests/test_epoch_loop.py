"""The epoch loop's decisions (``dct_tpu/train/epoch_loop.py``) without a
fit: the two early-stop guards of the pipelined loop, what an epoch bills
to the goodput ledger, and what the crash sweep closes."""

from __future__ import annotations

import pytest

from dct_tpu.train.epoch_loop import (
    EpochLoop,
    _SpanInFlight,
    may_prefetch_next,
    must_consume_pending,
    span_bill,
)
from dct_tpu.train.telemetry import RunTelemetry


@pytest.mark.parametrize(
    "patience, es_stale, pending, expect",
    [
        # early stop off: always speculate
        (0, 7, True, True),
        # far from patience: this epoch and the pending one cannot stop
        (5, 0, True, True),
        # this epoch alone could be the patience-th stale one
        (2, 1, False, False),
        # only together with the un-bookkept pending epoch
        (3, 1, True, False),
    ],
    ids=["off", "far", "within-by-this-epoch", "within-with-pending"],
)
def test_may_prefetch_next(patience, es_stale, pending, expect):
    assert may_prefetch_next(patience, es_stale, pending) is expect
    if patience == 3:
        # the same monitor without a pending epoch may speculate
        assert may_prefetch_next(patience, es_stale, False) is True


@pytest.mark.parametrize(
    "patience, es_stale, expect",
    [(0, 9, False), (2, 1, True)],
    ids=["off", "pending-could-stop"],
)
def test_must_consume_pending_before_dispatch(patience, es_stale, expect):
    assert must_consume_pending(patience, es_stale) is expect
    if patience:
        # one stale epoch further from the limit: dispatch ahead
        assert must_consume_pending(patience + 1, es_stale) is False


@pytest.mark.parametrize(
    "pipelined, expect",
    [
        # serial: one window, dispatch -> joined
        (False, 12.0 - 2.0),
        # pipelined: the two windows that blocked the thread, never the
        # wall interval that contains other billed windows
        (True, 0.25 + 3.0),
    ],
    ids=["serial", "pipelined"],
)
def test_span_bill(pipelined, expect):
    assert span_bill(
        pipelined, dispatch_elapsed=0.25, join_seconds=3.0,
        t_dispatch=2.0, join_t1=12.0,
    ) == expect


class _FakeSpan:
    def __init__(self):
        self.ended = None

    def end(self, **attrs):
        if self.ended is None:
            self.ended = attrs


class _Sink:
    """Stands in for the event log, tracer, guard and health monitor."""

    def __init__(self):
        self.emitted = []

    def emit(self, component, event, **fields):
        self.emitted.append((component, event))

    def uninstall(self):
        pass

    def set_write_through(self):
        pass

    def summary(self):
        return {"events": {}}


def test_crash_sweep_ends_the_pending_epochs_spans():
    """A crash while epoch e is bookkept leaves epoch e+1 dispatched and
    un-bookkept in ``pending``: the sweep closes both epochs' spans."""
    loop = EpochLoop.__new__(EpochLoop)
    loop.bookkeep_bracket, loop.dispatch_span, loop.epoch_span = (
        _FakeSpan(), _FakeSpan(), _FakeSpan()
    )
    # The success path already ended this one; the sweep must not
    # overwrite what it recorded.
    loop.dispatch_span.end(seconds=1.0)
    loop.pending = _SpanInFlight(
        epoch0=4, n_steps=1, state=None,
        dispatch_span=_FakeSpan(), epoch_span=_FakeSpan(),
    )
    spans = loop.in_flight_spans()
    assert spans[-2:] == [loop.pending.dispatch_span, loop.pending.epoch_span]

    tel = RunTelemetry.__new__(RunTelemetry)
    sink = _Sink()
    tel.guard = tel.events = tel.tracer = tel.health = sink
    tel.heartbeat = None
    tel.fit_span = _FakeSpan()
    tel.end_loop(
        completed=False, preempted=False, history=[], in_flight=spans
    )
    assert loop.pending.dispatch_span.ended == {"error": True}
    assert loop.pending.epoch_span.ended == {"error": True}
    assert loop.epoch_span.ended == {"error": True}
    assert loop.dispatch_span.ended == {"seconds": 1.0}
    assert tel.fit_span.ended["completed"] is False
    assert ("trainer", "fit_failed") in sink.emitted
    # No pending epoch (serial mode, or the tail already consumed): three.
    loop.pending = None
    assert len(loop.in_flight_spans()) == 3

"""The host side of a profiler trace: the program's own spans, per thread.

Read from the same ``.xplane.pb`` as ``reduce/trace.py``, with
``jax.profiler.ProfileData`` and nothing else. What the host plane
(``/host:CPU``) looks like (looked at by hand, PR 25): one line per thread
that made an event. Every thread Python started has a line named ``python``
(three in a traced ``Trainer.fit``: the trainer's, the resume tier's worker,
the prefetch pool's), so a line is told by what it holds and not by its name;
the runtime's own threads are ``main/<tid>``, ``pjrt-tpu-tasks/<tid>`` and
the like. Beside the annotations the program and the harness make, the
runtime writes its own events on the same lines (``PjitFunction(..)``,
``ParseArguments``, transfers): 400 on the trainer's line in a 40 s window.

- A program span is a host event that carries a ``span_id`` stat: the span
  recorder (``dct_tpu/observability/spans.py``) enters one
  ``TraceAnnotation`` per stack span, with the span's scalar attrs and its
  id as stats. A trace of a program without such spans (a parent commit's)
  has none, and every reader here then returns nothing.
- A marker is one of the harness's own annotations, named ``bench.*``.
- The trainer's thread is the line that holds ``bench.epoch_end.*`` (the
  harness stamps from the trainer's tracker call), else any marker.
- The window runs from the start of ``bench.trace_begin`` to the start of
  ``bench.trace_end``. An epoch of the window is the stretch between two
  consecutive stamps in it (``trace_begin``, then each ``epoch_end``).
- Depth is by containment among the program spans of one line;
  ``trainer.fit`` wraps the whole run and is left out, so the spans directly
  under it are the top level whether or not the session saw it open.
- A span open when the session starts or stops is not in the trace at all:
  every number here is from whole spans.

The device's clock runs a millisecond or two ahead of the host's
(``reduce/trace.py``), so where a host span is laid over a device gap it is
widened by ``SKEW_NS`` on both sides first.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass

import numpy as np

from benchmark.reduce import trace as tr

HOST_PLANE = "/host:CPU"
SPAN_STAT = "span_id"
ROOT_SPAN = "trainer.fit"
BEGIN, END, STAMP = "bench.trace_begin", "bench.trace_end", "bench.epoch_end."
SKEW_NS = 2e6

JOIN = "trainer.join"
CHECKPOINT = "trainer.checkpoint"
FILE_WRITE = "checkpoint.file_write"


@dataclass(frozen=True)
class Span:
    name: str
    start: float  # ns
    end: float
    depth: int
    stats: dict

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


class Thread:
    """One line of the host plane: its program spans in start order, with
    depth, and the harness's markers as (name, start_ns, end_ns)."""

    def __init__(self, name: str, events, markers):
        self.name = name
        self.markers = sorted(markers, key=lambda m: m[1])
        self.spans: list[Span] = []
        stack: list[tuple[float, float]] = []
        for ev_name, s, e, stats in sorted(
                events, key=lambda r: (r[1], -r[2])):
            while stack and stack[-1][1] <= s:
                stack.pop()
            if ev_name == ROOT_SPAN:
                continue
            self.spans.append(Span(ev_name, s, e, len(stack), stats))
            stack.append((s, e))

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def top(self) -> list[Span]:
        return [s for s in self.spans if s.depth == 0]


class Host:
    def __init__(self, threads: list[Thread]):
        self.threads = threads
        stamped = [t for t in threads
                   if any(m[0].startswith(STAMP) for m in t.markers)]
        marked = stamped or [t for t in threads if t.markers]
        #: The trainer's thread; nothing where the harness left no marker.
        self.trainer: Thread | None = marked[0] if marked else None
        marks = self.trainer.markers if self.trainer else []
        begin = [m[1] for m in marks if m[0] == BEGIN]
        end = [m[1] for m in marks if m[0] == END]
        #: (t0, t1) in ns, or nothing where either end is missing.
        self.window: tuple[float, float] | None = (
            (begin[0], end[-1]) if begin and end and end[-1] > begin[0]
            else None)

    @property
    def window_s(self) -> float | None:
        return (self.window[1] - self.window[0]) * 1e-9 if self.window else None

    def epochs(self) -> list[tuple[float, float]]:
        """(start_ns, end_ns) of each epoch of the window."""
        if not self.window:
            return []
        t0, t1 = self.window
        cuts = [t0] + [m[1] for m in self.trainer.markers
                       if m[0].startswith(STAMP) and t0 < m[1] <= t1]
        return list(zip(cuts, cuts[1:]))


def load(path: str) -> Host:
    from jax.profiler import ProfileData

    threads = []
    with warnings.catch_warnings():
        # "builtin type event_stats has no __module__ attribute"
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name != HOST_PLANE:
                continue
            for line in plane.lines:
                events, markers = [], []
                for ev in line.events:
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    if ev.name.startswith(tr.MARKER_PREFIX):
                        markers.append((ev.name, s, e))
                        continue
                    stats = dict(ev.stats)
                    if SPAN_STAT in stats:
                        events.append((ev.name, s, e, stats))
                if events or markers:
                    threads.append(Thread(line.name, events, markers))
    return Host(threads)


def overlap_ns(span: Span, a: float, b: float) -> float:
    return max(0.0, min(span.end, b) - max(span.start, a))


def _trainer_top(host: Host) -> list[Span]:
    """The trainer's top-level spans that touch the window; nothing where
    the trace has no window or the program made no span."""
    if not host.window or not host.trainer:
        return []
    t0, t1 = host.window
    return [s for s in host.trainer.top() if overlap_ns(s, t0, t1) > 0]


def host_epoch_seconds(host: Host) -> list[float]:
    """Per epoch of the window, the seconds the trainer's thread spends in
    top-level program spans other than its wait for the device."""
    top = [s for s in _trainer_top(host) if s.name != JOIN]
    if not top:
        return []
    return [sum(overlap_ns(s, a, b) for s in top) * 1e-9
            for a, b in host.epochs()]


def join_wait_share(host: Host) -> float | None:
    top = _trainer_top(host)
    if not top:
        return None
    t0, t1 = host.window
    waited = sum(overlap_ns(s, t0, t1) for s in top if s.name == JOIN)
    return waited / (t1 - t0)


def checkpoint_sections(host: Host) -> list[Span]:
    return [s for s in _trainer_top(host) if s.name == CHECKPOINT]


def disk_share(host: Host) -> float | None:
    """Seconds in ``checkpoint.file_write`` over seconds in the checkpoint
    sections that hold them."""
    sections = checkpoint_sections(host)
    total = sum(s.seconds for s in sections)
    if total <= 0:
        return None
    writes = sum(
        w.seconds for w in host.trainer.named(FILE_WRITE)
        if any(c.start <= w.start and w.end <= c.end for c in sections))
    return writes / total


def idle_unexplained_share(host: Host, trace: tr.Trace) -> float | None:
    """Of device 0's idle time in the window outside runs of a program, the
    share under no top-level program span of the trainer's thread. Idle time
    is what lies between two recorded ops: the device's tracer starts
    milliseconds after ``bench.trace_begin`` and, on four chips, stops
    milliseconds before ``bench.trace_end`` (3.6 ms, my chip run, PR 25),
    and what the device did there was not seen, so it is not counted."""
    top = _trainer_top(host)
    if not top or not trace.devices or not len(trace.devices[0].ops.start):
        return None
    dev = trace.devices[0]
    seen0 = max(host.window[0], float(dev.ops.start[0]))
    seen1 = min(host.window[1], float(dev.ops.end.max()))
    runs = tr.module_runs(dev)
    run_s = np.asarray([r[0] for r in runs])
    run_e = np.asarray([r[1] for r in runs])
    cover_s, cover_e = tr.union(
        np.asarray([s.start - SKEW_NS for s in top]),
        np.asarray([s.end + SKEW_NS for s in top]))
    idle = explained = 0.0
    for a, b in tr.gaps(trace, dev):
        if len(run_s) and ((run_s <= a) & (run_e >= b)).any():
            continue
        a, b = max(a, seen0), min(b, seen1)
        if b <= a:
            continue
        idle += b - a
        explained += float(np.clip(
            np.minimum(cover_e, b) - np.maximum(cover_s, a), 0, None).sum())
    return 1.0 - explained / idle if idle > 0 else None


def of(art: dict) -> Host | None:
    """The host side of this run's trace, read once a run; nothing where
    the run was not traced."""
    if "host" not in art:
        path = art.get("trace_dir") and tr.find_xplane(art["trace_dir"])
        art["host"] = load(path) if path else None
    return art["host"]

"""From a profiler trace (``.xplane.pb``) to the numbers the readers use.

Read with ``jax.profiler.ProfileData`` and nothing else. What the TPU's
trace looks like (looked at by hand, PR 23): one plane per chip named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed HLO
operation (fusions, custom calls, collectives, loop bodies unrolled in
time), named by the operation's whole HLO text (``%fusion.1 = bf16[..]
fusion(..), kind=kOutput, calls=..``), its line ``XLA Modules`` one event
per program execution, named ``jit_<function>(<fingerprint>)``. The host
plane (``/host:CPU``) holds ``TraceAnnotation`` spans on the line of the
thread that made them. All planes share one clock, in nanoseconds, but the
device's runs a millisecond or two ahead of the host's (a program starts
on the device "before" the host call that launched it), so host and device
events are only laid over each other at a scale of many milliseconds.

Definitions:

- busy: the union of the ``XLA Ops`` intervals of one device. An op that
  contains others (a ``while``) is on the same line, so the union, not the
  sum, is what counts.
- window: from the first to the last of the harness's ``bench.*`` markers
  and the device's own events (the profiler's start and stop, which take
  tens to hundreds of milliseconds themselves, are outside it).
- idle share: 1 - busy / window, averaged over the devices.
- gap: a maximal interval inside the window with no op on that device.
- exposed collective time: the part of the collective ops' intervals that
  no other op on that device overlaps.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARKER_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)", re.I)
#: Containers: ops whose interval is the sum of the ops inside them.
CONTAINER = re.compile(r"^(while|conditional|call)([.\d]*)$")


_HLO = re.compile(r"^%(\S+) = .*?\s([a-z][\w\-]*)\(")


def short_name(text: str) -> str:
    """``%fusion.1 = bf16[..] fusion(..)..`` -> ``fusion.1``; a custom call
    keeps its target (``custom-call.3:tpu_custom_call``). Anything that is
    not HLO text (a module's name) stays as it is."""
    m = _HLO.match(text)
    if not m:
        return text
    name = m.group(1)
    if m.group(2) == "custom-call":
        t = re.search(r'custom_call_target="([^"]+)"', text)
        if t:
            name += ":" + t.group(1)
    return name


class Line:
    """Events of one line as arrays, names shortened and interned."""

    def __init__(self, events):
        names: dict[str, int] = {}
        short: dict[str, str] = {}  # an instruction runs once a step
        idx, start, dur = [], [], []
        for text, s, d in events:
            name = short.get(text)
            if name is None:
                name = short[text] = short_name(text)
            idx.append(names.setdefault(name, len(names)))
            start.append(s)
            dur.append(d)
        self.names = list(names)
        self.idx = np.asarray(idx, np.int64)
        self.start = np.asarray(start, np.float64)
        self.dur = np.asarray(dur, np.float64)
        order = np.argsort(self.start, kind="stable")
        self.idx, self.start, self.dur = (
            self.idx[order], self.start[order], self.dur[order])

    @property
    def end(self):
        return self.start + self.dur

    def select(self, pred) -> np.ndarray:
        keep = np.asarray([bool(pred(n)) for n in self.names], bool)
        return keep[self.idx] if len(self.idx) else np.zeros(0, bool)


class Device:
    def __init__(self, ordinal: int, ops: Line, modules: Line):
        self.ordinal, self.ops, self.modules = ordinal, ops, modules


class Trace:
    def __init__(self, devices, markers, t0, t1):
        self.devices: list[Device] = devices
        #: (name, start_ns, dur_ns) of the harness's own annotations.
        self.markers: list[tuple[str, float, float]] = markers
        self.t0, self.t1 = float(t0), float(t1)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, markers = [], []
    t0, t1 = np.inf, -np.inf
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = {}
        for line in plane.lines:
            keep = m and line.name in (OPS_LINE, MODULES_LINE)
            events = []
            for ev in line.events:
                s, d = float(ev.start_ns), float(ev.duration_ns)
                if keep:
                    events.append((ev.name, s, d))
                elif not m and ev.name.startswith(MARKER_PREFIX):
                    markers.append((ev.name, s, d))
                else:
                    continue
                t0, t1 = min(t0, s), max(t1, s + d)
            if keep:
                lines[line.name] = Line(events)
        if m:
            devices.append(Device(
                int(m.group(1)),
                lines.get(OPS_LINE, Line([])),
                lines.get(MODULES_LINE, Line([])),
            ))
    devices.sort(key=lambda d: d.ordinal)
    markers.sort(key=lambda r: r[1])
    if not np.isfinite(t0):
        t0 = t1 = 0.0
    return Trace(devices, markers, t0, t1)


def union(start, end) -> tuple[np.ndarray, np.ndarray]:
    """Merged intervals of (start, end), start sorted ascending."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    run_end = np.maximum.accumulate(end)
    new = np.ones(len(start), bool)
    new[1:] = start[1:] > run_end[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(start) - 1)
    return start[first], run_end[last]


def busy_s(dev: Device) -> float:
    s, e = union(dev.ops.start, dev.ops.end)
    return float((e - s).sum()) * 1e-9


def busy_mean_s(trace: Trace) -> float:
    if not trace.devices:
        return 0.0
    return float(np.mean([busy_s(d) for d in trace.devices]))


def idle_share(trace: Trace) -> float | None:
    if not trace.devices or trace.window_s <= 0:
        return None
    return 1.0 - busy_mean_s(trace) / trace.window_s


def gaps(trace: Trace, dev: Device) -> list[tuple[float, float]]:
    """(start_ns, end_ns) of every idle interval of ``dev`` in the window."""
    s, e = union(dev.ops.start, dev.ops.end)
    edges_s = np.concatenate([[trace.t0], e])
    edges_e = np.concatenate([s, [trace.t1]])
    keep = edges_e > edges_s
    return list(zip(edges_s[keep].tolist(), edges_e[keep].tolist()))


def leaf_mask(line: Line) -> np.ndarray:
    """Ops that are not containers of other ops."""
    return ~line.select(lambda n: CONTAINER.match(n) is not None)


def op_seconds(dev: Device) -> dict[str, float]:
    """Device seconds by op name, containers left out."""
    line = dev.ops
    keep = leaf_mask(line)
    total = np.bincount(
        line.idx[keep], weights=line.dur[keep], minlength=len(line.names))
    return {
        n: float(t) * 1e-9 for n, t in zip(line.names, total) if t > 0
    }


def heaviest_op_starts(dev: Device) -> np.ndarray:
    """Start times (ns) of the leaf op that takes most of ``dev``'s time.
    Every instruction of a scanned step body runs once a step, so these
    are one per step."""
    seconds = op_seconds(dev)
    if not seconds:
        return np.zeros(0)
    line = dev.ops
    heaviest = max(seconds, key=seconds.get)
    return line.start[line.idx == line.names.index(heaviest)]


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ops that took most device time, averaged over the devices."""
    acc: dict[str, float] = {}
    for dev in trace.devices:
        for name, sec in op_seconds(dev).items():
            acc[name] = acc.get(name, 0.0) + sec / len(trace.devices)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in ranked]


def module_runs(dev: Device, contains: str | None = None):
    """(start_ns, end_ns, name) of program executions, in time order."""
    line = dev.modules
    out = []
    for i, s, d in zip(line.idx, line.start, line.dur):
        name = line.names[i]
        if contains is None or contains in name:
            out.append((float(s), float(s + d), name))
    return out


def share_of_ops(dev: Device, pred) -> float | None:
    """Time in leaf ops whose name satisfies ``pred`` over all leaf-op time."""
    line = dev.ops
    leaf = leaf_mask(line)
    total = float(line.dur[leaf].sum())
    if total <= 0:
        return None
    return float(line.dur[leaf & line.select(pred)].sum()) / total


def exposed_collective_s(dev: Device) -> float:
    """Seconds of collective ops that no other leaf op on ``dev`` overlaps."""
    line = dev.ops
    coll = line.select(lambda n: COLLECTIVE.match(n) is not None)
    other = leaf_mask(line) & ~coll
    cs, ce = union(line.start[coll], line.end[coll])
    os_, oe = union(line.start[other], line.end[other])
    total = float((ce - cs).sum())
    # Overlap of two sets of disjoint sorted intervals.
    overlap, j = 0.0, 0
    for a, b in zip(cs, ce):
        while j < len(os_) and oe[j] <= a:
            j += 1
        k = j
        while k < len(os_) and os_[k] < b:
            overlap += min(b, oe[k]) - max(a, os_[k])
            k += 1
    return (total - overlap) * 1e-9


def attribute_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The idle time of device 0 by what the host was doing. A gap inside
    one run of a program is ``inside_program``. Any other gap takes the last
    of the harness's ``bench.*`` markers the host had passed by the gap's
    midpoint: ``after_epoch_end`` is the host in what follows an epoch's
    stamp (tracker, events, both checkpoint tiers, the next dispatch)."""
    if not trace.devices:
        return []
    dev = trace.devices[0]
    runs = module_runs(dev)
    run_s = np.asarray([r[0] for r in runs])
    run_e = np.asarray([r[1] for r in runs])
    mark_t = np.asarray([m[1] for m in trace.markers])
    acc: dict[str, float] = {}
    for a, b in gaps(trace, dev):
        if len(run_s) and ((run_s <= a) & (run_e >= b)).any():
            label = "inside_program"
        else:
            i = int(np.searchsorted(mark_t, (a + b) / 2)) - 1
            if i < 0:
                label = "before_markers"
            else:
                kind = trace.markers[i][0][len(MARKER_PREFIX):]
                label = "after_" + re.sub(r"[.\d]+$", "", kind)
        acc[label] = acc.get(label, 0.0) + (b - a) * 1e-9
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in ranked]

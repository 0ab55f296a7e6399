"""Share of the traced window that the worst device spends in collective
operations while none of its other operations run."""

from benchmark.reduce import trace as tr

LAYER = "parallel"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    trace = art.get("trace")
    if not trace or not trace.devices or trace.window_s <= 0:
        return None
    worst = max(tr.exposed_collective_s(d) for d in trace.devices)
    return 100.0 * worst / trace.window_s

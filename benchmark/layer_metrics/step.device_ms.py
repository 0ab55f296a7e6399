"""Device time of one training step: on device 0, the median distance
between consecutive starts of the operation that takes most device time.
Every instruction of the scanned step body runs once a step, so that
distance is the step's period on the device, whole ops and the gaps between
them included, and it needs no whole epoch inside the trace."""

import numpy as np

from benchmark.reduce import trace as tr

LAYER = "step"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def step_period_ms(art):
    trace = art.get("trace")
    if not trace or not trace.devices:
        return None
    starts = tr.heaviest_op_starts(trace.devices[0])
    if len(starts) < 3:
        return None
    return float(np.median(np.diff(starts))) * 1e-6


def read(art):
    return step_period_ms(art)

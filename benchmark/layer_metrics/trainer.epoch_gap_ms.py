"""Median time the device waits between two runs of the epoch program: from
the end of epoch k's program to the start of epoch k+1's, on device 0."""

import numpy as np

from benchmark.reduce import trace as tr

LAYER = "trainer"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"
EPOCH_PROGRAM = "epoch_fused"


def read(art):
    trace = art.get("trace")
    if not trace or not trace.devices:
        return None
    runs = tr.module_runs(trace.devices[0], EPOCH_PROGRAM)
    waits = [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    return float(np.median(waits)) * 1e-6 if waits else None

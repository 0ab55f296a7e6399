"""Seconds an epoch costs the trainer's thread apart from its wait for the
device: per epoch of the traced window, the time in top-level program spans
other than ``trainer.join`` (data wait, dispatch call, bookkeeping, the
checkpoint section); median over the window's epochs. On the profiler's
clock. The cell turns host-bound when this passes the epoch's device time."""

import numpy as np

from benchmark.reduce import host as hr

LAYER = "trainer"
UNIT = "s"
SOURCE = "program_span"
MOVES = "fit_tokens_per_s"


def read(art):
    host = hr.of(art)
    per_epoch = hr.host_epoch_seconds(host) if host else []
    return float(np.median(per_epoch)) if per_epoch else None

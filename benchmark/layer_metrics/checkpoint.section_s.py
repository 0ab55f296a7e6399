"""Median length of the trainer's per-epoch checkpoint section
(``trainer.checkpoint``: host gather, deploy-tier writes, the resume tier's
snapshot) in the traced window, on the profiler's clock, so it can be laid
over the device's time."""

import numpy as np

from benchmark.reduce import host as hr

LAYER = "checkpoint"
UNIT = "s"
SOURCE = "program_span"
MOVES = "fit_tokens_per_s"


def read(art):
    host = hr.of(art)
    sections = hr.checkpoint_sections(host) if host else []
    return float(np.median([s.seconds for s in sections])) if sections else None

"""Routing balance, from the program's counters: rows of the worst held
expert of any layer over the rows an even spread would give each expert
(``N x k / routed experts``), per epoch, mean over the window's epochs. 1 is
even; the grouped products take what comes, so a skew costs tile padding
and, in the deployment, the slowest chip of the exchange."""

import numpy as np

LAYER = "step"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "fit_tokens_per_s"


def read(art):
    vals = [
        c["moe_rows_max_over_mean"]
        for c in getattr(art["window"], "counters", [])[1:]
        if "moe_rows_max_over_mean" in c]
    return float(np.mean(vals)) if vals else None

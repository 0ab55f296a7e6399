"""The program's own goodput ledger: mean ``goodput_fraction`` the trainer
logs to its tracker with each epoch inside the window. Its ``train_step``
seconds are a dispatch-to-consume window on the host and not device time,
so its distance from ``100 - device.idle_share`` is evidence for the tracing
work (D7), not a second reading of the same thing."""

import numpy as np

LAYER = "trainer"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "fit_tokens_per_s"


def read(art):
    # The first stamp opens the window; the epochs after it are inside.
    vals = [v for v in art["window"].goodput[1:] if v is not None]
    return 100.0 * float(np.mean(vals)) if vals else None

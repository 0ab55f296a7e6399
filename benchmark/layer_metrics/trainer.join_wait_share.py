"""Share of the traced window the trainer's thread spends in
``trainer.join``, waiting for the device: the host's slack. Near 0 the host
is the bottleneck. On the profiler's clock."""

from benchmark.reduce import host as hr

LAYER = "trainer"
UNIT = "%"
SOURCE = "program_span"
MOVES = "fit_tokens_per_s"


def read(art):
    host = hr.of(art)
    share = hr.join_wait_share(host) if host else None
    return None if share is None else 100.0 * share

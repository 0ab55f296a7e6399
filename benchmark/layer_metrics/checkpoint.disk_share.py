"""Share of the checkpoint sections of the traced window spent in
``checkpoint.file_write`` (open, write, rename of the deploy tier's files):
disk against gather, serialisation, hash and snapshot."""

from benchmark.reduce import host as hr

LAYER = "checkpoint"
UNIT = "%"
SOURCE = "program_span"
MOVES = "fit_tokens_per_s"


def read(art):
    host = hr.of(art)
    share = hr.disk_share(host) if host else None
    return None if share is None else 100.0 * share

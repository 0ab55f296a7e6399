"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""

from benchmark.reduce import trace as tr

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    share = tr.idle_share(art["trace"]) if art.get("trace") else None
    return None if share is None else 100.0 * share

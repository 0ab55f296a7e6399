"""Share of device-0 operation time under ``shortconv``: the gated short
convolution operator (input projection, three taps, gate, output
projection), forward and backward, in the layers that carry it."""

from benchmark.reduce import scopes

LAYER = "step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    return scopes.scope_share(art, "shortconv")

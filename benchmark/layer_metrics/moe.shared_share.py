"""Share of device-0 operation time under ``moe.shared``: the shared
expert every token passes beside its routed ones (three GEMMs and the
gate), forward and backward, in the layers that carry one. A program
without the scope gives nothing."""

from benchmark.reduce import scopes

LAYER = "step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    return scopes.scope_share(art, "moe.shared")

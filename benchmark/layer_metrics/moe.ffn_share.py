"""Share of device-0 operation time under the routed-expert layers' four
scopes (``moe.route``, ``moe.dispatch``, ``moe.experts``, ``moe.combine``),
forward and backward: what the mechanism this configuration adds costs of
the step. Joined to the trace through the saved HLO text
(``benchmark/reduce/scopes.py``)."""

from benchmark.reduce import scopes

LAYER = "step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    return scopes.scope_share(
        art, "moe.route", "moe.dispatch", "moe.experts", "moe.combine")

"""Share of device-0 operation time under the latent-attention layers'
three scopes, forward and backward: ``mla.project`` (the query and the
compressed key/value projections, the latent's norm, the rotation),
``mla.attend`` (the flash kernels) and ``mla.out`` (the output
projection). Joined to the trace through the saved HLO text
(``benchmark/reduce/scopes.py``); a program without the scopes gives
nothing."""

from benchmark.reduce import scopes

LAYER = "step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    return scopes.scope_share(art, "mla.project", "mla.attend", "mla.out")

"""Share of device-0 operation time under ``dense_mlp``, forward and
backward: the dense feed-forward block (gelu or SwiGLU) of every layer that
has one; routed layers have none. Joined to the trace through the
program's text (``benchmark/reduce/program_text.py``)."""

from benchmark.reduce import program_text, scopes

LAYER = "step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    return scopes.scope_share(program_text.joined(art), "dense_mlp")

"""Share of device-0 operation time under the attention module ``attn``,
forward and backward: the fused query/key/value projection, the rotation,
the three flash kernels and the output projection of every attention
layer. Joined to the trace through the program's text
(``benchmark/reduce/program_text.py``)."""

from benchmark.reduce import program_text, scopes

LAYER = "step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    return scopes.scope_share(program_text.joined(art), "attn")

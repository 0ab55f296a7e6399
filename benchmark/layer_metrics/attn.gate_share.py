"""Share of device-0 operation time under ``attn.gate``, forward and
backward: the sigmoid gate on every attention head's output (its
projection from the layer's normed input, the sigmoid and the product),
inside the ``attn.full`` / ``attn.sliding`` scopes. Joined to the trace
through the program's text (``benchmark/reduce/program_text.py``); a
program without the scope (a model without the gate, or the parent) gives
nothing."""

from benchmark.reduce import program_text, scopes

LAYER = "step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    return scopes.scope_share(program_text.joined(art), "attn.gate")

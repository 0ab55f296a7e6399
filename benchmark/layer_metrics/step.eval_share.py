"""Share of device-0 operation time under ``step.eval``: the validation
pass inside the epoch program, which trains no token. Joined to the trace
through the program's text (``benchmark/reduce/program_text.py``)."""

from benchmark.reduce import program_text, scopes

LAYER = "step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    return scopes.scope_share(program_text.joined(art), "step.eval")

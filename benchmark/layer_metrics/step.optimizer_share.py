"""Share of device-0 operation time under ``step.optimizer``: the
optimizer's update of every step (the clip's global norm, Adam's moments,
the f32 masters and the parameters' own steps after it,
``dct_tpu/train/steps.py:_optimizer_step``). Joined to the trace through
the program's text (``benchmark/reduce/program_text.py``)."""

from benchmark.reduce import program_text, scopes

LAYER = "step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    return scopes.scope_share(program_text.joined(art), "step.optimizer")

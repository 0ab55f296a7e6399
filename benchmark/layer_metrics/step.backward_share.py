"""Share of device-0 operation time in the backward pass: instructions
whose ``op_name`` puts ``step.loss`` (the training objective's forward,
``dct_tpu/train/steps.py``) under a ``transpose(..)``, the flash kernels'
backward calls and the gradients' all-reduce among them. Joined to the
trace through the program's text (``benchmark/reduce/program_text.py``)."""

from benchmark.reduce import program_text

LAYER = "step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    return program_text.op_name_share(
        art, lambda op: program_text.transposed(op, "step.loss"))

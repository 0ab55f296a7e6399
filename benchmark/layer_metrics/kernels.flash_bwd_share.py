"""Share of device-0 operation time in the flash backward's two kernels'
Mosaic calls, dK/dV and dQ; told from the forward and from XLA's
grouped-product calls in the program's text
(``benchmark/reduce/program_text.py``)."""

from benchmark.reduce import program_text

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    return program_text.kernel_share(art, "flash_bwd_dkdv", "flash_bwd_dq")

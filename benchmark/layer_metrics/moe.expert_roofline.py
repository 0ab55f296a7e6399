"""The grouped expert products against the chip's bf16 peak: operations of
the three grouped GEMMs, forward and backward, over the rows the program's
counters say were ROUTED to held experts (mean per step over the window's
epochs; ``benchmark/flops/lfm2_moe.py``), over the device time under
``moe.experts`` per traced step. Rows the kernel pads to its tiles, and the
silu and the product between the GEMMs, are time without counted
operations, so the share is a floor of the kernel's own."""

from benchmark import peaks
from benchmark.manifest import load_flops
from benchmark.reduce import scopes
from benchmark.reduce import trace as tr

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    under = scopes.scope_seconds(art, "moe.experts")
    counted = [c for c in getattr(art["window"], "counters", [])[1:] if c]
    flops = load_flops(art["config"])
    if not under or not counted or flops is None:
        return None
    # Every instruction of the scanned step body runs once a step.
    steps = len(tr.heaviest_op_starts(art["trace"].devices[0]))
    rows_per_step = sum(c["moe_rows"] for c in counted) / (
        len(counted) * art["plan"].steps)
    rate = flops.expert_train_flops(art["config"], rows_per_step) * steps \
        / under
    return 100.0 * rate / peaks.peaks(
        art["device"]["kind"])["bf16_flops_per_s"]

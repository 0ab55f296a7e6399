"""The flash kernels of latent attention against the chip's bf16 peak:
the USEFUL operations of QK^T and PV (causal, scores over the whole 192-wide
query/key, values 128 wide; ``benchmark/flops/moonlight_moe.py``; the
backward's second pass over the scores and any padding are not counted),
for the training steps the traced window holds and the forward of its
validation batches, over device 0's time in the flash kernels' Mosaic
calls. The trace prints a Mosaic call under the innermost name scope it was
traced in: ``mla.attend.<n>`` in the latent layer (``attn.<n>`` where a
layer calls the kernels under its module's name alone); XLA's
grouped-product calls are named ``ragged-dot-*`` and are not counted. A
configuration whose operation counts have no ``attention_train_flops``, or
a trace without such calls, gives nothing."""

from benchmark import peaks
from benchmark.manifest import load_flops
from benchmark.reduce import trace as tr

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def is_flash(name: str) -> bool:
    return name.startswith(("mla.attend.", "attn.")) and name.endswith(
        ":tpu_custom_call")


def read(art):
    trace, flops = art.get("trace"), load_flops(art["config"])
    if not trace or not trace.devices or not hasattr(
            flops, "attention_train_flops"):
        return None
    dev = trace.devices[0]
    under = sum(s for op, s in tr.op_seconds(dev).items() if is_flash(op))
    if not under:
        return None
    plan = art["plan"]
    sequence = flops.attention_train_flops(art["config"], plan.seq_len)
    # Every instruction of the scanned step body runs once a step; an
    # epoch's validation batches run the forward alone, a third of it.
    steps = len(tr.heaviest_op_starts(dev))
    validated = art["end_to_end"].get("epochs", 0) * plan.val_batches
    useful = plan.batch_per_chip * sequence * (steps + validated / 3.0)
    return 100.0 * useful / under / peaks.peaks(
        art["device"]["kind"])["bf16_flops_per_s"]

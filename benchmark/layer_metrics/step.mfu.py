"""Model FLOP/s utilisation of the step while it runs: operations the
forward and backward passes of one step's tokens require (the family's file
under benchmark/flops/, recomputation not counted) over ``step.device_ms``,
chips and the published bf16 peak. A configuration that names no such file
reports nothing."""

from benchmark import peaks
from benchmark.manifest import load_flops, load_layer_metric

LAYER = "step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def read(art):
    ms = load_layer_metric("step.device_ms").step_period_ms(art)
    flops = load_flops(art["config"])
    if ms is None or flops is None:
        return None
    plan = art["plan"]
    per_token = flops.train_flops_per_token(art["config"], plan.seq_len)
    rate = plan.global_batch * plan.seq_len / (ms * 1e-3)
    return 100.0 * peaks.mfu(
        rate, per_token, art["device"]["kind"], plan.data_parallel)

"""Share of device-0 operation time spent in Mosaic custom calls: the three
flash-attention kernels (forward, dK/dV, dQ). They carry no name of their
own yet; the trace prints them as ``attn.<n>`` with
``custom_call_target="tpu_custom_call"``, which is what this matches."""

from benchmark.reduce import trace as tr

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "fit_tokens_per_s"


def is_mosaic(name: str) -> bool:
    return name.endswith(":tpu_custom_call")


def read(art):
    trace = art.get("trace")
    if not trace or not trace.devices:
        return None
    share = tr.share_of_ops(trace.devices[0], is_mosaic)
    return None if share is None else 100.0 * share

"""Length of the first ``trainer.dispatch_call``: the host-blocking call of
the epoch program on its first dispatch (trace, then AOT load or compile).
Before the traced window, so from the program's JSONL spans."""

from benchmark.manifest import load_layer_metric

LAYER = "trainer"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(art):
    calls = [s for s in art["spans"]
             if s.get("name") == "trainer.dispatch_call"
             and s.get("attrs", {}).get("first")]
    if not calls:
        return None
    return load_layer_metric("trainer.startup_s").span_seconds(
        min(calls, key=lambda s: s["t0"]))

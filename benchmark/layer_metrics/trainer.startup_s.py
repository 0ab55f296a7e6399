"""Length of ``trainer.startup``: ``fit()``'s entry to its loop (dataset
load, model and state creation, resume restore, AOT wiring). It ends before
the traced window opens, so it is read from the program's JSONL spans: the
``seconds`` its bracket read off the goodput ledger's clock, else the span's
wall-clock length."""

LAYER = "trainer"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def span_seconds(span):
    return float(span.get("attrs", {}).get("seconds", span["t1"] - span["t0"]))


def read(art):
    spans = [s for s in art["spans"] if s.get("name") == "trainer.startup"]
    return span_seconds(min(spans, key=lambda s: s["t0"])) if spans else None

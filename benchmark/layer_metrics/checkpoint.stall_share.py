"""Share of the window the trainer's thread spends in its checkpoint section
(host gather, deploy-tier write, the resume tier's device-to-host snapshot
and the join of the previous asynchronous write): the program's
``trainer.checkpoint`` spans, the interval its goodput ledger books as
``checkpoint``."""

LAYER = "checkpoint"
UNIT = "%"
SOURCE = "program_span"
MOVES = "fit_tokens_per_s"


def read(art):
    win = art["window"]
    if not win.start_wall or len(win.stamps) < 2:
        return None
    t0 = win.start_wall
    t1 = t0 + (win.stamps[-1] - win.start)
    spent = sum(
        min(s["t1"], t1) - max(s["t0"], t0) for s in art["spans"]
        if s.get("name") == "trainer.checkpoint"
        and s["t1"] > t0 and s["t0"] < t1
    )
    return 100.0 * spent / (t1 - t0)

"""Padding of the grouped expert layers, from the program's counters: rows
the layers ran their engine on (``moe_rows_bound``, summed over layers and
steps) over the rows the router sent to the held experts (``moe_rows``),
over the window's epochs. The gather, the masks, the silu and the weighted
scatter-add run on the first, the grouped products on the second: 1 is no
padding. A program that does not count the first (the layer under one
fixed bound) gives ``None``."""

LAYER = "step"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "fit_tokens_per_s"


def read(art):
    counted = [
        c for c in getattr(art["window"], "counters", [])[1:]
        if "moe_rows_bound" in c and c.get("moe_rows")]
    if not counted:
        return None
    return sum(c["moe_rows_bound"] for c in counted) / sum(
        c["moe_rows"] for c in counted)

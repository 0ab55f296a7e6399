"""Row-tile padding of the grouped kernel, from the program's counters: rows
the row tiles of one forward grouped product cover (``moe_rows_tiled``,
summed over the chunks, layers and steps that ran) over the rows the router
sent to the held experts (``moe_rows``), over the window's epochs. A tile
that two experts' rows share is computed once for each, so every group
boundary inside a tile costs up to a tile of rows: 1 is no padding. A
program that does not count the first (before the Pallas grouped matmul)
gives ``None``."""

LAYER = "kernels"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "fit_tokens_per_s"


def read(art):
    counted = [
        c for c in getattr(art["window"], "counters", [])[1:]
        if "moe_rows_tiled" in c and c.get("moe_rows")]
    if not counted:
        return None
    return sum(c["moe_rows_tiled"] for c in counted) / sum(
        c["moe_rows"] for c in counted)

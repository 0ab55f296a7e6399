"""Device time by ``jax.named_scope``, where the trace does not carry it.

The v5e's ``XLA Ops`` events are named by the instruction's HLO text WITHOUT
its metadata (looked at with ``tools/dump_trace.py``, PR 27: no
``op_name=`` in the text and none among the stats), so a scope cannot be
read off the trace alone. The driver that wants scopes saves the optimized
HLO text of the program that ran (``art["hlo_text"]``); there every
instruction carries ``metadata={op_name="jit(..)/../<scope>/..op"}``, the
backward pass's as ``transpose(jvp(<scope>))``. Instruction names are
unique in a module, and the trace's short names (``reduce/trace.py``) are
those names, so the two join by name. A fusion carries the metadata of one
of the instructions fused into it (its root), so an op fused across a
scope's edge counts wholly on one side.

A program without the scopes (the parent of the PR that adds them), a run
whose driver saved no text, or a CPU trace without a device plane, gives
``None``: the reader's metric is left out of the line.
"""

from __future__ import annotations

import functools
import re

from benchmark.reduce import trace as tr

_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*?op_name=\"([^\"]*)\"")


@functools.lru_cache(maxsize=2)
def instruction_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> its ``op_name`` metadata (one parse a text:
    several readers ask for the same program's)."""
    out: dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


#: XLA's ragged-dot rewrite names the two Mosaic calls it emits itself
#: (``ragged-dot-none.<n>``, ``ragged-dot-metadata.<n>``) and gives them its
#: own ``op_name``, so a grouped product loses the scope it was written in.
#: The only grouped products of the programs the benchmark runs are the
#: routed experts', under ``moe.experts``.
REWRITTEN = {"ragged-dot-none": "moe.experts",
             "ragged-dot-metadata": "moe.experts"}


def in_scope(op_name: str, scope: str) -> bool:
    """``scope`` as a whole component of the name stack: ``moe.route``
    matches ``../moe.route/dot_general`` and ``transpose(jvp(moe.route))``
    and not ``moe.routes`` or flax's ``moe._grouped``."""
    op_name = REWRITTEN.get(op_name, op_name)
    return re.search(
        r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])", op_name
    ) is not None


def scope_seconds(art: dict, *scopes: str) -> float | None:
    """Device-0 leaf-op seconds of the traced window under any of
    ``scopes``."""
    trace, text = art.get("trace"), art.get("hlo_text")
    if not trace or not trace.devices or not text:
        return None
    names = instruction_scopes(text)

    def under(op_name: str) -> bool:
        return any(in_scope(op_name, s) for s in scopes)

    if not any(under(v) for v in names.values()):
        return None
    seconds = tr.op_seconds(trace.devices[0])
    return sum(
        sec for op, sec in seconds.items()
        if under(names.get(op.split(":", 1)[0], "")))


def scope_share(art: dict, *scopes: str) -> float | None:
    """``scope_seconds`` over all leaf-op seconds of device 0, in %."""
    under = scope_seconds(art, *scopes)
    if under is None:
        return None
    total = sum(tr.op_seconds(art["trace"].devices[0]).values())
    return 100.0 * under / total if total > 0 else None

"""The optimized HLO text of the epoch program a run ran, and what the
readers of the step's scopes and of the flash kernels take from it.

Where the text comes from: ``art["hlo_text"]`` where the driver saved it
(``fit_routed``); otherwise the file the trainer writes on every exit of
``fit`` when its event log is on, ``<events dir>/programs/scan_k1.hlo.txt``
(``dct_tpu/train/telemetry.py:RunTelemetry.write_programs``). Driver
``fit`` keeps its events beside its trace, under one work directory. A
program that writes no such file (the parent of the change that added it)
gives ``None``, and so does every reader below: its metric is left out of
the line.

The three flash kernels print in the trace under the innermost scope they
were traced in (``attn.<n>``, ``mla.attend.<n>``, ``attn.sliding.<n>``),
one name for all three. A Pallas ``name=`` would push a scope of its own
and rename them, so the text tells them apart instead: a flash call is a
Mosaic custom call whose ``op_name`` ends in ``pallas_call`` (XLA's
grouped-product calls are named ``ragged-dot-*``); the forward is traced
outside any ``transpose(..)`` (the training step's, which also returns
the log-sum-exp, and the validation pass's); of the two under the custom
VJP's backward, dK/dV returns a pair and dQ one array.
"""

from __future__ import annotations

import functools
import os
import re

from benchmark.reduce import scopes
from benchmark.reduce import trace as tr

#: The epoch program's key in the trainer's AOT store
#: (``dct_tpu/train/steps.py:EPOCH_PROGRAM_KEY``).
PROGRAM_KEY = "scan_k1"

_CALL = re.compile(
    r'^\s*(?:ROOT )?%(\S+) = (\(?).*? custom-call\(.*?'
    r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"')


def path(art: dict) -> str | None:
    """Where the trainer wrote the text, from the run's trace directory."""
    trace_dir = art.get("trace_dir")
    if not trace_dir:
        return None
    return os.path.join(os.path.dirname(trace_dir), "events", "programs",
                        PROGRAM_KEY + ".hlo.txt")


def text(art: dict) -> str | None:
    if art.get("hlo_text"):
        return art["hlo_text"]
    where = path(art)
    if where is None or not os.path.exists(where):
        return None
    with open(where) as f:
        return f.read()


def joined(art: dict) -> dict:
    """``art`` with the program's text where ``reduce/scopes.py`` reads it."""
    return {**art, "hlo_text": text(art)}


def transposed(op_name: str, scope: str) -> bool:
    """``scope`` under a ``transpose(..)``: the backward pass of it."""
    return any(part.startswith("transpose(") and scopes.in_scope(part, scope)
               for part in op_name.split("/"))


def _share(art: dict, names: dict, pred) -> float | None:
    """Device-0 leaf-op time of the instructions of ``names`` (instruction
    -> label) whose label satisfies ``pred``, over all leaf-op time, in %;
    ``None`` without a trace, a device plane, or any such instruction."""
    trace = art.get("trace")
    if not trace or not trace.devices or not any(
            pred(v) for v in names.values()):
        return None
    seconds = tr.op_seconds(trace.devices[0])
    total = sum(seconds.values())
    under = sum(sec for op, sec in seconds.items()
                if pred(names.get(op.split(":", 1)[0], "")))
    return 100.0 * under / total if total > 0 else None


def op_name_share(art: dict, pred) -> float | None:
    """The share of the instructions whose ``op_name`` satisfies ``pred``."""
    hlo = text(art)
    return None if hlo is None else _share(
        art, scopes.instruction_scopes(hlo), pred)


@functools.lru_cache(maxsize=2)
def flash_kernels(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``flash_fwd``, ``flash_bwd_dkdv`` or
    ``flash_bwd_dq``, for every flash call of the text."""
    out: dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _CALL.match(line)
        if not m or m.group(3).rsplit("/", 1)[-1] != "pallas_call":
            continue
        name, pair, op_name = m.groups()
        if not any(p.startswith("transpose(") for p in op_name.split("/")):
            out[name] = "flash_fwd"
        else:
            out[name] = "flash_bwd_dkdv" if pair else "flash_bwd_dq"
    return out


def kernel_share(art: dict, *kernels: str) -> float | None:
    """The share of the flash calls of the named kernels."""
    hlo = text(art)
    return None if hlo is None else _share(
        art, flash_kernels(hlo), lambda k: k in kernels)

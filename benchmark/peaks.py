"""The published peaks of the chips, and utilisation against them."""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown kind is an error."""
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(k for k in table if k != 'source')}")
    return table[device_kind]


def mfu(tokens_per_s: float, flops_per_token: float, device_kind: str,
        chips: int) -> float:
    """Model FLOP/s utilisation as a share (0..1) of chips x bf16 peak."""
    return tokens_per_s * flops_per_token / (
        chips * peaks(device_kind)["bf16_flops_per_s"])

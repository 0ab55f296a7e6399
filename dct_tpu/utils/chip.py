"""One process for each chip.

A chip belongs to one process at a time: a parent that has touched JAX
holds it, and a second process that needs it then fails or hangs inside
backend init with no timeout. The places that can start such a second
process check here at start-up and refuse, loudly, instead.

The only thing a parent can know without touching JAX itself is what the
environment asks for: ``JAX_PLATFORMS=cpu`` (how tests and drills ask
for CPU) means any number of processes may share the host; anything
else means the processes of this environment will take the accelerator
JAX finds. This module must stay importable without importing jax —
the supervisor, loop and scheduler parents use it to stay off the chip.
"""

from __future__ import annotations

import os
from collections.abc import Mapping


class ChipContentionError(RuntimeError):
    """Two processes of one host would need the same chip."""


def pinned_to_cpu(env: Mapping | None = None) -> bool:
    """True when ``JAX_PLATFORMS`` pins processes of ``env`` to CPU."""
    env = os.environ if env is None else env
    first = str(env.get("JAX_PLATFORMS") or "").split(",")[0]
    return first.strip().lower() == "cpu"


def refuse_shared_chip(what: str, env: Mapping | None = None) -> None:
    """Raise :class:`ChipContentionError` naming ``what`` unless ``env``
    pins its processes to CPU."""
    if not pinned_to_cpu(env):
        raise ChipContentionError(
            f"{what}: more than one process of this host would need the "
            "accelerator, and a chip belongs to one process at a time "
            "(the second hangs or fails in backend init). Run it in one "
            "process, or set JAX_PLATFORMS=cpu to keep every process "
            "off the chip."
        )

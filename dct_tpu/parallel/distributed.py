"""Multi-host rendezvous: the TPU-native replacement for gloo's TCP store.

Reference behavior being replaced: Lightning reads MASTER_ADDR / MASTER_PORT
/ NODE_RANK / WORLD_SIZE from container env (docker-compose.yml:121-124,
140-143) and calls ``torch.distributed.init_process_group("gloo")`` with a
TCP store at pytorch-master:29500 during ``trainer.fit``
(jobs/train_lightning_ddp.py:136,143).

TPU-native: ``jax.distributed.initialize(coordinator_address, num_processes,
process_id)``. After it returns, ``jax.devices()`` spans every host's chips
and jitted collectives ride ICI/DCN. We accept the reference's env names so
the same compose files / DAG launch blocks work unchanged.
"""

from __future__ import annotations

import jax

from dct_tpu.config import DistributedConfig


def initialize_from_env(cfg: DistributedConfig | None = None) -> DistributedConfig:
    """Initialize jax.distributed when WORLD_SIZE > 1; no-op otherwise.

    Idempotent: safe to call twice (the zombie-cleanup concern the reference
    handles with pkill, dags/2_pytorch_training.py:29-38, does not arise —
    there is no long-lived port-bound store to leak; the coordinator dies
    with process 0).
    """
    cfg = cfg or DistributedConfig.from_env()
    if cfg.num_processes <= 1:
        return cfg
    if cfg.coordinator_address is None:
        raise ValueError(
            "WORLD_SIZE > 1 but no coordinator address: set MASTER_ADDR "
            "(+ MASTER_PORT) or DCT_COORDINATOR_ADDRESS"
        )
    # Multi-process CPU rigs (the two-container test bed, CI) need the
    # gloo cross-host collective backend; the default CPU backend
    # refuses multiprocess computations outright. Must be set BEFORE
    # initialize — config.update is authoritative where the env var is
    # not reliably honored. Platform is read from config/env, NOT
    # jax.default_backend(): that call would initialize the backends
    # ahead of jax.distributed.initialize.
    import os as _os

    platforms = (
        getattr(jax.config, "jax_platforms", None)
        or _os.environ.get("JAX_PLATFORMS", "")
        or ""
    )
    if platforms.split(",")[0].strip().lower() == "cpu":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    try:
        jax.distributed.initialize(
            coordinator_address=cfg.coordinator_address,
            num_processes=cfg.num_processes,
            process_id=cfg.process_id,
        )
    except RuntimeError as e:  # already initialized
        if "already" not in str(e).lower():
            raise
    return cfg


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_coordinator() -> bool:
    """The rank-0 gate for side effects (checkpoint writes, MLflow upload),
    the analog of ``trainer.global_rank == 0``
    (jobs/train_lightning_ddp.py:146)."""
    return jax.process_index() == 0

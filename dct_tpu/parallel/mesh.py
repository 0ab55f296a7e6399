"""Device mesh + sharding helpers: the DDP/TP/SP substrate.

The reference's process topology is fixed at deploy time: two containers,
one rank each, gradients all-reduced by gloo (docker-compose.yml:115-151,
jobs/train_lightning_ddp.py:136). The TPU-native topology is a named
``jax.sharding.Mesh`` over all addressable devices:

- ``data``  — batch-sharded axis (the DDP analog; grads all-reduce over ICI),
- ``model`` — tensor-parallel axis (extension; used by the transformer family),
- ``seq``   — sequence/context-parallel axis (ring attention).

Everything downstream is declarative: annotate the batch as sharded over
``data`` and params as replicated (or sharded over ``model``), and XLA
inserts the collectives. No NCCL/gloo calls to translate.
"""

from __future__ import annotations

import math
import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dct_tpu.config import MeshConfig

AXES = ("data", "model", "seq", "pipe")


def make_mesh(
    cfg: MeshConfig | None = None, devices=None, *, allow_subset: bool = False
) -> Mesh:
    """Build the 4-axis (data, model, seq, pipe) mesh; axis size -1
    absorbs all remaining devices.

    The mesh must cover every device: silently training on a subset would
    idle chips (or, multi-host, exclude another process's devices from the
    collectives). Test rigs that want a small mesh on a big device pool opt
    in explicitly with ``allow_subset=True``.
    """
    cfg = cfg or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    sizes = {
        "data": cfg.data, "model": cfg.model, "seq": cfg.seq,
        "pipe": cfg.pipe,
    }
    fixed = math.prod(s for s in sizes.values() if s != -1)
    free = [a for a, s in sizes.items() if s == -1]
    if len(free) > 1:
        raise ValueError("At most one mesh axis may be -1")
    if free:
        if n % fixed != 0:
            raise ValueError(f"{n} devices not divisible by fixed axes {sizes}")
        sizes[free[0]] = n // fixed
    need = math.prod(sizes.values())
    if need > n:
        raise ValueError(f"Mesh {sizes} needs {need} devices, have {n}")
    if need != n and not allow_subset:
        raise ValueError(
            f"Mesh {sizes} covers {need} of {n} devices; pass "
            "allow_subset=True if a partial mesh is intended (test rigs)"
        )
    return Mesh(_device_grid([sizes[a] for a in AXES], devices), AXES)


def _grid_blocks_contiguous(grid) -> bool:
    """True when every process's data-axis rows form a contiguous aligned
    block — the layout :func:`process_data_block` requires to feed each
    host the rows its devices own."""
    by_pid: dict[int, set] = {}
    for idx in np.ndindex(grid.shape):
        by_pid.setdefault(grid[idx].process_index, set()).add(idx[0])
    data_size = grid.shape[0]
    for rows_set in by_pid.values():
        rows = sorted(rows_set)
        n = len(rows)
        if (
            rows != list(range(rows[0], rows[0] + n))
            or rows[0] % n
            or data_size % n
        ):
            return False
    return True


def _device_grid(shape: list, devices: list):
    """Device layout for the mesh grid.

    On real TPU devices covering the whole mesh, defer to
    ``mesh_utils.create_device_mesh``: it maps the logical axes onto the
    physical ICI torus so each axis's collectives ride neighbor links
    (naive enumeration order can put a ring's neighbors on opposite
    corners of the slice — the scaling-book layout rule). Disable with
    ``DCT_ICI_MESH=0``.

    The ICI layout is only kept when every process's data-axis rows stay
    a contiguous aligned block (the input-pipeline contract
    :func:`process_data_block` enforces) — a torus mapping that
    interleaves a host's rows falls back to enumeration order instead of
    aborting training at startup. A shape ``create_device_mesh`` cannot
    place raises: asking for enumeration order is ``DCT_ICI_MESH=0``,
    said out loud. CPU rigs and explicit subsets always use enumeration
    order, which tests rely on. :func:`layout_of` reads back which one a
    mesh got.
    """
    import sys

    need = math.prod(shape)
    want_ici = os.environ.get("DCT_ICI_MESH", "1").strip().lower() not in (
        "0", "false", "no", "off"
    )
    if (
        want_ici
        and getattr(devices[0], "platform", "") == "tpu"
        and need == len(devices)
    ):
        from jax.experimental import mesh_utils

        grid = mesh_utils.create_device_mesh(shape, devices=devices)
        if _grid_blocks_contiguous(grid):
            return grid
        sys.stderr.write(
            "[dct_tpu] ICI-aware layout interleaves a process's "
            "data-axis rows; falling back to enumeration order\n"
        )
    return np.array(devices[:need]).reshape(shape)


def layout_of(mesh: Mesh) -> str:
    """``"enumeration"`` when the mesh holds its devices in ascending-id
    (``jax.devices()``) order, ``"ici"`` when ``create_device_mesh``
    re-ordered them onto the torus."""
    ids = [d.id for d in mesh.devices.flat]
    return "enumeration" if ids == sorted(ids) else "ici"


def process_data_block(mesh: Mesh) -> tuple[int, int]:
    """How the global batch splits across PROCESSES: (num_blocks, my_block).

    The data loader must feed each process exactly the rows its addressable
    devices own under :func:`batch_sharding`. For pure DP every process owns
    distinct data-axis rows -> (process_count, process_index) semantics. For
    tensor/sequence parallelism spanning processes, several processes share
    the same data rows (the batch is replicated across them), so they share
    a block and each must supply the identical full block.
    """
    pid = jax.process_index()
    grid = mesh.devices  # [data, model, seq, pipe]
    my_rows = sorted(
        {
            idx[0]
            for idx in np.ndindex(grid.shape)
            if grid[idx].process_index == pid
        }
    )
    if not my_rows:
        raise ValueError(f"process {pid} owns no devices in mesh {mesh}")
    rows = len(my_rows)
    data_size = grid.shape[0]
    if (
        my_rows != list(range(my_rows[0], my_rows[0] + rows))
        or my_rows[0] % rows
        or data_size % rows
    ):
        raise ValueError(
            f"process {pid}'s data-axis rows {my_rows} are not a contiguous "
            f"aligned block of the {data_size}-row data axis; reorder the "
            "mesh devices so each process's rows are contiguous"
        )
    return data_size // rows, my_rows[0] // rows


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch dim sharded over ``data``; feature dims replicated."""
    return NamedSharding(mesh, P("data"))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_state(state, mesh: Mesh):
    """Replicate the train state across the mesh (pure DP).

    Model/optimizer sharding (FSDP-style) would swap the spec here; for the
    flagship MLP full replication is optimal — params are tiny, batch math
    dominates.
    """
    return jax.device_put(state, replicated_sharding(mesh))


def stacked_batch_sharding(mesh: Mesh) -> NamedSharding:
    """For [S, B, ...] epoch stacks: steps replicated, batch dim sharded."""
    return NamedSharding(mesh, P(None, "data"))


def _make_global(sharding: NamedSharding, host_arrays):
    """Per-process host arrays -> global device arrays under ``sharding``.

    Single-process: a straight ``device_put``. Multi-process
    (``jax.distributed``): each process contributes its local shard via
    ``make_array_from_process_local_data`` — the explicit version of what
    torch DDP does implicitly with one-rank-one-batch.
    """
    if jax.process_count() > 1:
        return tuple(
            jax.make_array_from_process_local_data(sharding, a) for a in host_arrays
        )
    return tuple(jax.device_put(a, sharding) for a in host_arrays)


def make_global_batch(mesh: Mesh, *host_arrays):
    """[B_local, ...] per-process arrays -> global [B, ...] sharded on
    ``data``."""
    return _make_global(batch_sharding(mesh), host_arrays)


def make_global_epoch(mesh: Mesh, *host_arrays):
    """[S, B_local, ...] per-process stacks -> global [S, B, ...] arrays
    sharded over ``data`` on the batch dim."""
    return _make_global(stacked_batch_sharding(mesh), host_arrays)

"""SPMD correctness on the virtual 8-device mesh.

The key property (the DDP-parity guarantee): training on a mesh-sharded
global batch produces the SAME numbers as single-device training on the
unsharded batch — XLA's inserted all-reduce is semantically invisible. This
is the analog of the reference's implicit claim that 2-rank DDP == big-batch
SGD (jobs/train_lightning_ddp.py:131-140), made testable.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dct_tpu.config import MeshConfig, ModelConfig
from dct_tpu.models.registry import get_model
from dct_tpu.parallel.mesh import (
    batch_sharding,
    make_global_batch,
    make_mesh,
    replicated_sharding,
    shard_state,
)
from dct_tpu.train.state import create_train_state
from dct_tpu.train.steps import make_train_step


def test_mesh_axes_and_sizes():
    mesh = make_mesh(MeshConfig())
    assert mesh.axis_names == ("data", "model", "seq", "pipe")
    assert mesh.shape["data"] == 8
    assert mesh.shape["model"] == 1

    mesh2 = make_mesh(MeshConfig(data=4, model=2))
    assert mesh2.shape["data"] == 4 and mesh2.shape["model"] == 2

    # All-fixed axes may take a device subset (test meshes on the 8-dev rig).
    mesh3 = make_mesh(MeshConfig(data=3, model=1, seq=1), allow_subset=True)
    assert mesh3.size == 3

    with pytest.raises(ValueError):
        make_mesh(MeshConfig(data=16, model=1, seq=1))  # more than we have

    with pytest.raises(ValueError):
        make_mesh(MeshConfig(data=-1, model=3, seq=1))  # 8 % 3 != 0


def test_batch_actually_sharded_over_data_axis():
    mesh = make_mesh(MeshConfig())
    x = np.arange(16 * 5, dtype=np.float32).reshape(16, 5)
    (gx,) = make_global_batch(mesh, x)
    assert gx.sharding == batch_sharding(mesh)
    # Each device holds 2 rows.
    shard_shapes = {s.data.shape for s in gx.addressable_shards}
    assert shard_shapes == {(2, 5)}
    np.testing.assert_array_equal(np.asarray(gx), x)


def test_sharded_training_matches_single_device(rng):
    """8-way DP step == 1-device step on the same global batch."""
    x = rng.standard_normal((32, 5)).astype(np.float32)
    y = rng.integers(0, 2, 32).astype(np.int32)
    w = np.ones(32, np.float32)

    def run(devices):
        mesh = make_mesh(MeshConfig(), devices=devices)
        model = get_model(ModelConfig(), input_dim=5)
        state = create_train_state(model, input_dim=5, lr=0.01, seed=42)
        state = shard_state(state, mesh)
        step = make_train_step(donate=False)
        losses = []
        for _ in range(5):
            gx, gy, gw = make_global_batch(mesh, x, y, w)
            state, m = step(state, gx, gy, gw)
            losses.append(float(m["train_loss"]))
        return losses, jax.device_get(state.params)

    l8, p8 = run(jax.devices())
    l1, p1 = run(jax.devices()[:1])
    np.testing.assert_allclose(l8, l1, rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6), p8, p1
    )


def test_metrics_are_global_not_per_shard(rng):
    """The weighted-mean loss must be the global mean over all shards,
    not a per-device mean — exact sync_dist semantics."""
    mesh = make_mesh(MeshConfig())
    model = get_model(ModelConfig(dropout=0.0), input_dim=5)
    state = create_train_state(model, input_dim=5, lr=0.01, seed=0)
    state = shard_state(state, mesh)

    x = rng.standard_normal((16, 5)).astype(np.float32)
    y = rng.integers(0, 2, 16).astype(np.int32)
    # Mask out the second half: global mean must only count 8 rows.
    w = np.concatenate([np.ones(8), np.zeros(8)]).astype(np.float32)

    from dct_tpu.ops.losses import masked_cross_entropy

    @jax.jit
    def global_loss(params, gx, gy, gw):
        logits = state.apply_fn(params, gx, train=False)
        s, c = masked_cross_entropy(logits, gy, gw)
        return s / c

    gx, gy, gw = make_global_batch(mesh, x, y, w)
    sharded = float(global_loss(state.params, gx, gy, gw))

    logits = model.apply(state.params, jnp.asarray(x[:8]), train=False)
    s, c = masked_cross_entropy(logits, jnp.asarray(y[:8]), jnp.ones(8))
    np.testing.assert_allclose(sharded, float(s / c), rtol=1e-6)


def test_state_replicated(rng):
    mesh = make_mesh(MeshConfig())
    model = get_model(ModelConfig(), input_dim=5)
    state = create_train_state(model, input_dim=5, lr=0.01, seed=0)
    state = shard_state(state, mesh)
    kernel = state.params["params"]["TorchStyleDense_0"]["kernel"]
    assert kernel.sharding == replicated_sharding(mesh)
    assert len(kernel.addressable_shards) == 8


def test_device_grid_uses_ici_layout_on_tpu(monkeypatch):
    """Full-coverage TPU meshes go through mesh_utils.create_device_mesh
    (ICI-aware torus mapping); CPU rigs keep enumeration order."""
    import numpy as _np

    from dct_tpu.parallel import mesh as mesh_mod

    class FakeTpu:
        platform = "tpu"

        def __init__(self, i, pid=0):
            self.id = i
            self.process_index = pid

        def __repr__(self):
            return f"tpu{self.id}"

    fakes = [FakeTpu(i) for i in range(8)]
    calls = []

    from jax.experimental import mesh_utils

    def fake_create(shape, devices=None):
        calls.append(tuple(shape))
        return _np.array(devices).reshape(shape)

    monkeypatch.setattr(mesh_utils, "create_device_mesh", fake_create)
    grid = mesh_mod._device_grid([2, 2, 2, 1], fakes)
    assert calls == [(2, 2, 2, 1)]
    assert grid.shape == (2, 2, 2, 1)

    # CPU devices: enumeration order, no create_device_mesh call.
    cpu = jax.devices()[:8]
    grid_cpu = mesh_mod._device_grid([8, 1, 1, 1], cpu)
    assert calls == [(2, 2, 2, 1)]
    assert list(grid_cpu.reshape(-1)) == list(cpu)

    # A failing create_device_mesh raises: no silent enumeration order
    # (DCT_ICI_MESH=0 below is how to ask for it).
    def boom(shape, devices=None):
        raise ValueError("unsupported topology")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", boom)
    with pytest.raises(ValueError, match="unsupported topology"):
        mesh_mod._device_grid([8, 1, 1, 1], fakes)

    # DCT_ICI_MESH=0 opts out entirely.
    monkeypatch.setattr(mesh_utils, "create_device_mesh", fake_create)
    monkeypatch.setenv("DCT_ICI_MESH", "0")
    grid_off = mesh_mod._device_grid([2, 2, 2, 1], fakes)
    assert calls == [(2, 2, 2, 1)]  # not called again
    assert list(grid_off.reshape(-1)) == fakes

    # layout_of reads the order back off a real mesh.
    from jax.sharding import Mesh

    cpu4 = jax.devices()[:4]
    assert mesh_mod.layout_of(Mesh(_np.array(cpu4), ("data",))) == "enumeration"
    assert mesh_mod.layout_of(
        Mesh(_np.array([cpu4[i] for i in (0, 1, 3, 2)]), ("data",))
    ) == "ici"


def test_device_grid_rejects_interleaved_process_rows(monkeypatch):
    """A torus mapping that interleaves one process's data-axis rows
    would break process_data_block's contiguous-block contract — the
    layout must fall back to enumeration order, not abort training."""
    import numpy as _np

    from dct_tpu.parallel import mesh as mesh_mod

    class FakeTpu:
        platform = "tpu"

        def __init__(self, i, pid):
            self.id = i
            self.process_index = pid

    # Two processes; enumeration order gives each a contiguous half.
    fakes = [FakeTpu(i, pid=i // 4) for i in range(8)]

    from jax.experimental import mesh_utils

    def interleaving_create(shape, devices=None):
        # Rows alternate processes: pid pattern 0,1,0,1,... over data.
        order = [0, 4, 1, 5, 2, 6, 3, 7]
        return _np.array([devices[i] for i in order]).reshape(shape)

    monkeypatch.setattr(mesh_utils, "create_device_mesh", interleaving_create)
    grid = mesh_mod._device_grid([8, 1, 1, 1], fakes)
    # Fallback: enumeration order, which IS contiguous per process.
    assert list(grid.reshape(-1)) == fakes
    assert mesh_mod._grid_blocks_contiguous(grid)

"""Pipeline parallelism: GPipe microbatch streaming must equal sequential
stage application — forward and backward — on the virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dct_tpu.config import MeshConfig
from dct_tpu.parallel.mesh import make_mesh
from dct_tpu.parallel.pipeline import (
    pipeline_apply,
    stack_stage_params,
    stage_params_sharding,
)


D = 16
N_STAGES = 4


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _stages(rng):
    return [
        {
            "w": jnp.asarray(rng.standard_normal((D, D)) * 0.3, jnp.float32),
            "b": jnp.asarray(rng.standard_normal(D) * 0.1, jnp.float32),
        }
        for _ in range(N_STAGES)
    ]


def _sequential(stages, x):
    for p in stages:
        x = _stage_fn(p, x)
    return x


@pytest.fixture()
def mesh():
    return make_mesh(MeshConfig(data=2, model=1, seq=1, pipe=N_STAGES))


@pytest.mark.parametrize("n_microbatches", [4, 8])
def test_pipeline_matches_sequential(rng, mesh, n_microbatches):
    stages = _stages(rng)
    stacked = stack_stage_params(stages)
    stacked = jax.device_put(stacked, stage_params_sharding(stacked, mesh))
    x = jnp.asarray(rng.standard_normal((16, D)), jnp.float32)

    y_pipe = pipeline_apply(
        _stage_fn, stacked, x, mesh=mesh, n_microbatches=n_microbatches
    )
    y_seq = _sequential(stages, x)
    np.testing.assert_allclose(
        np.asarray(y_pipe), np.asarray(y_seq), atol=1e-6
    )


def test_pipeline_grad_matches_sequential(rng, mesh):
    """jax.grad through the pipeline == grad of the sequential stack: the
    reverse (backward) pipeline schedule comes from AD, not hand code."""
    stages = _stages(rng)
    stacked = stack_stage_params(stages)
    stacked = jax.device_put(stacked, stage_params_sharding(stacked, mesh))
    x = jnp.asarray(rng.standard_normal((8, D)), jnp.float32)

    def loss_pipe(params):
        return pipeline_apply(_stage_fn, params, x, mesh=mesh).sum()

    def loss_seq(stages):
        return _sequential(stages, x).sum()

    g_pipe = jax.grad(loss_pipe)(stacked)
    g_seq = stack_stage_params(
        list(jax.grad(lambda s: loss_seq(s))(stages))
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        g_pipe,
        g_seq,
    )


def test_pipeline_under_jit(rng, mesh):
    stages = _stages(rng)
    stacked = stack_stage_params(stages)
    stacked = jax.device_put(stacked, stage_params_sharding(stacked, mesh))
    x = jnp.asarray(rng.standard_normal((8, D)), jnp.float32)
    y = jax.jit(
        lambda p, x: pipeline_apply(_stage_fn, p, x, mesh=mesh)
    )(stacked, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_sequential(stages, x)), atol=1e-6
    )


def test_pipeline_validates_inputs(rng, mesh):
    stages = _stages(rng)
    stacked = stack_stage_params(stages[:2] + stages[:1])  # 3 != 4 stages
    with pytest.raises(ValueError, match="stages"):
        pipeline_apply(
            _stage_fn, stacked, jnp.zeros((8, D), jnp.float32), mesh=mesh
        )
    good = stack_stage_params(stages)
    with pytest.raises(ValueError, match="divisible"):
        pipeline_apply(
            _stage_fn, good, jnp.zeros((9, D), jnp.float32), mesh=mesh,
            n_microbatches=4,
        )

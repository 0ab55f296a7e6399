"""Pipeline-parallel transformer family: end-to-end integration.

VERDICT r1 item 3: pipeline parallelism must be a CAPABILITY, not a
library — a stage-stacked model trained by the standard Trainer over a
``pipe``-axis mesh, placed by the sharding rules, equal to the sequential
stack. These tests pin all three on the 8-device CPU rig.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dct_tpu.config import MeshConfig, ModelConfig, RunConfig
from dct_tpu.models.registry import get_model
from dct_tpu.parallel.mesh import make_global_batch, make_mesh
from dct_tpu.parallel.sharding_rules import (
    shard_state_with_rules,
    spec_for_path,
    state_shardings,
)
from dct_tpu.train.state import create_train_state
from dct_tpu.train.steps import make_train_step


CFG = dict(
    name="weather_transformer_pp", seq_len=8, d_model=16, n_heads=2,
    n_layers=4, d_ff=32, n_stages=4,
)


def _model(mesh=None, **over):
    cfg = ModelConfig(**{**CFG, **over})
    return get_model(cfg, input_dim=5, mesh=mesh)


def test_pp_matches_sequential(rng):
    """pipe=4 pipeline forward == the sequential stage stack (same params,
    mesh-less model instance) — the model-level pipeline oracle."""
    mesh = make_mesh(MeshConfig(data=2, pipe=4))
    x = jnp.asarray(rng.standard_normal((8, 8, 5)), jnp.float32)
    m_seq = _model(mesh=None)
    params = m_seq.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 5)))
    out_seq = m_seq.apply(params, x)
    out_pp = _model(mesh=mesh).apply(params, x)
    np.testing.assert_allclose(
        np.asarray(out_pp), np.asarray(out_seq), atol=1e-5
    )


def test_pp_sharding_rule():
    """Every pp_stages leaf lands P('pipe', ...) on its stage dim — even
    leaves whose names also match TP patterns (qkv_proj etc.)."""
    mesh = make_mesh(MeshConfig(data=2, pipe=4))
    model = _model(mesh=mesh)
    state = create_train_state(
        model, input_dim=5, lr=1e-3, seed=0, example_shape=(1, 8, 5)
    )
    shardings = state_shardings(state, mesh)
    checked = 0
    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    for path, leaf in flat:
        names = [str(getattr(k, "key", k)) for k in path]
        if "pp_stages" in names:
            spec = spec_for_path(path, ndim=leaf.ndim)
            assert spec[0] == "pipe", f"{names} got {spec}"
            assert len(spec) == leaf.ndim
            checked += 1
    assert checked >= 8  # 4 stages x (attn + ffn) leaves exist


def test_pp_train_step_dp_pp(rng):
    """One jitted train step over dp=2 x pipe=4: finite loss, finite
    grads, stage params actually updated."""
    mesh = make_mesh(MeshConfig(data=2, pipe=4))
    model = _model(mesh=mesh)
    state = create_train_state(
        model, input_dim=5, lr=1e-2, seed=0, example_shape=(1, 8, 5)
    )
    state = shard_state_with_rules(state, mesh)
    x = rng.standard_normal((8, 8, 5)).astype(np.float32)
    y = rng.integers(0, 2, 8).astype(np.int32)
    w = np.ones(8, np.float32)
    gx, gy, gw = make_global_batch(mesh, x, y, w)
    step = make_train_step(donate=False)
    before = jax.device_get(
        jax.tree.leaves(state.params["params"]["pp_stages"])[0]
    )
    state2, metrics = step(state, gx, gy, gw)
    loss = float(jax.device_get(metrics["train_loss"]))
    assert np.isfinite(loss)
    after = jax.device_get(
        jax.tree.leaves(state2.params["params"]["pp_stages"])[0]
    )
    assert not np.allclose(before, after), "stage params did not update"


def test_pp_trainer_e2e(processed_dir, tmp_path):
    """The standard Trainer trains the PP family over a pipe>=2 mesh:
    finite val metrics and a checkpoint on disk."""
    from dct_tpu.train.trainer import Trainer

    cfg = RunConfig.from_env()
    cfg.model = ModelConfig(**{**CFG, "n_layers": 2, "n_stages": 2})
    cfg.data.processed_dir = processed_dir
    cfg.data.models_dir = str(tmp_path / "models")
    cfg.train.epochs = 1
    cfg.train.batch_size = 4
    cfg.train.lr = 1e-3
    cfg.train.bf16_compute = False
    cfg.mesh = MeshConfig(data=4, model=1, seq=1, pipe=2)
    trainer = Trainer(cfg, tracker=_null_tracker())
    res = trainer.fit()
    assert np.isfinite(res.val_loss)
    assert np.isfinite(res.val_acc)


def _null_tracker():
    from dct_tpu.tracking.client import get_tracker

    return get_tracker(tracking_uri=None, experiment="t", coordinator=False)


def test_pp_rejects_indivisible_layers():
    with pytest.raises(ValueError, match="n_stages"):
        _model(n_layers=3, n_stages=2).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, 5))
        )


def test_pp_untileable_real_batch_raises(rng):
    """Review regression: a real batch that cannot tile the configured
    pipeline must raise, not silently run sequentially."""
    mesh = make_mesh(MeshConfig(data=2, pipe=4))
    model = _model(mesh=mesh)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 5)))
    x = jnp.asarray(rng.standard_normal((10, 8, 5)), jnp.float32)  # 10 % 4
    with pytest.raises(ValueError, match="does not tile"):
        model.apply(params, x)


def test_pp_tp_composed_matches_sequential(rng):
    """PP x TP: stages streamed over `pipe` with their projection kernels
    sharded over `model` — output equals the meshless sequential stack
    (parallelism is layout, not math)."""
    from dct_tpu.config import MeshConfig, ModelConfig
    from dct_tpu.models.registry import get_model
    from dct_tpu.parallel.mesh import make_mesh
    from dct_tpu.parallel.sharding_rules import state_shardings
    from dct_tpu.train.state import create_train_state

    cfg = ModelConfig(
        name="weather_transformer_pp", seq_len=8, d_model=16, n_heads=2,
        n_layers=2, d_ff=32, n_stages=2,
    )
    mesh = make_mesh(MeshConfig(data=2, model=2, pipe=2))
    m_seq = get_model(cfg, input_dim=5)  # meshless sequential oracle
    params = m_seq.init(jax.random.PRNGKey(3), jnp.zeros((1, 8, 5)))
    x = rng.standard_normal((8, 8, 5)).astype(np.float32)
    ref = np.asarray(m_seq.apply(params, jnp.asarray(x)))

    m_pp = get_model(cfg, input_dim=5, mesh=mesh)
    state = create_train_state(
        m_pp, input_dim=5, lr=1e-3, seed=3, example_shape=(1, 8, 5)
    )
    shardings = state_shardings(state, mesh)
    # The qkv kernel inside the stacked stages must be model-sharded —
    # TP composed, not just replicated under the pipe split.
    qkv_spec = jax.tree_util.tree_map_with_path(
        lambda p, s: s.spec
        if "qkv_proj" in jax.tree_util.keystr(p) and "kernel" in jax.tree_util.keystr(p)
        else None,
        shardings,
    )
    specs = [s for s in jax.tree.leaves(qkv_spec, is_leaf=lambda v: v is not None) if s]
    assert any("model" in str(s) for s in specs), specs

    sharded_params = jax.device_put(params, shardings.params)
    out = np.asarray(m_pp.apply(sharded_params, jnp.asarray(x)))
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_pp_tp_train_step_runs(rng):
    """Full train step over the data x model x pipe mesh with composed
    PP x TP shardings: finite loss, params update."""
    from dct_tpu.config import MeshConfig, ModelConfig
    from dct_tpu.models.registry import get_model
    from dct_tpu.parallel.mesh import make_global_batch, make_mesh
    from dct_tpu.parallel.sharding_rules import shard_state_with_rules
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import make_train_step

    cfg = ModelConfig(
        name="weather_transformer_pp", seq_len=8, d_model=16, n_heads=2,
        n_layers=2, d_ff=32, n_stages=2,
    )
    mesh = make_mesh(MeshConfig(data=2, model=2, pipe=2))
    model = get_model(cfg, input_dim=5, mesh=mesh)
    state = create_train_state(
        model, input_dim=5, lr=1e-3, seed=0, example_shape=(1, 8, 5)
    )
    state = shard_state_with_rules(state, mesh)
    x = rng.standard_normal((8, 8, 5)).astype(np.float32)
    y = rng.integers(0, 2, 8).astype(np.int32)
    w = np.ones(8, np.float32)
    gx, gy, gw = make_global_batch(mesh, x, y, w)
    before = jax.device_get(
        jax.tree.leaves(state.params["params"]["pp_stages"])[0]
    )
    state2, m = make_train_step(donate=False)(state, gx, gy, gw)
    assert np.isfinite(float(jax.device_get(m["train_loss"])))
    after = jax.device_get(
        jax.tree.leaves(state2.params["params"]["pp_stages"])[0]
    )
    assert np.abs(after - before).max() > 0  # grads flowed through PPxTP


def test_pp_tp_collective_in_hlo(rng):
    """The compiled PP x TP body contains a model-axis all-reduce INSIDE
    the pipeline (the row-parallel psum) — TP compute is real, not an
    all-gather of the stage weights."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dct_tpu.config import MeshConfig
    from dct_tpu.parallel.mesh import make_mesh
    from dct_tpu.parallel.pipeline import pipeline_apply

    mesh = make_mesh(MeshConfig(data=2, model=2, pipe=2))
    d = 8
    w = jnp.asarray(rng.standard_normal((2, d, d)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((8, 4, d)), jnp.float32)
    w_s = jax.device_put(w, NamedSharding(mesh, P("pipe", None, "model")))
    x_s = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))

    def stage_fn(p, a):  # col-parallel then row-parallel matmul pair
        return jnp.tanh(a @ p @ p.T)

    def run(params, xx):
        return pipeline_apply(
            stage_fn, params, xx, mesh=mesh, n_microbatches=2,
            data_axis="data",
        )

    hlo_tp = jax.jit(run).lower(w_s, x_s).compile().as_text()
    # Baseline with TP disabled (weights replicated over model): the
    # pipe-axis psum broadcast alone contributes all-reduces, so the
    # assertion must be RELATIVE — the TP compile has strictly more
    # (the in-stage row-parallel psum).
    w_rep = jax.device_put(w, NamedSharding(mesh, P("pipe", None, None)))
    hlo_rep = jax.jit(run).lower(w_rep, x_s).compile().as_text()
    n_tp = hlo_tp.count("all-reduce")
    n_rep = hlo_rep.count("all-reduce")
    assert n_tp > n_rep, (n_tp, n_rep)
    out = jax.jit(run)(w_s, x_s)
    h = x
    for i in range(2):
        h = stage_fn(w[i], h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(h), atol=1e-4)


def test_pp_remat_is_layout_not_math(rng):
    """DCT_REMAT through the PP family: same param tree, same outputs and
    gradients as the non-remat pipeline (remat only reschedules the
    backward's memory inside each stage)."""
    mesh = make_mesh(MeshConfig(data=4, pipe=2))
    x = jnp.asarray(rng.standard_normal((8, 8, 5)), jnp.float32)
    m = _model(mesh=mesh, n_stages=2)
    m_r = _model(mesh=mesh, n_stages=2, remat=True)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 5)))
    params_r = m_r.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 5)))
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        params_r
    )
    np.testing.assert_allclose(
        np.asarray(m_r.apply(params, x)), np.asarray(m.apply(params, x)),
        atol=1e-6,
    )
    g = jax.grad(lambda p: m.apply(p, x).astype(jnp.float32).sum())(params)
    g_r = jax.grad(lambda p: m_r.apply(p, x).astype(jnp.float32).sum())(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        g, g_r,
    )

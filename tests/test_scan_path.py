"""The scan (whole-epoch-as-one-XLA-program) path must be numerically
identical to the eager per-step path — it is the same math, re-staged."""

import jax
import jax.numpy as jnp
import numpy as np

from dct_tpu.config import DataConfig, ModelConfig, RunConfig, TrainConfig
from dct_tpu.models.registry import get_model
from dct_tpu.tracking.client import LocalTracking
from dct_tpu.train.state import create_train_state
from dct_tpu.train.steps import (
    make_epoch_eval_step,
    make_epoch_train_eval_step,
    make_epoch_train_step,
    make_eval_step,
    make_train_step,
)
from dct_tpu.train.trainer import Trainer


def test_scan_equals_eager_steps(rng):
    x = rng.standard_normal((6, 8, 5)).astype(np.float32)  # 6 steps of batch 8
    y = rng.integers(0, 2, (6, 8)).astype(np.int32)
    w = np.ones((6, 8), np.float32)

    model = get_model(ModelConfig(), input_dim=5)  # dropout ACTIVE

    def eager():
        state = create_train_state(model, input_dim=5, lr=0.01, seed=42)
        step = make_train_step(donate=False)
        losses = []
        for i in range(6):
            state, m = step(state, jnp.asarray(x[i]), jnp.asarray(y[i]), jnp.asarray(w[i]))
            losses.append(float(m["train_loss"]))
        return losses, jax.device_get(state.params)

    def scanned():
        state = create_train_state(model, input_dim=5, lr=0.01, seed=42)
        ep = make_epoch_train_step(donate=False)
        state, losses = ep(state, jnp.asarray(x), jnp.asarray(y), jnp.asarray(w))
        return [float(v) for v in jax.device_get(losses)], jax.device_get(state.params)

    el, ep_ = eager()
    sl, sp = scanned()
    np.testing.assert_allclose(el, sl, rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6), ep_, sp)


def test_fused_train_eval_matches_separate(rng):
    """The one-dispatch train+eval program == epoch train followed by
    epoch eval (same losses, same params, same val sums)."""
    x = rng.standard_normal((4, 8, 5)).astype(np.float32)
    y = rng.integers(0, 2, (4, 8)).astype(np.int32)
    w = np.ones((4, 8), np.float32)
    vx = rng.standard_normal((2, 8, 5)).astype(np.float32)
    vy = rng.integers(0, 2, (2, 8)).astype(np.int32)
    vw = np.ones((2, 8), np.float32)
    model = get_model(ModelConfig(), input_dim=5)  # dropout ACTIVE

    def separate():
        state = create_train_state(model, input_dim=5, lr=0.01, seed=42)
        state, losses = make_epoch_train_step(donate=False)(
            state, jnp.asarray(x), jnp.asarray(y), jnp.asarray(w)
        )
        sums = make_epoch_eval_step()(
            state, jnp.asarray(vx), jnp.asarray(vy), jnp.asarray(vw)
        )
        return (
            jax.device_get(losses), jax.device_get(state.params),
            tuple(float(v) for v in sums),
        )

    def fused():
        state = create_train_state(model, input_dim=5, lr=0.01, seed=42)
        state, losses, sums, _ = make_epoch_train_eval_step(
            donate=False
        )(
            state, jnp.asarray(x), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(vx), jnp.asarray(vy), jnp.asarray(vw),
        )
        return (
            jax.device_get(losses), jax.device_get(state.params),
            tuple(float(v) for v in sums),
        )

    sl, sp, sv = separate()
    fl, fp, fv = fused()
    np.testing.assert_allclose(sl, fl, rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6), sp, fp
    )
    np.testing.assert_allclose(sv, fv, rtol=1e-6)


def test_epoch_eval_matches_eager(rng):
    model = get_model(ModelConfig(dropout=0.0), input_dim=5)
    state = create_train_state(model, input_dim=5, lr=0.01, seed=0)
    x = rng.standard_normal((3, 8, 5)).astype(np.float32)
    y = rng.integers(0, 2, (3, 8)).astype(np.int32)
    w = np.ones((3, 8), np.float32)
    w[2, 5:] = 0.0  # padded tail

    ev = make_eval_step()
    tot = [0.0] * 6
    for i in range(3):
        for j, v in enumerate(
            ev(state, jnp.asarray(x[i]), jnp.asarray(y[i]), jnp.asarray(w[i]))
        ):
            tot[j] += float(v)

    ep = make_epoch_eval_step()
    sums = ep(state, jnp.asarray(x), jnp.asarray(y), jnp.asarray(w))
    np.testing.assert_allclose([float(v) for v in sums], tot, rtol=1e-6)
    ls, accs, c, tp, fp, fn = (float(v) for v in sums)
    assert c == 21.0
    # Positive-class counts partition the real rows: tp+fp+fn <= count,
    # and accuracy equals 1 - (fp+fn)/count for binary labels.
    assert tp + fp + fn <= c
    np.testing.assert_allclose(accs, c - fp - fn, rtol=1e-6)


def test_trainer_scan_vs_eager_same_result(processed_dir, tmp_path):
    def run(use_scan, sub):
        cfg = RunConfig(
            data=DataConfig(
                processed_dir=processed_dir, models_dir=str(tmp_path / sub)
            ),
            train=TrainConfig(
                epochs=2, batch_size=4, bf16_compute=False, use_scan=use_scan
            ),
        )
        tr = LocalTracking(root=str(tmp_path / f"runs_{sub}"))
        return Trainer(cfg, tracker=tr).fit()

    r_scan = run(True, "scan")
    r_eager = run(False, "eager")
    assert abs(r_scan.val_loss - r_eager.val_loss) < 1e-5
    assert abs(r_scan.val_acc - r_eager.val_acc) < 1e-6
    for a, b in zip(r_scan.history, r_eager.history):
        assert abs(a["train_loss"] - b["train_loss"]) < 1e-5

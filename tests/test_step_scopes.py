"""The step's device scopes in the compiled epoch program, and the program's
text the trainer writes beside its event log.

The scopes (``step.loss`` and its backward ``transpose(jvp(step.loss))``,
``step.optimizer``, ``step.eval``, ``dense_mlp``) are metadata: they name
every instruction of the optimized text, where the benchmark joins a
device trace to them (``benchmark/reduce/program_text.py``). ``fit``
writes that text, ``<events dir>/programs/scan_k1.hlo.txt``, on every exit
path, and nothing with the event log off."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from dct_tpu.config import (
    DataConfig,
    ObservabilityConfig,
    RunConfig,
    TrackingConfig,
    TrainConfig,
)
from dct_tpu.resilience.preempt import PreemptedError
from dct_tpu.tracking.client import LocalTracking
from dct_tpu.train.trainer import Trainer


def _epoch_program_op_names(monkeypatch, mlp: str, accum: int) -> list[str]:
    """The ``op_name`` of every instruction of a tiny causal model's fused
    epoch program, compiled on the CPU (dense attention at 32 positions)."""
    from dct_tpu.models.registry import get_model
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import make_epoch_train_eval_step

    for k, v in dict(DCT_MODEL="weather_transformer_causal", DCT_D_MODEL=32,
                     DCT_N_HEADS=2, DCT_D_FF=64, DCT_N_LAYERS=1,
                     DCT_SEQ_LEN=32, DCT_MLP=mlp, DCT_DROPOUT=0.1).items():
        monkeypatch.setenv(k, str(v))
    cfg = RunConfig.from_env()
    assert cfg.model.mlp == mlp
    model = get_model(cfg.model, input_dim=5)
    state = jax.eval_shape(lambda: create_train_state(
        model, input_dim=5, lr=1e-3, seed=0, example_shape=(1, 32, 5),
        grad_clip_norm=1.0))

    def stacks(n):
        return (jax.ShapeDtypeStruct((n, 2, 32, 5), jnp.float32),
                jax.ShapeDtypeStruct((n, 2, 32), jnp.int32),
                jax.ShapeDtypeStruct((n, 2), jnp.float32))

    fused = make_epoch_train_eval_step(
        donate=False, accum_steps=accum, with_grad_norms=True)
    text = fused.lower(state, *stacks(2), *stacks(1)).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("accum", [1, 2], ids=["plain", "accumulated"])
@pytest.mark.parametrize("mlp", ["gelu", "swiglu"])
def test_the_epoch_program_names_the_step_s_passes(monkeypatch, mlp, accum):
    names = _epoch_program_op_names(monkeypatch, mlp, accum)
    backward = [n for n in names if "transpose(jvp(step.loss))" in n]
    forward = [n for n in names
               if "jvp(step.loss)" in n and "transpose(" not in n]
    assert forward and backward
    assert any("/step.optimizer/" in n for n in names)
    assert any("/step.eval/" in n for n in names)
    # The dense block of either kind, forward and backward.
    assert any("/dense_mlp/" in n for n in forward)
    assert any("/dense_mlp/" in n for n in backward)
    # The optimizer's update and the validation pass are no part of the
    # objective, and neither is differentiated.
    assert not [n for n in names if "step.optimizer" in n and (
        "step.loss" in n or "step.eval" in n)]


def _fit(processed_dir, tmp_path, *, tracker_cls=LocalTracking, events=True):
    cfg = RunConfig(
        data=DataConfig(processed_dir=processed_dir,
                        models_dir=str(tmp_path / "models")),
        train=TrainConfig(epochs=2, batch_size=8, bf16_compute=False),
        tracking=TrackingConfig(experiment="scopes"),
        obs=ObservabilityConfig(
            enabled=events, events_dir=str(tmp_path / "events")),
    )
    tracker = tracker_cls(root=str(tmp_path / "runs"), experiment="scopes")
    return Trainer(cfg, tracker=tracker).fit()


class _Preempting(LocalTracking):
    """Stops ``fit`` at the first epoch's metrics, as the benchmark does."""

    def log_metrics(self, metrics, step=None):
        super().log_metrics(metrics, step=step)
        if "val_loss" in metrics:
            raise PreemptedError("window closed")


class _Failing(LocalTracking):
    def log_metrics(self, metrics, step=None):
        if "val_loss" in metrics:
            raise RuntimeError("tracker down")
        return super().log_metrics(metrics, step=step)


@pytest.mark.parametrize("end", ["completed", "preempted", "failed"])
def test_fit_writes_the_epoch_program_s_text_on_every_exit(
        processed_dir, tmp_path, monkeypatch, end):
    # The epoch program goes through the AOT store, whose executables are
    # what ran; an armed store of its own, so no process-wide cache is set.
    from dct_tpu.compilecache.aot import ExecutableStore
    from dct_tpu.train import fit_setup

    monkeypatch.setattr(
        fit_setup, "open_aot_store",
        lambda *a, **kw: ExecutableStore(str(tmp_path / "aot")))
    tracker_cls, raises = {
        "completed": (LocalTracking, None),
        "preempted": (_Preempting, PreemptedError),
        "failed": (_Failing, RuntimeError),
    }[end]
    if raises is None:
        _fit(processed_dir, tmp_path, tracker_cls=tracker_cls)
    else:
        with pytest.raises(raises):
            _fit(processed_dir, tmp_path, tracker_cls=tracker_cls)
    path = tmp_path / "events" / "programs" / "scan_k1.hlo.txt"
    text = path.read_text()
    assert text.startswith("HloModule jit_epoch_fused")
    assert "step.optimizer" in text and "transpose(jvp(step.loss))" in text
    spans = "".join(
        (tmp_path / "events" / "spans" / f).read_text()
        for f in os.listdir(tmp_path / "events" / "spans"))
    assert '"trainer.program_text"' in spans


def test_nothing_is_written_with_the_event_log_off(
        processed_dir, tmp_path, monkeypatch):
    from dct_tpu.compilecache.aot import ExecutableStore
    from dct_tpu.train import fit_setup

    monkeypatch.setattr(
        fit_setup, "open_aot_store",
        lambda *a, **kw: ExecutableStore(str(tmp_path / "aot")))
    _fit(processed_dir, tmp_path, events=False)
    assert not (tmp_path / "events" / "programs").exists()

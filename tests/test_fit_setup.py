"""The decisions ``Trainer.fit`` takes before its first epoch, as the pure
functions of ``dct_tpu/train/fit_setup.py`` (no fit, no device)."""

from __future__ import annotations

import dataclasses

import pytest

from dct_tpu.config import TrainConfig
from dct_tpu.train.fit_setup import (
    LOOP_CONTROL_FIELDS,
    aot_train_identity,
    cosine_decay_horizon,
    resolve_continuation,
)


@pytest.mark.parametrize(
    "saved, kwargs, expect",
    [
        # no checkpoint: train [0, epochs)
        (None, {}, (0, 3)),
        # interrupted prior run: finish to ITS target, not this budget
        ({"epochs_completed": 2, "target_epochs": 5}, {}, (2, 5)),
        # completed prior run: extend by this run's budget
        ({"epochs_completed": 5, "target_epochs": 5}, {}, (5, 8)),
        # pre-meta checkpoint: start from the restored step counter; no
        # saved target, so it counts as completed and extends
        ({}, {"restored_step": 40, "steps_per_epoch": 10}, (4, 7)),
    ],
    ids=["no-checkpoint", "interrupted", "completed", "pre-meta"],
)
def test_resolve_continuation(saved, kwargs, expect):
    assert resolve_continuation(saved, 3, **kwargs) == expect


def _identity(cfg, **kw):
    args = dict(
        decay_resolved=cfg.decay_steps, shard_rules="r0", dtype_rules="off",
        donate=False,
    )
    args.update(kw)
    return aot_train_identity(cfg, **args)


_OTHER = {
    "resume": True, "epochs": 99, "log_every_n_steps": 1,
    "early_stop_patience": 4, "early_stop_min_delta": 0.5,
    "prefetch_spans": 0,
}


def test_loop_control_fields_are_the_six_the_loop_reads():
    assert set(LOOP_CONTROL_FIELDS) == set(_OTHER)


@pytest.mark.parametrize("name", sorted(_OTHER))
def test_aot_identity_ignores_loop_control(name):
    """A relaunch flips resume (or changes the epoch budget, the logging
    cadence, early stopping, the pipelining) and must still hit."""
    base = TrainConfig()
    other = dataclasses.replace(base, **{name: _OTHER[name]})
    assert getattr(other, name) != getattr(base, name)
    assert _identity(other) == _identity(base)
    assert name not in _identity(base)


@pytest.mark.parametrize(
    "change",
    [
        {"lr": 0.5},
        {"optimizer": "sgd"},
        {"grad_accum_steps": 4},
        {"bf16_compute": not TrainConfig().bf16_compute},
        {"shard_opt_state": True},
        "decay_resolved",
    ],
    ids=["lr", "optimizer", "grad_accum_steps", "bf16_compute",
         "shard_opt_state", "decay_resolved"],
)
def test_aot_identity_follows_what_the_executable_bakes_in(change):
    base = TrainConfig()
    if change == "decay_resolved":
        # Same config, another restored trajectory: the schedule's
        # constants differ, so the executable does.
        assert _identity(base, decay_resolved=70) != _identity(
            base, decay_resolved=140
        )
        return
    assert _identity(dataclasses.replace(base, **change)) != _identity(base)


def test_aot_identity_carries_layout_precision_and_donation():
    base = TrainConfig()
    ident = _identity(base)
    assert ident["shard_rules"] == "r0" and ident["dtype_rules"] == "off"
    assert ident["donate"] is False
    for kw in ({"shard_rules": "r1"}, {"dtype_rules": "bf16"},
               {"donate": True}):
        assert _identity(base, **kw) != ident


@pytest.mark.parametrize(
    "prior_epochs, expect",
    [(0, 3 * 10 - 5), (4, 7 * 10 - 5)],
    ids=["fresh", "resumed"],
)
def test_cosine_horizon_auto_spans_the_whole_trajectory(prior_epochs, expect):
    cfg = TrainConfig(
        epochs=3, lr_schedule="cosine", decay_steps=0, warmup_steps=5
    )
    assert cosine_decay_horizon(
        cfg, updates_per_epoch=10, prior_epochs=prior_epochs
    ) == expect


def test_cosine_horizon_configured_is_taken_as_given():
    cfg = TrainConfig(epochs=3, lr_schedule="cosine", decay_steps=17)
    assert cosine_decay_horizon(
        cfg, updates_per_epoch=10, prior_epochs=4
    ) == 17
    # and never under one update, whatever the warm-up
    tiny = TrainConfig(
        epochs=1, lr_schedule="cosine", decay_steps=0, warmup_steps=50
    )
    assert cosine_decay_horizon(
        tiny, updates_per_epoch=2, prior_epochs=0
    ) == 1

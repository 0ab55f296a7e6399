"""ISSUE 5 tentpole: pipelined span prefetch + non-blocking epoch
bookkeeping (`TrainConfig.prefetch_spans` / DCT_PREFETCH_SPANS), the
buffered telemetry writer, and the vectorized health span pass.

The pipelined loop defers a span's bookkeeping one iteration (it runs
while the next span computes on device). These tests pin that the
deferral changes NOTHING observable: histories, checkpoints, resume
meta, early-stop behavior, and health-halt semantics are identical to
the strictly-serial loop — and that every telemetry buffer drains on
every exit path.
"""

import json
import os
import time

import numpy as np
import pytest

from dct_tpu.config import (
    DataConfig,
    ObservabilityConfig,
    ResilienceConfig,
    RunConfig,
    TrackingConfig,
    TrainConfig,
)
from dct_tpu.observability.buffered import BufferedAppender
from dct_tpu.observability.events import EventLog
from dct_tpu.observability.health import HealthMonitor, TrainingHealthError
from dct_tpu.observability.spans import SpanRecorder
from dct_tpu.tracking.client import LocalTracking
from dct_tpu.train.trainer import Trainer


def _fit(processed_dir, tmp_path, tag, **train_kw):
    train_kw.setdefault("epochs", 4)
    train_kw.setdefault("batch_size", 8)
    train_kw.setdefault("bf16_compute", False)
    cfg = RunConfig(
        data=DataConfig(
            processed_dir=processed_dir,
            models_dir=str(tmp_path / f"m_{tag}"),
        ),
        train=TrainConfig(**train_kw),
        tracking=TrackingConfig(experiment="pl"),
        obs=ObservabilityConfig(
            events_dir=str(tmp_path / f"ev_{tag}"),
            heartbeat_dir=str(tmp_path / f"hb_{tag}"),
        ),
    )
    tracker = LocalTracking(root=str(tmp_path / f"r_{tag}"), experiment="pl")
    return cfg, Trainer(cfg, tracker=tracker).fit()


# -- pipelined == serial ------------------------------------------------


def test_pipelined_matches_serial_bitwise(processed_dir, tmp_path):
    """Same seed, same data: the pipelined loop must produce the exact
    histories, final metrics, and resume meta of the serial loop — the
    deferral changes when bookkeeping runs, never what it records."""
    _, r1 = _fit(processed_dir, tmp_path, "pf1", prefetch_spans=1)
    _, r0 = _fit(processed_dir, tmp_path, "pf0", prefetch_spans=0)
    assert r1.history == r0.history
    assert r1.val_loss == r0.val_loss
    assert r1.val_acc == r0.val_acc
    # Both checkpoint tiers agree: resume meta marks the same progress.
    from dct_tpu.checkpoint.manager import TrainStateCheckpointer

    for tag, res in (("pf1", r1), ("pf0", r0)):
        meta = TrainStateCheckpointer(os.path.join(
            str(tmp_path / f"m_{tag}"), "train_state", "p0"
        )).load_meta()
        assert meta["epochs_completed"] == 4
        assert os.path.exists(res.best_model_path)


def test_pipelined_goodput_windows_never_double_count(
    processed_dir, tmp_path
):
    """Pipelined billing splits the train_step window into the two
    main-thread-blocking intervals (dispatch call + consume join):
    categories must stay disjoint, so per-epoch and run-end
    goodput_fraction can never exceed 1 and accounted time can never
    exceed wall time (the GoodputLedger invariant PR 1 documented)."""
    cfg, res = _fit(processed_dir, tmp_path, "gp", prefetch_spans=1)
    g = res.goodput
    assert g["goodput_fraction"] <= 1.0 + 1e-9
    assert g["accounted_seconds"] <= g["wall_seconds"] + 1e-6
    events = [
        json.loads(line)
        for line in open(
            os.path.join(str(tmp_path / "ev_gp"), "events.jsonl")
        )
    ]
    fracs = [
        e["goodput_fraction"] for e in events if e["event"] == "epoch_end"
    ]
    assert len(fracs) == 4
    assert all(0.0 <= f <= 1.0 + 1e-9 for f in fracs), fracs


def test_pipelined_loop_holds_two_states_at_a_dispatch(
    processed_dir, tmp_path, monkeypatch
):
    """At every dispatch of the pipelined loop the states reachable are
    the program's input and nothing older (its output makes two): the span
    joined before the dispatch has finished, and the span bookkept before
    it let its state go. Bookkeeping of span e still starts after the
    dispatch of e+1."""
    import gc
    import weakref

    from dct_tpu.train import fit_setup as setup_mod

    order: list = []
    states: list = []
    real = setup_mod.make_epoch_train_eval_step

    def counting(**kw):
        fused = real(**kw)

        def epoch_fused(state, *stacks):
            gc.collect()
            alive = sum(1 for r in states if r() is not None)
            order.append(("dispatch", len(
                [o for o in order if o[0] == "dispatch"]), alive))
            out = fused(state, *stacks)
            states.append(weakref.ref(out[0]))
            return out

        return epoch_fused

    monkeypatch.setattr(setup_mod, "make_epoch_train_eval_step", counting)

    class Recording(LocalTracking):
        def log_metrics(self, metrics, step=None):
            if "val_loss" in metrics:
                order.append(("bookkeep", sum(
                    1 for o in order if o[0] == "bookkeep")))
            return super().log_metrics(metrics, step=step)

    cfg = RunConfig(
        data=DataConfig(
            processed_dir=processed_dir, models_dir=str(tmp_path / "m2s")),
        train=TrainConfig(
            epochs=5, batch_size=8, bf16_compute=False, prefetch_spans=1),
        tracking=TrackingConfig(experiment="pl"),
        obs=ObservabilityConfig(
            events_dir=str(tmp_path / "ev2s"),
            heartbeat_dir=str(tmp_path / "hb2s")),
    )
    res = Trainer(cfg, tracker=Recording(
        root=str(tmp_path / "r2s"), experiment="pl")).fit()
    assert len(res.history) == 5
    dispatches = [o for o in order if o[0] == "dispatch"]
    assert len(dispatches) == 5
    # Outputs of earlier programs still reachable when program n is
    # dispatched: its input, never its input's predecessor.
    assert [d[2] for d in dispatches] == [0, 1, 1, 1, 1]
    kinds = [(o[0], o[1]) for o in order]
    for e in range(4):
        assert kinds.index(("dispatch", e + 1)) < kinds.index(("bookkeep", e))
        if e:
            assert kinds.index(("bookkeep", e - 1)) < kinds.index(
                ("dispatch", e + 1))


def test_early_stop_same_epoch_pipelined(processed_dir, tmp_path):
    """The early-stop drain guard consumes the in-flight span before the
    stop decision can be speculated past: identical stop epoch, and the
    stopped run is marked complete at the stop point in both modes."""
    kw = dict(early_stop_patience=2, early_stop_min_delta=1e9, epochs=10)
    _, r1 = _fit(processed_dir, tmp_path, "es1", prefetch_spans=1, **kw)
    _, r0 = _fit(processed_dir, tmp_path, "es0", prefetch_spans=0, **kw)
    assert [h["epoch"] for h in r1.history] == [0, 1, 2]
    assert r1.history == r0.history
    from dct_tpu.checkpoint.manager import TrainStateCheckpointer

    meta = TrainStateCheckpointer(os.path.join(
        str(tmp_path / "m_es1"), "train_state", "p0"
    )).load_meta()
    assert meta["target_epochs"] == meta["epochs_completed"] == 3


def test_fault_plan_forces_serial_consume(processed_dir, tmp_path):
    """An armed DCT_FAULT_SPEC auto-disables pipelining so injection
    drills keep the exact serial crash/checkpoint ordering; a benign
    slow_epoch clause must still train to target with prefetch_spans=1
    requested."""
    cfg = RunConfig(
        data=DataConfig(
            processed_dir=processed_dir, models_dir=str(tmp_path / "mf")
        ),
        train=TrainConfig(
            epochs=2, batch_size=8, bf16_compute=False, prefetch_spans=1
        ),
        resilience=ResilienceConfig(
            fault_spec="slow_epoch:epoch1", fault_sleep_s=0.01
        ),
    )
    res = Trainer(
        cfg, tracker=LocalTracking(root=str(tmp_path / "rf"))
    ).fit()
    assert [h["epoch"] for h in res.history] == [0, 1]


def test_health_halt_writes_no_checkpoint_of_diverged_span(
    processed_dir, tmp_path
):
    """halt_on_nan + a data-poison fault: the run raises before the
    diverged span's bookkeeping, so neither checkpoint tier records it
    (the fault plan also forces serial mode — both guarantees hold)."""
    cfg = RunConfig(
        data=DataConfig(
            processed_dir=processed_dir, models_dir=str(tmp_path / "mh")
        ),
        train=TrainConfig(
            epochs=4, batch_size=8, bf16_compute=False, prefetch_spans=1
        ),
        obs=ObservabilityConfig(
            events_dir=str(tmp_path / "evh"), halt_on_nan=True
        ),
        resilience=ResilienceConfig(fault_spec="nan:epoch1"),
    )
    with pytest.raises(TrainingHealthError):
        Trainer(cfg, tracker=LocalTracking(root=str(tmp_path / "rh"))).fit()
    from dct_tpu.checkpoint.manager import TrainStateCheckpointer

    meta = TrainStateCheckpointer(os.path.join(
        str(tmp_path / "mh"), "train_state", "p0"
    )).load_meta()
    assert meta["epochs_completed"] == 1  # epoch 0 durable, epoch 1 not
    # The halt is on the durable record (buffered writer flushed it).
    events = [
        json.loads(line)
        for line in open(
            os.path.join(str(tmp_path / "evh"), "events.jsonl")
        )
    ]
    kinds = [e["event"] for e in events]
    assert "health.nan_loss" in kinds and "fit_failed" in kinds


# -- buffered telemetry -------------------------------------------------


def test_buffered_appender_write_through_by_default(tmp_path):
    path = str(tmp_path / "a.jsonl")
    app = BufferedAppender(path)
    assert app.append("one\n")
    assert open(path).read() == "one\n"  # visible before any flush call


def test_buffered_appender_batches_then_timer_flushes(tmp_path):
    path = str(tmp_path / "b.jsonl")
    app = BufferedAppender(path, flush_interval=0.1)
    assert app.append("one\n")
    assert app.pending == 1  # buffered, not yet on disk
    deadline = time.time() + 5.0
    while app.pending and time.time() < deadline:
        time.sleep(0.02)
    assert app.pending == 0  # the one-shot timer drained it
    assert open(path).read() == "one\n"


def test_buffered_appender_flush_close_and_write_through(tmp_path):
    path = str(tmp_path / "c.jsonl")
    app = BufferedAppender(path, flush_interval=60.0)
    app.append("one\n")
    assert app.pending == 1
    app.flush()
    assert open(path).read() == "one\n"
    app.append("two\n")
    app.close()  # flush + release handle; appender stays usable
    assert open(path).read() == "one\ntwo\n"
    app.set_write_through()
    app.append("three\n")
    assert open(path).read() == "one\ntwo\nthree\n"


def test_buffered_appender_max_records_flush(tmp_path):
    path = str(tmp_path / "d.jsonl")
    app = BufferedAppender(path, flush_interval=60.0, max_records=3)
    for i in range(3):
        app.append(f"{i}\n")
    assert app.pending == 0  # record cap forced the flush
    assert open(path).read().splitlines() == ["0", "1", "2"]


def test_event_log_buffers_and_flushes(tmp_path):
    path = str(tmp_path / "ev" / "events.jsonl")
    log = EventLog(path, run_id="dct-buf", flush_interval=60.0)
    log.emit("trainer", "epoch_end", epoch=0)
    assert not os.path.exists(path) or open(path).read() == ""
    log.flush()
    recs = [json.loads(x) for x in open(path).read().splitlines()]
    assert recs[0]["event"] == "epoch_end"
    log.emit("trainer", "fit_end")
    log.close()
    assert len(open(path).read().splitlines()) == 2


def test_span_recorder_buffers_and_flushes(tmp_path):
    path = str(tmp_path / "sp" / "rank_00000.jsonl")
    rec = SpanRecorder(path, trace_id="dct-buf", flush_interval=60.0)
    rec.start("trainer.epoch", component="trainer").end()
    assert not os.path.exists(path) or open(path).read() == ""
    rec.flush()
    spans = [json.loads(x) for x in open(path).read().splitlines()]
    assert spans[0]["name"] == "trainer.epoch"
    # for_trace clones share the appender: one buffer per file.
    other = rec.for_trace("dct-other")
    other.start("deploy.gate", component="deploy").end()
    rec.flush()
    assert len(open(path).read().splitlines()) == 2


def test_flush_all_appenders_covers_hard_exit_paths(tmp_path):
    from dct_tpu.observability.buffered import flush_all_appenders

    path = str(tmp_path / "f.jsonl")
    app = BufferedAppender(path, flush_interval=60.0)
    app.append("evidence\n")
    flush_all_appenders()  # what faults.maybe_fire runs before os._exit
    assert open(path).read() == "evidence\n"


def test_buffered_failure_degrades_to_silence(tmp_path):
    blocker = tmp_path / "plainfile"
    blocker.write_text("x")
    log = EventLog(str(blocker / "events.jsonl"), run_id="dct-x")
    log.emit("trainer", "anything")  # OSError swallowed at flush
    assert not log.enabled


def test_trainer_run_flushes_events_before_return(processed_dir, tmp_path):
    """With buffering ON (the ObservabilityConfig default), every event
    of the run must be on disk when fit() returns — the trainer's exit
    path drains the buffer and drops to write-through."""
    cfg, res = _fit(processed_dir, tmp_path, "flush", epochs=2)
    lines = open(
        os.path.join(str(tmp_path / "ev_flush"), "events.jsonl")
    ).read().splitlines()
    events = [json.loads(x)["event"] for x in lines]
    assert "fit_start" in events and "fit_end" in events
    assert events.count("epoch_end") == 2
    assert cfg.obs.telemetry_flush_s > 0  # the buffered default


# -- config knobs -------------------------------------------------------


def test_prefetch_and_flush_env_knobs(monkeypatch):
    monkeypatch.setenv("DCT_PREFETCH_SPANS", "0")
    monkeypatch.setenv("DCT_TELEMETRY_FLUSH_S", "1.5")
    monkeypatch.setenv("DCT_TELEMETRY_FLUSH_RECORDS", "32")
    cfg = RunConfig.from_env()
    assert cfg.train.prefetch_spans == 0
    assert cfg.obs.telemetry_flush_s == 1.5
    assert cfg.obs.telemetry_flush_records == 32


# -- vectorized health span pass ---------------------------------------


def _feed_sequential(losses, gnorms, **kw):
    mon = HealthMonitor(**kw)
    halt = None
    for i, (ls, gn) in enumerate(zip(losses, gnorms)):
        f = mon.observe_step(
            float(ls), grad_norm=float(gn), step=i + 1, epoch=i // 8
        )
        if halt is None and f is not None and f.halt:
            halt = f
    return mon, halt


def _feed_span(losses, gnorms, **kw):
    mon = HealthMonitor(**kw)
    halt = mon.observe_span(
        np.asarray(losses, np.float32), np.asarray(gnorms, np.float32),
        start_step=0, epoch=0, steps_per_epoch=8,
    )
    return mon, halt


@pytest.mark.parametrize(
    "case",
    ["clean", "nan", "loss_spike", "grad_spike", "near_threshold"],
)
def test_observe_span_matches_observe_step(case):
    rng = np.random.default_rng(3)
    losses = (1.0 + 0.01 * rng.standard_normal(64)).astype(np.float32)
    gnorms = (0.5 + 0.005 * rng.standard_normal(64)).astype(np.float32)
    if case == "nan":
        losses[40] = np.nan
    elif case == "loss_spike":
        losses[40] = 50.0
    elif case == "grad_spike":
        gnorms[40] = 100.0
    elif case == "near_threshold":
        # Right at the detector's edge: must take the exact replay path
        # and agree with the sequential decision either way.
        losses[40] = float(np.mean(losses[24:40]) + 8.0 * np.std(losses[24:40]))
    kw = dict(spike_window=16, spike_zscore=8.0, halt_on_nan=True)
    seq_mon, seq_halt = _feed_sequential(losses, gnorms, **kw)
    span_mon, span_halt = _feed_span(losses, gnorms, **kw)
    assert span_mon.counts == seq_mon.counts
    assert list(span_mon._loss.window) == list(seq_mon._loss.window)
    assert list(span_mon._gnorm.window) == list(seq_mon._gnorm.window)
    assert (span_halt is None) == (seq_halt is None)
    if span_halt is not None:
        assert span_halt.kind == seq_halt.kind
        assert span_halt.step == seq_halt.step
        assert span_halt.epoch == seq_halt.epoch
    assert span_mon.last_loss == seq_mon.last_loss
    assert span_mon.last_grad_norm == seq_mon.last_grad_norm


def test_observe_span_fast_path_skips_python_loop(monkeypatch):
    """A healthy span must not fall back to the per-step loop (that loop
    costing more than the epoch's compute was the motivating defect)."""
    mon = HealthMonitor(spike_window=16, spike_zscore=8.0)
    calls = {"n": 0}
    orig = mon.observe_step

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(mon, "observe_step", counting)
    losses = 1.0 + 0.01 * np.random.default_rng(0).standard_normal(4000)
    assert mon.observe_span(
        losses.astype(np.float32), losses.astype(np.float32),
        start_step=0, epoch=0, steps_per_epoch=1000,
    ) is None
    assert calls["n"] == 0
    assert len(mon._loss.window) == 16  # state advanced regardless


def test_observe_span_carries_window_across_spans():
    """Detector state spans spans: a spike relative to the PREVIOUS
    span's baseline must still be caught."""
    mon = HealthMonitor(spike_window=16, spike_zscore=8.0, emit=None)
    flat = np.full(32, 1.0, np.float32) + np.linspace(
        0, 0.001, 32, dtype=np.float32
    )
    assert mon.observe_span(flat, flat, start_step=0, epoch=0) is None
    nxt = np.full(8, 1.0, np.float32)
    nxt[3] = 60.0  # spike vs the carried window
    mon.observe_span(nxt, np.full(8, 1.0, np.float32),
                     start_step=32, epoch=1)
    assert mon.counts["loss_spike"] == 1

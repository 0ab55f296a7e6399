"""What one ``Trainer.fit`` observes itself with, built once as one object.

``RunTelemetry.open`` builds the planes every later part of ``fit`` writes
to (event log, span recorder, goodput ledger, heartbeat, health monitor,
live metrics, fault plan, preemption guard) in the order the run needs them,
``arm_loop`` adds the three that depend on what the setup resolved
(throughput timer, planned profiler window, flight recorder), and
``end_loop`` is the hot loop's last word on every exit path. ``_Timed`` is
the one bracket that feeds the ledger and the span timeline from one pair
of clock reads. Nothing here knows the setup, the loop or ``trainer.py``.
"""

from __future__ import annotations

import jax

from dct_tpu.observability.capture import (
    recorder_from_config as flight_from_config,
)
from dct_tpu.observability.dump import live_train_metrics
from dct_tpu.observability.events import event_log_from_config
from dct_tpu.observability.goodput import GoodputLedger
from dct_tpu.observability.health import HealthMonitor
from dct_tpu.observability.heartbeat import HeartbeatWriter
from dct_tpu.observability.spans import recorder_from_config
from dct_tpu.resilience import faults as _faults
from dct_tpu.resilience.preempt import PreemptionGuard
from dct_tpu.utils.profiling import EpochTimer, Profiler, chip_peak_flops


class _Timed:
    """One interval of ``Trainer.fit``, read once and written twice: the
    goodput ledger's clock is read on entry and on exit, and the seconds
    between go to the ledger under ``category`` and onto a stack span
    (JSONL and the profiler's timeline) as ``seconds`` — one bracket, so
    the two timelines cannot drift. ``category=None`` bills nothing: the
    dispatch and join windows go through ``add_dispatch``'s arithmetic,
    which reads ``t0`` / ``t1`` / ``seconds`` here. A ``with`` block, or
    ``begin()`` / ``end()`` where the interval cannot be one; ``end`` is
    idempotent, for the crash sweep."""

    def __init__(self, ledger, tracer, category, name, **attrs):
        self._ledger, self._tracer = ledger, tracer
        self._category, self._name, self._attrs = category, name, attrs
        self.span = None
        self.t0 = self.t1 = self.seconds = None

    def begin(self) -> "_Timed":
        self.span = self._tracer.open(self._name, **self._attrs)
        self.t0 = self._ledger.clock()
        return self

    def end(self, **attrs) -> None:
        if self.t1 is not None:
            return
        self.t1 = self._ledger.clock()
        self.seconds = self.t1 - self.t0
        if self._category is not None:
            self._ledger.add(self._category, self.seconds)
        self.span.end(seconds=self.seconds, **attrs)

    __enter__ = begin

    def __exit__(self, exc_type, exc, tb):
        self.end(**({"error": exc_type.__name__} if exc_type else {}))
        return False


class RunTelemetry:
    """The run's observers. Attributes: ``events``, ``tracer``,
    ``fit_span``, ``health``, ``live_metrics``, ``plan``, ``guard``,
    ``ledger``, ``heartbeat`` (None when unarmed), ``startup`` (the open
    ``trainer.startup`` bracket), and after ``arm_loop``: ``timer``,
    ``profiler``, ``flight``."""

    @classmethod
    def open(cls, cfg, *, preempt_guard=None) -> "RunTelemetry":
        self = cls()
        rank = jax.process_index()
        # Observability plane: structured events (installed as the
        # process default so the checkpoint/tracking layers stamp the
        # same run-correlation ID), the goodput ledger, and this rank's
        # heartbeat. Everything degrades to no-ops when disabled.
        self.events = event_log_from_config(cfg.obs, rank=rank)
        # Span runtime: this rank's spans join the cycle-wide trace
        # (trace_id = run-correlation ID; if a launcher spawned us, its
        # DCT_SPAN_ID makes fit a child of the launch span).
        self.tracer = recorder_from_config(cfg.obs, rank=rank)
        self.fit_span = self.tracer.open(
            "trainer.fit", component="trainer",
            model=cfg.model.name, epochs=cfg.train.epochs,
            world_size=jax.process_count(),
        )
        # Training-health telemetry: every step's loss (and grad global
        # norm) flows through the monitor; findings become health.*
        # events and, under a halting policy, stop the run.
        self.health = HealthMonitor.from_config(
            cfg.obs, emit=self.events.emit
        )
        # Live per-epoch metrics (ISSUE 17): the coordinator publishes
        # val-loss / goodput / step-time gauges to the metrics plane at
        # epoch cadence, so the telemetry history store (DCT_TS_DIR)
        # sees the run WHILE it happens — the final dump replaces this
        # stream at run end. None when the plane is unarmed.
        self.live_metrics = live_train_metrics(
            cfg.obs, run_id=self.events.run_id, rank=rank
        )
        # Resilience plane: the deterministic fault plan (installed as
        # the process default so the checkpoint tiers consult the SAME
        # instance — shared save ordinals and fired flags), and the
        # graceful-preemption guard. The SIGTERM handler only sets a
        # flag; the trainer honors it at the next step/span boundary.
        self.plan = _faults.FaultPlan.parse(
            cfg.resilience.fault_spec,
            rank=rank,
            sleep_s=cfg.resilience.fault_sleep_s,
        )
        _faults.set_default(self.plan)
        self.guard = (
            preempt_guard if preempt_guard is not None
            else PreemptionGuard()
        )
        if cfg.resilience.graceful_preemption:
            self.guard.install()
        self.ledger = GoodputLedger()
        self.ledger.start()
        # Supervised-relaunch accounting: the wall clock the failed
        # attempts (and backoff) cost this cycle, booked as
        # startup_recovery badput so the healed run's goodput fraction
        # reflects what the failure actually cost.
        if cfg.resilience.startup_debt_s > 0:
            self.ledger.add(
                "startup_recovery", cfg.resilience.startup_debt_s
            )
        self.heartbeat = None
        if cfg.obs.enabled and cfg.obs.heartbeat_dir:
            self.heartbeat = HeartbeatWriter(
                cfg.obs.heartbeat_dir,
                rank,
                run_id=self.events.run_id,
                min_interval=cfg.obs.heartbeat_interval,
            )
            self.heartbeat.beat(phase="startup", force=True)
        self.events.emit(
            "trainer", "fit_start",
            model=cfg.model.name, epochs=cfg.train.epochs,
            resume=cfg.train.resume, world_size=jax.process_count(),
        )
        # Everything from here to the loop's first iteration — dataset
        # load, model init, state creation/sharding, resume restore,
        # validation staging — is the run's startup/recovery cost in the
        # goodput ledger (and the trainer.startup span: the ledger's
        # window, on the timeline). ``fit`` ends it.
        self.startup = self.timed(
            "startup_recovery", "trainer.startup"
        ).begin()
        self.timer = self.profiler = self.flight = None
        return self

    def timed(self, category, name, **attrs) -> _Timed:
        return _Timed(self.ledger, self.tracer, category, name, **attrs)

    def arm_loop(
        self, cfg, *, n_chips, flops_per_sample, target_epochs, coordinator,
    ) -> None:
        """The three observers that need what the setup resolved."""
        # Throughput accounting + optional one-epoch jax.profiler trace
        # (SURVEY §5.1: the reference installs TensorBoard but never
        # writes it — here the trace is real TB-compatible profile data).
        self.timer = EpochTimer(
            n_chips=n_chips,
            flops_per_sample=flops_per_sample,
            peak_flops=chip_peak_flops(),
            ledger=self.ledger,
        )
        self.profiler = Profiler(
            cfg.profile.trace_dir,
            enabled=cfg.profile.enabled,
            epoch=min(cfg.profile.epoch, target_epochs - 1),
            coordinator=coordinator,
        )
        # On-demand flight recorder (observability/capture.py): a
        # DCT_PROFILE_TRIGGER touch or SIGUSR2 starts a per-rank
        # jax.profiler capture at the next span boundary, mid-run,
        # without stopping training. Polling is one stat per span.
        self.flight = flight_from_config(
            cfg.profile, rank=jax.process_index(), emit=self.events.emit,
        )

    def close_captures(self) -> None:
        """Crash-path hygiene: never leave a jax.profiler session open."""
        try:
            self.flight.close()
        finally:
            self.profiler.close()

    def end_loop(self, *, completed, preempted, history, in_flight) -> None:
        """The hot loop is over (success, crash, or preempt): restore the
        signal handler, say how it ended, close what is still open."""
        # The SIGTERM contract ends here either way: restore the
        # previous handler so post-training code (and whatever embeds
        # us) keeps its own semantics.
        self.guard.uninstall()
        # Terminal heartbeat: "done" stops the monitor ageing this rank;
        # "preempted" and "failed" name ends an exit code alone cannot
        # (the rank may be killed by fail-fast before it can exit).
        if self.heartbeat is not None:
            self.heartbeat.beat(
                phase="done" if completed else (
                    "preempted" if preempted else "failed"
                ),
                force=True,
            )
        if preempted:
            self.events.emit(
                "trainer", "fit_preempted", epochs_run=len(history),
            )
        elif not completed:
            self.events.emit(
                "trainer", "fit_failed",
                health=self.health.summary()["events"],
            )
        if not completed:
            # The crashing/preempted epoch is exactly the window the
            # operator opens the trace to inspect: record any span still
            # in flight (Span.end is idempotent: a span the success path
            # already ended is a no-op here).
            for span in in_flight:
                if span is not None:
                    span.end(error=not preempted)
        # Fit span closes HERE, success or failure: a post-training tail
        # error (artifact upload, tracker teardown) must not orphan the
        # whole rank's span tree from its recorded root.
        self.fit_span.end(
            completed=completed,
            preempted=preempted,
            epochs_run=len(history),
            val_loss=history[-1]["val_loss"] if history else None,
        )
        # Drain buffered telemetry and drop both sinks to write-through,
        # so every record emitted so far is durable and post-run
        # emitters through the installed process defaults get
        # read-after-emit visibility back.
        self.events.set_write_through()
        self.tracer.set_write_through()

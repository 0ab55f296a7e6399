"""The training engine: SPMD epoch loop with tracking + checkpointing.

Capability-parity map to the reference's ``main()``
(jobs/train_lightning_ddp.py:90-164):

- MLFlowLogger(...)            -> tracking client (coordinator-only, §tracking)
- WeatherDataset + random_split -> load_processed_dataset + train_val_split
- DataLoader(batch_size=4)      -> BatchLoader (fixed-shape, process-sharded)
- pl.Trainer(num_nodes=W, DDPStrategy) + fit()
                                -> jitted train/eval steps over a Mesh; XLA
                                   all-reduces grads over ICI (no strategy
                                   object, no process group)
- ModelCheckpoint(top1+last)    -> BestLastCheckpointer (same filenames)
- sync_dist=True metric logging -> global weighted (sum,count) metrics
- rank-0 artifact upload        -> coordinator-gated log_artifact to
                                   "best_checkpoints"

Plus what the reference lacks: true resume from full optimizer state
(TrainStateCheckpointer) and per-epoch wall-clock/throughput accounting.
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from dataclasses import dataclass, field

import jax

from dct_tpu import compilecache
from dct_tpu.config import RunConfig
from dct_tpu.data.dataset import WeatherArrays
from dct_tpu.observability.dump import write_train_metrics_prom
from dct_tpu.observability.goodput import (
    compile_report,
    config_hash,
    mesh_descriptor,
)
from dct_tpu.observability.health import TrainingHealthError
from dct_tpu.observability.roofline import program_report
from dct_tpu.parallel.distributed import is_coordinator
from dct_tpu.parallel.mesh import make_mesh
from dct_tpu.resilience.preempt import PreemptedError
from dct_tpu.tracking.client import get_tracker
from dct_tpu.train.epoch_loop import (  # noqa: F401 — early_stop_update: re-exported
    EpochLoop,
    device_ids,
    early_stop_update,
)
from dct_tpu.train.fit_setup import (  # noqa: F401 — optimizer_identity: re-exported
    FitSetup,
    optimizer_identity,
    prepare_fit,
)
from dct_tpu.train.telemetry import (  # noqa: F401 — _Timed: re-exported
    RunTelemetry,
    _Timed,
)


@dataclass
class TrainResult:
    val_loss: float
    val_acc: float
    best_model_path: str
    last_model_path: str
    history: list = field(default_factory=list)
    samples_per_sec: float = 0.0
    # Steady-state product throughput: mean per-chip rate over the epochs
    # AFTER the first (epoch 0 pays XLA compilation).
    steady_samples_per_sec_per_chip: float = 0.0
    run_id: str | None = None
    state: object | None = None
    # Goodput/badput summary (observability.goodput.GoodputLedger) and
    # the run-correlation ID every event record of this run carries.
    goodput: dict = field(default_factory=dict)
    run_correlation_id: str | None = None
    # Training-health summary (observability.health.HealthMonitor):
    # nan/spike event counts and the last loss/grad-norm observed.
    health: dict = field(default_factory=dict)
    # Where the run actually lived: sorted ids of the devices that held
    # shards of the final train state and of the first dispatched train
    # batch. A multi-chip run confined to device 0 shows up here.
    placement: dict = field(default_factory=dict)


class Trainer:
    def __init__(
        self, cfg: RunConfig, *, mesh=None, tracker=None,
        preempt_guard=None,
    ):
        self.cfg = cfg
        # Caller-owned PreemptionGuard (the multi-tenant scheduler's
        # lease revocation channel): fit() consults it instead of
        # building its own, so another thread can request() a graceful
        # stop of a fit running off the main thread (where SIGTERM
        # never arrives).
        self._preempt_guard = preempt_guard
        self.mesh = mesh if mesh is not None else make_mesh(cfg.mesh)
        self.coordinator = is_coordinator()
        self.tracker = tracker if tracker is not None else get_tracker(
            tracking_uri=cfg.tracking.tracking_uri,
            experiment=cfg.tracking.experiment,
            coordinator=self.coordinator,
        )

    # ------------------------------------------------------------------
    def fit(self, data: WeatherArrays | None = None) -> TrainResult:
        """Telemetry, setup, the epoch loop, the close: four parts, in
        ``train/telemetry.py``, ``fit_setup.py``, ``epoch_loop.py`` and
        ``_close`` below."""
        cfg = self.cfg
        # Persistent compile cache: arm it before this process's FIRST
        # compile (model init in the setup is one) — a supervised
        # relaunch then disk-hits every program its dead predecessor
        # already compiled. No-op unless the env arms it
        # (compilecache.cache docstring).
        compilecache.enable_from_env()
        tel = RunTelemetry.open(cfg, preempt_guard=self._preempt_guard)
        setup = prepare_fit(cfg, self.mesh, data, tel, tracker=self.tracker)
        # Kept for whoever drives the trainer (the benchmark reads the
        # epoch program's HLO text off ``aot_store.executables``).
        self.aot_store = setup.aot_store
        tel.arm_loop(
            cfg, n_chips=self.mesh.size,
            flops_per_sample=setup.flops_per_sample,
            target_epochs=setup.target_epochs,
            coordinator=self.coordinator,
        )
        loop = EpochLoop(
            cfg, self.mesh, setup, tel,
            tracker=self.tracker, coordinator=self.coordinator,
        )
        tel.startup.end(resumed=setup.start_epoch > 0)
        completed = preempted = False
        try:
            loop.run()
            completed = True
        except PreemptedError:
            preempted = True
            # Cooperative exit: close the tracking run (a preempt+resume
            # fleet would otherwise accumulate one phantom RUNNING run on
            # the MLflow server per preemption). Best-effort — closing
            # the books must never mask the preemption itself.
            self._end_tracking_quietly("KILLED")
            raise
        except TrainingHealthError:
            # Also a controlled raise (HealthMonitor.raise_on): mark the
            # run failed instead of leaking it as RUNNING.
            self._end_tracking_quietly("FAILED")
            raise
        finally:
            # Crash-path hygiene: never leave a jax.profiler session open,
            # a resume-state write un-joined, or the prefetch thread
            # running (each guarded so one cleanup failing cannot abandon
            # the others).
            try:
                tel.close_captures()
            finally:
                try:
                    setup.state_ckptr.wait()
                finally:
                    try:
                        loop.shutdown()
                    finally:
                        tel.end_loop(
                            completed=completed, preempted=preempted,
                            history=loop.history,
                            in_flight=loop.in_flight_spans(),
                        )
        return self._close(setup, loop, tel)

    # ------------------------------------------------------------------
    def _upload_best(self, setup: FitSetup, tel: RunTelemetry) -> str:
        """Rank-0 post-train artifact upload, mirroring
        jobs/train_lightning_ddp.py:146-164 (best, else last.ckpt
        fallback). Returns the path uploaded (or that would have been)."""
        with tel.timed(
            "checkpoint", "trainer.upload", parent_id=tel.fit_span.span_id
        ):
            best_path = setup.ckptr.best_model_path
            if self.coordinator:
                if not os.path.exists(best_path):
                    best_path = setup.ckptr.last_path
                if os.path.exists(best_path):
                    self.tracker.log_artifact(
                        best_path, artifact_path=self.cfg.tracking.artifact_path
                    )
                    # log_model parity (MLFlowLogger(log_model=True) logs the
                    # model object too, reference jobs/train_lightning_ddp.py:95):
                    # the checkpoint plus loader metadata under artifact path
                    # "model", so the registry carries a self-describing model
                    # artifact, not only the raw .ckpt.
                    with tempfile.TemporaryDirectory() as td:
                        mlmodel = os.path.join(td, "MLmodel.json")
                        with open(mlmodel, "w") as f:
                            json.dump(
                                {
                                    "flavor": "dct_tpu",
                                    "checkpoint": os.path.basename(best_path),
                                    "serving": "dct_tpu.serving.runtime",
                                    **setup.meta,
                                },
                                f,
                                indent=2,
                            )
                        self.tracker.log_artifact(mlmodel, artifact_path="model")
                        self.tracker.log_artifact(best_path, artifact_path="model")
        return best_path

    # ------------------------------------------------------------------
    def _close(
        self, setup: FitSetup, loop: EpochLoop, tel: RunTelemetry
    ) -> TrainResult:
        """After the loop: upload, the run-end goodput / compile /
        roofline reports, the Prometheus dump, ``fit_end``, the result."""
        cfg, events, ledger = self.cfg, tel.events, tel.ledger
        history, aot_store = loop.history, setup.aot_store
        best_path = self._upload_best(setup, tel)
        # Run-end goodput accounting: logged to the tracker NEXT TO
        # val_loss (a goodput regression becomes queryable exactly like
        # an accuracy regression), emitted as a structured event, and
        # dumped in Prometheus text exposition for scrape-less rigs.
        goodput_summary = ledger.summary()
        self.tracker.log_metrics(
            ledger.tracker_metrics(), step=loop.global_step
        )
        events.emit("trainer", "goodput_summary", **goodput_summary)
        # Compile/restart accounting (ROADMAP item 5's baseline): the
        # ledger's compile windows become compile.window events keyed by
        # the (family, config-hash, mesh) identity an AOT compilation
        # cache would use, and dct_compile_* series in the prom dump —
        # re-compiles of the SAME identity across restarts/workers are
        # the debt a persistent cache would erase.
        identity = dict(
            family=cfg.model.name,
            config_hash=config_hash(dataclasses.asdict(cfg.model)),
            mesh=mesh_descriptor(self.mesh),
        )
        compile_windows = compile_report(
            ledger.compile_windows,
            # cache="hit" windows were deserialized executables, not XLA
            # compiles — the label a warm-relaunch e2e asserts on.
            cache_states=aot_store.states,
            # Roofline provenance: analytic FLOPs / bytes / peak HBM
            # captured at compile time ride the window record.
            costs=aot_store.costs,
            **identity,
        )
        if self.coordinator:
            for w in compile_windows:
                events.emit("compile", "compile.window", **w)
        # Roofline join (observability.roofline): the cost-model numbers
        # against the ledger's measured steady-state dispatch windows —
        # live per-program MFU, arithmetic intensity, and the compute-
        # vs-memory-bound placement, as roofline.report events and the
        # dct_program_* gauges in the metrics dump below.
        roofline_rep = program_report(
            aot_store.costs,
            ledger.dispatch_stats,
            n_chips=self.mesh.size,
            **identity,
        )
        if self.coordinator:
            for r in roofline_rep:
                events.emit("roofline", "roofline.report", **r)
        # Retire the live per-epoch snapshot BEFORE the final dump
        # writes the terminal one under the same proc name — close()
        # removes the live file, the dump re-creates it as final.
        if tel.live_metrics is not None:
            tel.live_metrics.close()
        health_summary = tel.health.summary()
        # An explicit DCT_METRICS_PROM must work even with the event log
        # disabled (textfile-collector-only rigs clear DCT_EVENTS_DIR).
        if self.coordinator and cfg.obs.enabled and (
            cfg.obs.metrics_path or cfg.obs.events_dir
        ):
            write_train_metrics_prom(
                cfg.obs.metrics_path
                or os.path.join(cfg.obs.events_dir, "train_metrics.prom"),
                goodput_summary,
                run_id=events.run_id,
                samples_per_sec=tel.timer.samples_per_sec,
                val_loss=(
                    history[-1]["val_loss"] if history else float("nan")
                ),
                health=health_summary,
                resilience={
                    "faults_injected": tel.plan.fired_count,
                    "startup_debt_s": cfg.resilience.startup_debt_s,
                },
                compile_windows=compile_windows,
                roofline=roofline_rep,
                # Metrics plane: leave a final snapshot so a /metrics
                # scrape of the serving pool reports this run's goodput
                # and compile debt next to the request series.
                metrics_dir=cfg.obs.metrics_dir,
                proc=f"train-rank{jax.process_index()}",
            )
        self.tracker.end_run()

        final = history[-1] if history else {
            "val_loss": float("nan"), "val_acc": float("nan"),
        }
        events.emit(
            "trainer", "fit_end",
            val_loss=final["val_loss"], val_acc=final["val_acc"],
            epochs_run=len(history),
            goodput_fraction=goodput_summary["goodput_fraction"],
            health=health_summary["events"],
        )
        timed_epochs = tel.timer.history
        steady = timed_epochs[1:] if len(timed_epochs) > 1 else timed_epochs
        return TrainResult(
            val_loss=final["val_loss"],
            val_acc=final["val_acc"],
            best_model_path=best_path,
            last_model_path=setup.ckptr.last_path,
            history=history,
            samples_per_sec=tel.timer.samples_per_sec,
            steady_samples_per_sec_per_chip=(
                sum(s.samples_per_sec_per_chip for s in steady) / len(steady)
                if steady else 0.0
            ),
            run_id=setup.run_id,
            state=loop.state,
            goodput=goodput_summary,
            run_correlation_id=events.run_id,
            health=health_summary,
            placement={
                "state": device_ids(loop.state),
                "batch": loop.batch_devices,
            },
        )

    # ------------------------------------------------------------------
    def _end_tracking_quietly(self, status: str) -> None:
        try:
            self.tracker.end_run(status=status)
        except Exception:  # noqa: BLE001 — bookkeeping must not mask the exit
            pass

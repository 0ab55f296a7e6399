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

import gc
import math
import os
import sys
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from dct_tpu.checkpoint.manager import (
    BestLastCheckpointer,
    TrainStateCheckpointer,
    needs_cross_process_gather,
    to_host,
)
from dct_tpu.config import RunConfig
from dct_tpu.data.dataset import WeatherArrays, load_processed_dataset
from dct_tpu.data.pipeline import BatchLoader, contiguous_split, train_val_split
from dct_tpu.models.registry import get_model, is_sequence_model
from dct_tpu.ops.losses import precision_recall_f1
from dct_tpu.parallel.distributed import is_coordinator
from dct_tpu.parallel.mesh import (
    make_global_batch,
    make_global_epoch,
    make_global_epoch_chunk,
    make_mesh,
    process_data_block,
)
from dct_tpu.parallel.sharding_rules import (
    dtype_rules_digest,
    layout_mismatches,
    rules_digest,
    shard_state_with_rules,
    state_shardings,
)
from dct_tpu.observability import lineage as _lineage
from dct_tpu.observability.events import event_log_from_config
from dct_tpu.observability.goodput import GoodputLedger
from dct_tpu.observability.health import HealthMonitor, TrainingHealthError
from dct_tpu.observability.heartbeat import HeartbeatWriter
from dct_tpu.observability.spans import recorder_from_config
from dct_tpu.resilience import faults as _faults
from dct_tpu.resilience.preempt import PreemptedError, PreemptionGuard
from dct_tpu.tracking.client import get_tracker
from dct_tpu.train.state import create_train_state
from dct_tpu.utils.profiling import EpochTimer, Profiler
from dct_tpu.train.steps import (
    counter_metrics,
    make_epoch_train_eval_step,
    make_eval_step,
    make_train_step,
)


def early_stop_update(
    val_loss: float,
    best: float | None,
    stale: int,
    *,
    patience: int,
    min_delta: float,
) -> tuple[float | None, int, bool]:
    """One early-stopping step (monitor val_loss, min mode): returns the
    updated ``(best, stale, stop)``. A NaN val_loss never counts as an
    improvement — in particular a NaN on the FIRST monitored epoch must
    not seed ``best`` (nothing compares below NaN, which would turn every
    later finite epoch 'stale' and force a spurious stop)."""
    improved = not math.isnan(val_loss) and (
        best is None or val_loss < best - min_delta
    )
    if improved:
        return val_loss, 0, False
    return best, stale + 1, stale + 1 >= patience


def span_shadow_warning(
    history: list, span_end_vl_min: float, chunk: int
) -> str | None:
    """With ``epoch_chunk`` > 1 only span-END params exist on device, so
    the deploy "best" checkpoint can only ever hold a span-end epoch. If
    a mid-span epoch achieved the run's best val_loss, that optimum is
    recorded in history but unreachable by the checkpoint — a silent
    divergence operators should see named (ADVICE r4). Returns the
    warning line, or None."""
    if chunk <= 1 or not history:
        return None
    valid = [
        h["val_loss"] for h in history if not math.isnan(h["val_loss"])
    ]
    if not valid or min(valid) >= span_end_vl_min - 1e-12:
        return None
    return (
        f"[dct_tpu] epoch_chunk={chunk}: the run's best val_loss "
        f"{min(valid):.6f} occurred MID-span; the deploy 'best' "
        f"checkpoint holds the best span-END epoch "
        f"({span_end_vl_min:.6f}). Lower DCT_EPOCH_CHUNK if the deploy "
        "checkpoint must capture the optimum."
    )


def optimizer_identity(train_cfg) -> dict:
    """The knobs that select (and can reshape) the optax state tree
    (train.state.make_optimizer): the name picks the chain, ``momentum``
    > 0 adds the sgd trace leaf, and a positive ``weight_decay`` turns
    adam into adamw. Persisted in the train-state meta and compared
    EXACTLY on resume: two configs can produce structurally isomorphic
    opt_state trees (same leaf count, same shapes — e.g. adam vs adamw,
    whose decay transform holds no state), so the count/shape heuristic
    in checkpoint.manager.restore cannot catch a cross-restore between
    them (ADVICE r4). Values are plain JSON scalars so the comparison
    survives the meta.json round trip."""
    # Same normalization as state.make_optimizer: 'Adam' and ' adam'
    # build the identical chain and must not refuse each other.
    name = str(train_cfg.optimizer).strip().lower()
    wd = float(train_cfg.weight_decay)
    # Mirror make_optimizer's chain selection exactly (state.py): adam
    # with a positive weight_decay IS adamw, and adamw at wd == 0
    # degenerates to adam — spellings that build the identical chain
    # must not refuse each other's checkpoints.
    if name == "adam" and wd > 0:
        name = "adamw"
    elif name == "adamw" and wd == 0:
        name = "adam"
    return {
        "name": name,
        "momentum": float(train_cfg.momentum),
        "weight_decay": wd,
    }


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


@dataclass
class _SpanInFlight:
    """One dispatched span awaiting host bookkeeping (the pipelined
    loop's unit of deferral): its device result futures, the output
    state both checkpoint tiers will read, and the open trace spans the
    crash sweep must be able to close."""

    epoch0: int
    k: int
    n_steps: int
    state: object
    losses: object = None
    val_sums: object = None
    gnorms: object = None
    # The model's sown counters, summed per epoch (steps.py).
    counters: object = None
    t_dispatch: float = 0.0
    # Host seconds the dispatch call itself blocked (jit tracing + XLA
    # compile on a program's first span, ~enqueue cost after). Pipelined
    # billing uses it: see _consume_span's ledger note.
    dispatch_elapsed: float = 0.0
    dispatch_span: object = None
    epoch_span: object = None


@dataclass
class TrainResult:
    val_loss: float
    val_acc: float
    best_model_path: str
    last_model_path: str
    history: list = field(default_factory=list)
    samples_per_sec: float = 0.0
    # Steady-state product throughput: mean per-chip rate over the epochs
    # AFTER the first (epoch 0 pays XLA compilation) — the honest number
    # the bench reports as trainer_loop_samples_per_sec_per_chip.
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


def _device_ids(tree) -> list:
    """Sorted ids of every device holding a shard of any leaf of ``tree``."""
    ids: set = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            ids.update(d.id for d in leaf.sharding.device_set)
    return sorted(ids)


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
        cfg = self.cfg
        # Persistent compile cache: arm it before this process's FIRST
        # compile (model init below is one) — a supervised relaunch then
        # disk-hits every program its dead predecessor already compiled.
        # No-op unless the env arms it (compilecache.cache docstring).
        from dct_tpu import compilecache as _compilecache

        _compilecache.enable_from_env()
        # Observability plane: structured events (installed as the
        # process default so the checkpoint/tracking layers stamp the
        # same run-correlation ID), the goodput ledger, and this rank's
        # heartbeat. Everything degrades to no-ops when disabled.
        events = event_log_from_config(
            cfg.obs, rank=jax.process_index()
        )
        # Span runtime: this rank's spans join the cycle-wide trace
        # (trace_id = run-correlation ID; if a launcher spawned us, its
        # DCT_SPAN_ID makes fit a child of the launch span).
        tracer = recorder_from_config(cfg.obs, rank=jax.process_index())
        fit_span = tracer.open(
            "trainer.fit", component="trainer",
            model=cfg.model.name, epochs=cfg.train.epochs,
            world_size=jax.process_count(),
        )
        # Training-health telemetry: every step's loss (and grad global
        # norm) flows through the monitor; findings become health.*
        # events and, under a halting policy, stop the run.
        health = HealthMonitor.from_config(cfg.obs, emit=events.emit)
        # Live per-epoch metrics (ISSUE 17): the coordinator publishes
        # val-loss / goodput / step-time gauges to the metrics plane at
        # epoch cadence, so the telemetry history store (DCT_TS_DIR)
        # sees the run WHILE it happens — the final dump replaces this
        # stream at run end. None when the plane is unarmed.
        from dct_tpu.observability.dump import live_train_metrics

        live_metrics = live_train_metrics(
            cfg.obs, run_id=events.run_id, rank=jax.process_index()
        )
        # Resilience plane: the deterministic fault plan (installed as
        # the process default so the checkpoint tiers consult the SAME
        # instance — shared save ordinals and fired flags), and the
        # graceful-preemption guard. The SIGTERM handler only sets a
        # flag; the trainer honors it at the next step/span boundary.
        plan = _faults.FaultPlan.parse(
            cfg.resilience.fault_spec,
            rank=jax.process_index(),
            sleep_s=cfg.resilience.fault_sleep_s,
        )
        _faults.set_default(plan)
        guard = (
            self._preempt_guard
            if self._preempt_guard is not None
            else PreemptionGuard()
        )
        if cfg.resilience.graceful_preemption:
            guard.install()
        ledger = GoodputLedger()
        ledger.start()
        # Supervised-relaunch accounting: the wall clock the failed
        # attempts (and backoff) cost this cycle, booked as
        # startup_recovery badput so the healed run's goodput fraction
        # reflects what the failure actually cost.
        if cfg.resilience.startup_debt_s > 0:
            ledger.add("startup_recovery", cfg.resilience.startup_debt_s)
        heartbeat = None
        if cfg.obs.enabled and cfg.obs.heartbeat_dir:
            heartbeat = HeartbeatWriter(
                cfg.obs.heartbeat_dir,
                jax.process_index(),
                run_id=events.run_id,
                min_interval=cfg.obs.heartbeat_interval,
            )
            heartbeat.beat(phase="startup", force=True)
        events.emit(
            "trainer", "fit_start",
            model=cfg.model.name, epochs=cfg.train.epochs,
            resume=cfg.train.resume, world_size=jax.process_count(),
        )

        def timed(category, name, **attrs):
            return _Timed(ledger, tracer, category, name, **attrs)

        startup = timed("startup_recovery", "trainer.startup").begin()
        # Data-generation provenance for the always-on loop's freshness
        # accounting (dct_tpu.continuous): the incremental ETL stamps a
        # generation + arrival_ts into etl_state.json, read here BEFORE
        # the parquet load — so a checkpoint's stamped generation never
        # claims rows a concurrent ETL published after our snapshot.
        # Only when this fit loads the data itself: a caller-provided
        # array set has no provable tie to the processed dir.
        _data_provenance: dict = {}
        # Lineage ledger (installed as the process default alongside the
        # event log): checkpoints this run publishes get ``consumed``
        # edges to the dataset snapshot declared below.
        _lin = _lineage.ledger_from_config(cfg.obs, rank=jax.process_index())
        _lineage.set_run_inputs([])
        if data is None:
            from dct_tpu.etl.preprocess import read_etl_state

            _etl_state = read_etl_state(cfg.data.processed_dir)
            if _etl_state.get("generation"):
                _data_provenance = {
                    "data_generation": int(_etl_state["generation"]),
                    "data_arrival_ts": float(
                        _etl_state.get("arrival_ts") or 0.0
                    ),
                }
                # Stream-fed generations carry the committed offset
                # vector: the checkpoint names the exact log positions
                # its rows came from, the same way ``data_generation``
                # names the parquet snapshot.
                if _etl_state.get("stream_offsets") is not None:
                    _data_provenance["stream_offsets"] = [
                        int(o) for o in _etl_state["stream_offsets"]
                    ]
                # The ETL stamped its snapshot's lineage node id into the
                # state file — adopt it (no parquet re-hash) and put the
                # provenance dict on the graph record. A pre-lineage
                # state file (no stamp) re-addresses the snapshot dir by
                # content, landing on the same node id the ETL would
                # have minted.
                snap_nid = _etl_state.get("lineage_node")
                if _lin.enabled and not snap_nid:
                    snap_nid = _lin.node(
                        "dataset_snapshot",
                        path=os.path.join(
                            cfg.data.processed_dir, "data.parquet"
                        ),
                        attrs={
                            "generation": int(_etl_state["generation"]),
                        },
                    )
                elif _lin.enabled and snap_nid:
                    _lin.node(
                        "dataset_snapshot",
                        sha256=snap_nid.split(":", 1)[-1],
                        attrs=_data_provenance,
                    )
                _lineage.set_run_inputs([snap_nid])
        if data is None:
            data = load_processed_dataset(
                cfg.data.processed_dir,
                feature_suffix=cfg.data.feature_suffix,
                label_column=cfg.data.label_column,
            )

        # Sequence models train on sliding windows of the same stream; the
        # row-wise contract (and everything downstream: split, loader,
        # checkpointing) is unchanged because WindowArrays mirrors
        # WeatherArrays.
        sequence = is_sequence_model(cfg.model.name)
        if sequence:
            from dct_tpu.data.windows import make_windows
            from dct_tpu.models.registry import is_causal_model

            causal = is_causal_model(cfg.model.name)
            data = make_windows(
                data, cfg.model.seq_len,
                per_position_labels=causal,
                horizon=cfg.model.horizon if causal else 1,
            )
            # Overlapping windows leak under a random split; hold out the
            # TAIL of the stream, gapped by seq_len (+ the extra horizon
            # reach: train window i supervises label rows up to
            # i+seq_len+horizon-1) so no val window shares rows — feature
            # OR supervision — with any train window.
            gap = cfg.model.seq_len + (cfg.model.horizon - 1 if causal else 0)
            train_idx, val_idx = contiguous_split(
                len(data),
                val_fraction=cfg.data.val_fraction,
                gap=gap,
            )
        else:
            train_idx, val_idx = train_val_split(
                len(data), val_fraction=cfg.data.val_fraction, seed=cfg.train.seed
            )
        # Reference semantics: batch_size is per-rank (DataLoader(batch_size=4)
        # per container); global batch = per-device batch x data-parallel size.
        global_batch = cfg.train.batch_size * self.mesh.shape["data"]
        # Loader sharding follows the MESH, not the raw process count: DP
        # processes own distinct blocks of each global batch; processes that
        # only split the model/seq axes share their data rows and must feed
        # identical blocks (process_data_block encodes both cases).
        n_blocks, block_id = process_data_block(self.mesh)
        train_loader = BatchLoader(
            data, train_idx, global_batch=global_batch, shuffle=True,
            seed=cfg.train.seed, num_processes=n_blocks, process_id=block_id,
        )
        val_loader = BatchLoader(
            data, val_idx, global_batch=global_batch, shuffle=False,
            seed=cfg.train.seed, num_processes=n_blocks, process_id=block_id,
        )

        compute_dtype = jnp.bfloat16 if cfg.train.bf16_compute else jnp.float32
        if sequence:
            from dct_tpu.ops.attention import make_attention_fn

            model = get_model(
                cfg.model,
                input_dim=data.input_dim,
                compute_dtype=compute_dtype,
                attn_fn=make_attention_fn(self.mesh),
                mesh=self.mesh,
            )
            example_shape = (1, cfg.model.seq_len, data.input_dim)
        else:
            model = get_model(
                cfg.model, input_dim=data.input_dim, compute_dtype=compute_dtype
            )
            example_shape = None
        # Per-process state dir, constructed before the LR schedule: a
        # resumed run must size its cosine horizon from the restored
        # trajectory, not this run's budget alone.
        state_ckptr = TrainStateCheckpointer(
            os.path.join(
                cfg.data.models_dir, "train_state", f"p{jax.process_index()}"
            )
        )
        updates_per_epoch = train_loader.num_batches // max(
            1, cfg.train.grad_accum_steps
        )
        if cfg.train.grad_accum_steps > 1 and updates_per_epoch == 0:
            raise ValueError(
                f"grad_accum_steps={cfg.train.grad_accum_steps} exceeds the "
                f"{train_loader.num_batches} batches per epoch — every "
                "epoch would run ZERO optimizer updates"
            )

        lr_schedule = None
        # The decay horizon actually baked into the schedule (auto mode
        # resolves it from the restored trajectory): part of the AOT
        # store's program identity — the schedule's constants live
        # inside the compiled executable.
        resolved_decay = cfg.train.decay_steps
        if cfg.train.lr_schedule != "constant" or cfg.train.warmup_steps > 0:
            from dct_tpu.train.state import make_lr_schedule

            decay = cfg.train.decay_steps
            if cfg.train.lr_schedule == "cosine" and decay <= 0:
                # Auto: decay over the FULL trajectory. The optimizer's
                # restored update count already includes prior runs, so a
                # continuation sized only to THIS run's budget would start
                # at (or clamp to) the floor LR and train nothing.
                prior_epochs = 0
                if cfg.train.resume and state_ckptr.exists():
                    prior_epochs = int(
                        state_ckptr.load_meta().get("epochs_completed", 0)
                    )
                decay = max(
                    1,
                    (prior_epochs + cfg.train.epochs) * updates_per_epoch
                    - cfg.train.warmup_steps,
                )
            lr_schedule = make_lr_schedule(
                cfg.train.lr,
                schedule=cfg.train.lr_schedule,
                warmup_steps=cfg.train.warmup_steps,
                decay_steps=decay,
                end_lr_fraction=cfg.train.end_lr_fraction,
            )
            resolved_decay = decay
        state = create_train_state(
            model, input_dim=data.input_dim, lr=cfg.train.lr,
            seed=cfg.train.seed, example_shape=example_shape,
            lr_schedule=lr_schedule, weight_decay=cfg.train.weight_decay,
            grad_clip_norm=cfg.train.grad_clip_norm,
            optimizer=cfg.train.optimizer, momentum=cfg.train.momentum,
        )
        # Declarative partition rules: the per-family rule table (env-
        # overridable via DCT_SHARD_RULES) gives tensor-parallel
        # placement for the transformer family, full replication for
        # the MLP (no patterns match). TP/SP axes may span processes:
        # the checkpoint tier assembles such params with a cross-process
        # allgather (checkpoint.manager.to_host), called on EVERY rank
        # before the coordinator-gated write.
        state = shard_state_with_rules(
            state, self.mesh, shard_opt=cfg.train.shard_opt_state,
            shard_params=cfg.train.shard_params, family=cfg.model.name,
        )
        # The DECLARED layout. The jitted step's OUTPUT shardings can
        # drift from it — under ZeRO-1, XLA keeps the weight update (and
        # therefore the output params) sharded over ``data`` instead of
        # all-gathering — and the resume tier saves per-process local
        # shards of whatever layout the state actually has. Checkpoints
        # must be written in the declared layout, or a resumed process
        # (whose fresh template is the declared layout) cannot match the
        # saved shards to its topology. The first consumed span's output
        # is reconciled against this layout and any drift emitted as a
        # loud ``shard.layout_mismatch`` event (see _consume_span).
        declared_shardings = state_shardings(
            state, self.mesh, shard_opt=cfg.train.shard_opt_state,
            shard_params=cfg.train.shard_params, family=cfg.model.name,
        )

        # Continuous-training semantics (the reference re-trains from
        # scratch daily — its fit() never gets a ckpt_path, reference
        # jobs/train_lightning_ddp.py:143):
        # - no checkpoint          -> train epochs [0, cfg.train.epochs)
        # - interrupted prior run  -> finish to its saved target
        # - COMPLETED prior run    -> continue for cfg.train.epochs MORE
        #   epochs on the (possibly refreshed) data, keeping optimizer
        #   state — each DAG run extends the same optimization trajectory.
        start_epoch = 0
        target_epochs = cfg.train.epochs
        opt_identity = optimizer_identity(cfg.train)
        if cfg.train.resume and not state_ckptr.exists():
            # Cross-topology pivot: an MPMD session's per-stage
            # checkpoints (train_state_mpmd/stage<k>/, ISSUE 13) re-map
            # into the stacked SPMD layout — bitwise, pure data movement
            # — and this run resumes the same trajectory. An untileable
            # stage map (manifest stages != this model's n_stages)
            # refuses loudly inside the adoption.
            from dct_tpu.train import mpmd_trainer as _mpmd_tr

            _manifest = _mpmd_tr.read_manifest(cfg.data.models_dir)
            # Family-gated: a manifest left by a PP session must not
            # crash an unrelated family's resume in the same models_dir
            # (that run trains fresh, exactly as before the hook).
            if _manifest and _manifest.get("family") == cfg.model.name:
                _mpmd_tr.adopt_mpmd_checkpoint(cfg.data.models_dir, state)
        if cfg.train.resume and state_ckptr.exists():
            saved = state_ckptr.load_meta()
            saved_opt = saved.get("optimizer")
            if saved_opt is not None and saved_opt != opt_identity:
                # Named refusal BEFORE restore: opt_state trees of
                # different optimizer configs can be structurally
                # isomorphic (same leaf count/shapes), so the manager's
                # count/shape check would let a cross-restore through and
                # the run would train from mismatched moments.
                raise RuntimeError(
                    f"Resume refused: the checkpoint under "
                    f"{state_ckptr.dirpath} was written by optimizer "
                    f"{saved_opt} but this run configures {opt_identity}. "
                    "Restore the original DCT_OPTIMIZER / DCT_MOMENTUM / "
                    "DCT_WEIGHT_DECAY, or clear the train_state dir to "
                    "restart the trajectory."
                )
            # Restore yields host arrays; re-apply the mesh placement.
            state = shard_state_with_rules(
                state_ckptr.restore(state), self.mesh,
                shard_opt=cfg.train.shard_opt_state,
                shard_params=cfg.train.shard_params,
                family=cfg.model.name,
            )
            if "epochs_completed" in saved:
                start_epoch = int(saved["epochs_completed"])
            else:  # pre-meta checkpoint: derive from the step counter
                steps_per_epoch = max(train_loader.num_batches, 1)
                start_epoch = int(jax.device_get(state.step)) // steps_per_epoch
            saved_target = int(saved.get("target_epochs", cfg.train.epochs))
            if start_epoch >= saved_target:
                target_epochs = start_epoch + cfg.train.epochs
            else:
                target_epochs = saved_target
        if cfg.train.resume and jax.process_count() > 1:
            # All ranks must agree on start_epoch or the SPMD step counts
            # diverge and collectives deadlock. Fail loudly instead.
            from jax.experimental import multihost_utils

            epochs_seen = multihost_utils.process_allgather(
                jnp.asarray(start_epoch)
            )
            if int(epochs_seen.min()) != int(epochs_seen.max()):
                raise RuntimeError(
                    f"Resume divergence: per-process start epochs "
                    f"{list(map(int, epochs_seen))} differ. Sync or clear "
                    f"{os.path.join(cfg.data.models_dir, 'train_state')} "
                    "on every host."
                )

        ckptr = BestLastCheckpointer(cfg.data.models_dir)
        params_cross_process = needs_cross_process_gather(state.params)

        if start_epoch >= target_epochs:
            # Only reachable with epochs <= 0: the continuation semantics
            # above always extend the target past a completed run. Fail
            # LOUDLY — returning nan metrics here would let the DAG's
            # verify_model gate "pass" on a stale checkpoint having
            # trained nothing (VERDICT r1 weak-point 6).
            raise RuntimeError(
                f"Nothing to train: start_epoch={start_epoch} >= "
                f"target_epochs={target_epochs} (DCT_EPOCHS="
                f"{cfg.train.epochs}). Set a positive epoch budget."
            )
        use_scan = cfg.train.use_scan
        accum = max(1, cfg.train.grad_accum_steps)
        # Span pipelining (the dispatch-gap work): with prefetch_spans
        # >= 1, span e+1 is DISPATCHED before span e's bookkeeping runs,
        # so the health pass, tracker/event logging, and both checkpoint
        # tiers' writes all overlap device compute instead of
        # serializing the hot loop. The loop JOINS span e first (a
        # device_get of a few scalars that returns when its program
        # ends) and dispatches e+1 right after: program e's input state
        # is free by then, so TWO states are alive at a dispatch (e's
        # output, which e+1 reads and the bookkeeping saves, and e+1's
        # output), where dispatching behind a running program held
        # three. The price is the host time from the join's return to
        # the enqueue, once a span (trainer.epoch_gap_ms). Bounded to
        # ONE span in flight past the bookkeeping (early-stop and health
        # decisions trail the device by at most that span — see
        # _finish_span).
        # Auto-disabled under an armed fault plan: the injection drills
        # assert the exact serial crash/checkpoint ordering.
        pipelined = (
            use_scan
            and cfg.train.prefetch_spans >= 1
            and not plan.enabled
        )
        # AOT executable store (compilecache): the fused epoch programs
        # load-or-miss against <models_dir>/aot (override:
        # DCT_COMPILE_CACHE_AOT_DIR) — a resume snapshot's layout
        # carries its pre-compiled steps. The identity is the compile-
        # accounting key (family, model-config hash, resolved mesh)
        # PLUS the train knobs whose constants are baked into the
        # executable (optimizer chain, lr/schedule with its RESOLVED
        # decay horizon, precision, sharding, accumulation) and the
        # resolved donation mode — serial mode donates the input state,
        # and a donating executable loaded into the pipelined loop
        # would free a buffer the checkpoint tier still reads. Loop-
        # control knobs (epochs, resume, early-stop, logging cadence)
        # are deliberately OUT: a relaunch flips resume=1 and must
        # still hit. Disabled = a transparent pass-through.
        import dataclasses as _dc

        from dct_tpu.observability.goodput import (
            config_hash as _config_hash,
            mesh_descriptor as _mesh_descriptor,
        )

        _train_identity = {
            k: v
            for k, v in _dc.asdict(cfg.train).items()
            if k not in (
                "resume", "epochs", "log_every_n_steps",
                "early_stop_patience", "early_stop_min_delta",
                "prefetch_spans",
            )
        }
        _train_identity["decay_resolved"] = int(resolved_decay)
        # The partition-rule table is part of the program: a layout
        # change (DCT_SHARD_RULES, a family-table edit) compiles a
        # DIFFERENT executable — it must miss; the same layout must
        # warm-relaunch, sharded exactly like DP.
        _train_identity["shard_rules"] = rules_digest(cfg.model.name)
        # Same contract for the PRECISION table: the dtype rules pick
        # which param leaves run the step in bf16 (cast inside the
        # traced loss body, train/steps.py), so the compiled program
        # differs whenever they do — a precision change must be a loud
        # cache miss, never a stale full-width (or half-width)
        # executable. "off" when unset keys identically to every
        # pre-rules artifact.
        _train_identity["dtype_rules"] = dtype_rules_digest()
        aot_store = _compilecache.store_from_env(
            os.environ.get("DCT_COMPILE_CACHE_AOT_DIR")
            or os.path.join(cfg.data.models_dir, "aot"),
            family=cfg.model.name,
            config_hash=_config_hash(_dc.asdict(cfg.model)),
            mesh=_mesh_descriptor(self.mesh),
            extra={
                **_train_identity,
                "donate": not pipelined,
                "input_dim": data.input_dim,
            },
            emit=events.emit,
        )
        # Kept for whoever drives the trainer (the benchmark reads the
        # epoch program's HLO text off ``aot_store.executables``).
        self.aot_store = aot_store
        if use_scan:
            # Built only for the per-epoch path: with epoch_chunk > 1
            # every span (including k == 1 remainders) dispatches the
            # multi-epoch program instead. Span stacks are single-use in
            # the trainer, so donating them frees a full span of HBM
            # before the step's activations peak. The STATE is donated
            # only in serial mode: pipelined bookkeeping still reads the
            # previous span's output state (checkpoint gather + resume
            # snapshot) while the next span computes from it, so that
            # buffer must survive the dispatch — the second resident
            # state is the documented price of the overlap.
            if max(1, cfg.train.epoch_chunk) == 1:
                epoch_fused = aot_store.wrap(make_epoch_train_eval_step(
                    donate=not pipelined,
                    accum_steps=accum, donate_stacks=True,
                    with_grad_norms=True,
                ))
        else:
            train_step = make_train_step(
                accum_steps=accum, with_grad_norm=True
            )
            eval_step = make_eval_step()

        # Self-describing checkpoint meta: the FULL model config (whichever
        # family), plus the data-derived facts — enough to rebuild the model
        # from the checkpoint alone.
        import dataclasses as _dc

        meta = {
            **_dc.asdict(cfg.model),
            "model": cfg.model.name,
            "input_dim": data.input_dim,
            "feature_names": list(data.feature_names),
            # Which ETL generation this trajectory extension trained on
            # (empty pre-incremental-ETL): the loop's evaluator reads it
            # off the packaged meta to attribute promotion freshness.
            **_data_provenance,
        }
        meta.pop("name", None)
        run_id = self.tracker.start_run(params={**meta, "lr": cfg.train.lr,
                                                "batch_size": cfg.train.batch_size,
                                                "epochs": cfg.train.epochs,
                                                "seed": cfg.train.seed,
                                                # The split this run was
                                                # validated on: the deploy
                                                # side's eval harness must
                                                # rebuild EXACTLY it
                                                # (prepare_package stamps
                                                # both into the package
                                                # manifest).
                                                "val_fraction": cfg.data.val_fraction,
                                                "global_batch": global_batch})

        history: list[dict] = []
        global_step = int(jax.device_get(state.step))
        # Throughput accounting + optional one-epoch jax.profiler trace
        # (SURVEY §5.1: the reference installs TensorBoard but never writes
        # it — here the trace is real TB-compatible profile data).
        from dct_tpu.utils.profiling import (
            chip_peak_flops, transformer_train_flops,
        )

        flops_per_sample = None
        if cfg.model.name in ("weather_transformer", "weather_transformer_pp"):
            flops_per_sample = transformer_train_flops(
                d_model=cfg.model.d_model, d_ff=cfg.model.d_ff,
                seq_len=cfg.model.seq_len, n_heads=cfg.model.n_heads,
                n_layers=cfg.model.n_layers, input_dim=data.input_dim,
                batch=1, num_classes=cfg.model.num_classes,
            )
        timer = EpochTimer(
            n_chips=self.mesh.size,
            flops_per_sample=flops_per_sample,
            peak_flops=chip_peak_flops(),
            ledger=ledger,
        )
        profiler = Profiler(
            cfg.profile.trace_dir,
            enabled=cfg.profile.enabled,
            epoch=min(cfg.profile.epoch, target_epochs - 1),
            coordinator=self.coordinator,
        )
        # On-demand flight recorder (observability/capture.py): a
        # DCT_PROFILE_TRIGGER touch or SIGUSR2 starts a per-rank
        # jax.profiler capture at the next span boundary, mid-run,
        # without stopping training. Polling is one stat per span.
        from dct_tpu.observability.capture import (
            recorder_from_config as _flight_from_config,
        )

        flight = _flight_from_config(
            cfg.profile, rank=jax.process_index(), emit=events.emit,
        )

        # Pre-staged validation arrays (order is fixed): stacked AND
        # transferred to device once, reused every epoch.
        if use_scan:
            val_global = make_global_epoch(
                self.mesh, *self._stack_epoch(val_loader, 0)
            )

        es_best: float | None = None
        es_stale = 0
        batch_devices: list = []
        # For the epoch_chunk > 1 shadowing diagnostic: only span-END
        # params ever exist on device, so only span-end epochs can become
        # the deploy "best" checkpoint.
        span_end_vl_min = float("inf")

        # Epoch chunking (scan path): fuse K epochs into one dispatch —
        # one host round trip instead of K.
        # Per-epoch metrics are preserved (the fused program
        # returns losses[K, S] and a 6-tuple of [K] eval sums); checkpoints, resume
        # snapshots, and early-stop effects move to chunk boundaries
        # (config.TrainConfig.epoch_chunk documents the trade).
        chunk = max(1, cfg.train.epoch_chunk) if use_scan else 1
        multi_fused = None
        if chunk > 1:
            from dct_tpu.train.steps import make_multi_epoch_train_eval_step

            multi_fused = aot_store.wrap(make_multi_epoch_train_eval_step(
                donate=not pipelined,
                accum_steps=accum, donate_stacks=True,
                with_grad_norms=True,
            ))

        # Epoch-ahead input pipeline (scan path): the next span's host
        # batch assembly + H2D staging runs on a worker thread WHILE the
        # current span computes on device — shuffle/stack/device_put leave
        # the step critical path (device_put is async; the transfer itself
        # also overlaps compute). One span deep: bounded host memory, and
        # the device queue never sees stale epochs after an early stop.
        def _assemble_span(e0: int, k: int):
            # Spanned HERE so it follows the work onto the prefetch
            # thread (the consumer side only joins a future).
            with tracer.span("data.assemble", epoch=e0, k=k):
                per = []
                for e in range(e0, e0 + k):
                    xs, ys, ws = self._stack_epoch(train_loader, e)
                    # Data-pipeline fault hook: a `nan` clause poisons
                    # this epoch's staged features, so the non-finite
                    # loss arrives through the REAL compute path and the
                    # health policy (warn/halt) is exercised end-to-end.
                    if plan.enabled and plan.check("data", epoch=e):
                        import numpy as _np

                        xs = _np.array(xs, copy=True)
                        xs[0, ...] = _np.nan
                    if accum > 1:
                        # Whole accumulation groups only; the ragged tail
                        # (< accum batches) is dropped, like drop_last on
                        # the group granularity.
                        s_eff = (xs.shape[0] // accum) * accum
                        xs, ys, ws = xs[:s_eff], ys[:s_eff], ws[:s_eff]
                    per.append((xs, ys, ws))
                if k == 1 and multi_fused is None:
                    xs, ys, ws = per[0]
                    return xs.shape[0], make_global_epoch(
                        self.mesh, xs, ys, ws
                    )
                import numpy as _np

                kxs = _np.stack([p[0] for p in per])
                kys = _np.stack([p[1] for p in per])
                kws = _np.stack([p[2] for p in per])
                return kxs.shape[1], make_global_epoch_chunk(
                    self.mesh, kxs, kys, kws
                )

        prefetch_pool = None
        prefetched = None
        if use_scan and cfg.train.prefetch_spans >= 1:
            from concurrent.futures import ThreadPoolExecutor

            prefetch_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="epoch-prefetch"
            )
        # Everything up to here — dataset load, model init, state
        # creation/sharding, resume restore, validation staging — is the
        # run's startup/recovery cost in the goodput ledger (and the
        # trainer.startup span: the ledger's window, on the timeline).
        startup.end(resumed=start_epoch > 0)
        completed = False
        preempted = False
        # In-flight phase spans, tracked so a crash mid-epoch still
        # records them (Span.end is idempotent: the success path's own
        # end() wins and the crash-path sweep becomes a no-op).
        epoch_span = dispatch_span = bookkeep = None
        # Program keys already dispatched: a key's first dispatch_call is
        # its trace + AOT load or compile (attr first=true).
        dispatched_keys: set = set()
        # Pipelined mode: the one dispatched-but-unbookkept span. Its
        # results are consumed one iteration late, while the NEXT span
        # computes on device; the crash sweep also closes its spans.
        pending = None
        consumed_through = start_epoch
        timer_running = False
        layout_checked = False

        def _bookkeep_span(sp, sub_epochs, epoch_stats, span_updates,
                           counted=None):
            """Every host-side consequence of a finished span: goodput
            report, per-epoch history/tracker/event records, early-stop
            updates, and BOTH checkpoint tiers. Shared by the scan
            path's consume (where, pipelined, it all overlaps the next
            span's device compute) and the eager path. ``counted`` holds
            one dict an epoch of the model's own counters
            (steps.counter_metrics); they join the epoch's tracker
            metrics and its ``epoch_end`` event. Returns
            ``stop_early``, and lets go of the span's state: the next
            dispatch must find two states alive, not three."""
            nonlocal es_best, es_stale, span_end_vl_min
            nonlocal consumed_through, bookkeep, layout_checked
            e0, k = sp.epoch0, sp.k
            # The scan path's consume opened it right after its join;
            # the eager path enters here.
            if bookkeep is None:
                bookkeep = timed(None, "trainer.bookkeep", epoch=e0).begin()
            # Declared-vs-actual layout reconciliation, once, on the
            # FIRST span the jitted step produced: its output shardings
            # can drift from the declared rule layout (ZeRO-1 keeps the
            # updated params data-sharded), and silently checkpointing
            # whatever layout fell out is how a resume refusal is born.
            # The drift goes on the record LOUDLY; the device_put re-pin
            # below reconciles the checkpoint to the declared layout.
            if not layout_checked:
                layout_checked = True
                _drift = layout_mismatches(sp.state, declared_shardings)
                if _drift:
                    events.emit(
                        "shard", "shard.layout_mismatch",
                        leaves=len(_drift),
                        reconciled=True,
                        examples=_drift[:3],
                    )
            # Per-span goodput: category deltas since the previous
            # report, logged to the tracker next to val_loss so a
            # goodput regression is queryable like an accuracy one.
            span_goodput = ledger.epoch_report()
            if heartbeat is not None:
                heartbeat.beat(
                    step=global_step, epoch=e0 + k - 1, phase="train"
                )
            # Per-epoch bookkeeping for every epoch in the span; with
            # k > 1 the chunk is the dispatch unit, so wall time is
            # span-amortized and the metric step is reconstructed per
            # epoch from the update count.
            per_epoch_updates = span_updates // k if k else 0
            last_rec = None
            stop_early = False
            for i, (epoch_loss, val_loss, val_acc, (tp, fp, fn)) in (
                enumerate(sub_epochs)
            ):
                epoch_rec = {
                    "epoch": e0 + i,
                    "train_loss": epoch_loss if epoch_loss is not None else float("nan"),
                    "val_loss": val_loss,
                    "val_acc": val_acc,
                }
                epoch_metrics = {
                    "train_loss_epoch": epoch_rec["train_loss"],
                    "val_loss": val_loss,
                    "val_acc": val_acc,
                    "epoch_time": epoch_stats.seconds / k,
                    "samples_per_sec": epoch_stats.samples_per_sec,
                    "samples_per_sec_per_chip": epoch_stats.samples_per_sec_per_chip,
                    # Span-level fraction (the span is the dispatch
                    # unit; every epoch in it shares the value).
                    "goodput_fraction": span_goodput["goodput_fraction"],
                }
                if cfg.model.num_classes == 2:
                    # Positive class 1 = "rain" (the reference's label
                    # encoding, jobs/preprocess.py:23-25). One-vs-rest
                    # counts would mislead for num_classes > 2, so the
                    # P/R/F1 surface is binary-only.
                    val_precision, val_recall, val_f1 = precision_recall_f1(
                        tp, fp, fn
                    )
                    epoch_rec["val_f1"] = val_f1
                    epoch_metrics.update(
                        val_precision=val_precision,
                        val_recall=val_recall,
                        val_f1=val_f1,
                    )
                history.append(epoch_rec)
                if epoch_stats.mfu is not None:
                    epoch_metrics["mfu"] = epoch_stats.mfu
                epoch_counted = counted[i] if counted else {}
                epoch_metrics.update(epoch_counted)
                metric_step = (
                    global_step - span_updates
                    + (i + 1) * per_epoch_updates
                    if use_scan else global_step
                )
                self.tracker.log_metrics(epoch_metrics, step=metric_step)
                events.emit(
                    "trainer", "epoch_end",
                    epoch=e0 + i,
                    train_loss=epoch_rec["train_loss"],
                    val_loss=val_loss, val_acc=val_acc,
                    goodput_fraction=span_goodput["goodput_fraction"],
                    **epoch_counted,
                )
                if live_metrics is not None:
                    live_metrics.epoch_end(
                        val_loss=val_loss,
                        goodput_fraction=span_goodput["goodput_fraction"],
                        samples_per_sec=epoch_stats.samples_per_sec,
                        step_seconds=(
                            (epoch_stats.seconds / k)
                            / max(1, per_epoch_updates)
                        ),
                        grad_norm=health.last_grad_norm,
                    )
                last_rec = epoch_rec
                # Early stopping (monitor val_loss, min mode — the
                # companion of the reference's ModelCheckpoint
                # policy). val_loss is a globally-reduced scalar, so
                # every SPMD rank takes the same branch; a nan never
                # counts as an improvement (including as the first
                # es_best). Inside a span the epochs already ran on
                # device; the stop takes effect at the span boundary,
                # and the es state freezes at the trigger point.
                if cfg.train.early_stop_patience > 0 and not stop_early:
                    es_best, es_stale, stop_early = early_stop_update(
                        val_loss, es_best, es_stale,
                        patience=cfg.train.early_stop_patience,
                        min_delta=cfg.train.early_stop_min_delta,
                    )
            _span_end_vl = sub_epochs[-1][1]
            if not math.isnan(_span_end_vl):
                span_end_vl_min = min(span_end_vl_min, _span_end_vl)
            profiler.maybe_stop_span(e0, k)
            bookkeep.end()
            bookkeep = None
            # Both checkpoint tiers' synchronous cost (host gather,
            # deploy-tier writes, the resume snapshot's device->host
            # copy; the npz write itself overlaps on a worker thread).
            # A stack span: the checkpoint manager's own spans parent
            # implicitly to this thread's stack top, and they belong
            # under the trainer.checkpoint window. Safe under pipelining
            # — the whole window is synchronous inside this consume,
            # nothing else touches the stack in between.
            with timed(
                "checkpoint", "trainer.checkpoint",
                epoch=e0 + k - 1, parent_id=sp.epoch_span.span_id,
            ):
                # Host-gather BEFORE the coordinator gate: with TP/SP
                # spanning processes this is a collective every rank
                # must join; in the common fully-addressable case only
                # the coordinator pays the device-to-host copy.
                # Pipelined: the gathered state is the NEXT span's live
                # input — valid because the fused step does not donate
                # it in that mode.
                if params_cross_process or self.coordinator:
                    with tracer.span("trainer.gather_params"):
                        host_params = to_host(sp.state.params)
                if self.coordinator:
                    # Deploy-checkpoint policy at span granularity: only
                    # the span-end params exist on device, so best/last
                    # selection sees the span-end epoch's metrics (k == 1
                    # reduces to the per-epoch policy exactly).
                    _, last_vl, last_va, _ = sub_epochs[-1]
                    ckpt_metrics = {"val_loss": last_vl, "val_acc": last_va}
                    if "val_f1" in last_rec:
                        ckpt_metrics["val_f1"] = last_rec["val_f1"]
                    ckptr.update(
                        epoch=e0 + k - 1,
                        metrics=ckpt_metrics,
                        params=host_params,
                        meta=meta,
                    )

                # Every process keeps its own resume state (host-local
                # disk) plus the run facts the next run's continuation
                # semantics are decided from. The write overlaps the next
                # epoch's compute (device->host snapshot is synchronous;
                # the npz/rotation runs on a worker thread). On an early
                # stop the run is marked COMPLETE at the stop point
                # (target_epochs = epochs_completed) so a resumed run
                # EXTENDS (continuous semantics) instead of "finishing"
                # the abandoned target.
                # Re-pin to the declared layout before snapshotting (a
                # no-op for leaves already there; a collective reshard —
                # every rank calls it — for any the step's output layout
                # drifted, e.g. ZeRO-1 output params).
                state_ckptr.save_async(
                    jax.device_put(sp.state, declared_shardings),
                    meta={
                        "epochs_completed": e0 + k,
                        "target_epochs": (
                            e0 + k if stop_early else target_epochs
                        ),
                        # Exact resume refusal across optimizer configs
                        # whose state trees are isomorphic (ADVICE r4).
                        "optimizer": opt_identity,
                    },
                )
            sp.epoch_span.end(val_loss=sub_epochs[-1][1])
            consumed_through = e0 + k
            # The next dispatch must find two states alive, not three.
            sp.state = None
            return stop_early

        def _join_span(sp):
            """Wait for span ``sp``'s program: returns the join bracket.
            Once it returns the program's input state is no longer held
            by the device. Pipelined, the loop calls it right BEFORE the
            next dispatch, so it waits on ONE small output and reads
            nothing back: every device_get of a result is a round trip
            of its own on the TPU, and what stands between this return
            and the next enqueue is time the device idles."""
            with timed(
                None, "trainer.join", epoch=sp.epoch0, k=sp.k
            ) as join:
                # While the program still runs, collect: a full gc, which
                # also has jax drop the Python references of the buffers
                # the bookkeeping let go (the state before this one). Left
                # alone, both happen inside the next dispatch call (159 ms
                # of PythonRefManager::CollectGarbage in the profile) or
                # whenever the allocator's counters say, between this
                # join's return and the enqueue. A program that has ended
                # means the host sets the pace: nothing to hide it under.
                if pipelined and not sp.losses.is_ready():
                    gc.collect()
                jax.block_until_ready(sp.losses)
            return join

        def _finish_span(sp, join):
            """All host bookkeeping of the joined span ``sp``. Serial
            mode runs it right after the join; pipelined mode after the
            NEXT span's dispatch, while that span computes on device (so
            early-stop/health decisions trail the device by at most one
            span — the documented trade). Returns ``stop_early``."""
            nonlocal global_step, dispatch_span, epoch_span, bookkeep
            import numpy as _np

            e0, k = sp.epoch0, sp.k
            # The program has ended and the D2H copies were started right
            # after its dispatch: the bytes are on the host or on their
            # way.
            if multi_fused is not None:
                # [K, S] losses; val_sums is a 6-tuple of [K] arrays
                # (dtype-preserving per leaf — see
                # make_multi_epoch_train_eval_step). Stack host-side as
                # float64 -> [K, 6]; the upcast only protects the
                # stacking, precision is bounded by the on-device f32
                # accumulation (exact for integral weights up to 2^24
                # per epoch, steps.py).
                losses_host = _np.asarray(jax.device_get(sp.losses))
                gnorms_host = _np.asarray(jax.device_get(sp.gnorms))
                val_host = _np.stack(
                    [
                        _np.asarray(v, dtype=_np.float64)
                        for v in jax.device_get(sp.val_sums)
                    ],
                    axis=1,
                )
            else:  # [S] / 6-tuple — the k == 1 parity layout
                losses_host = _np.asarray(jax.device_get(sp.losses))[None]
                gnorms_host = _np.asarray(jax.device_get(sp.gnorms))[None]
                val_host = _np.asarray(
                    [float(v) for v in jax.device_get(sp.val_sums)]
                )[None]
            counters_host = jax.device_get(sp.counters)
            # Point the crash sweep at the span being bookkept: if this
            # dies, THESE are the spans still in flight (a pipelined
            # successor's live in pending).
            dispatch_span = sp.dispatch_span
            epoch_span = sp.epoch_span
            # Everything between the join and the checkpoint section
            # (tracker, events, health, heartbeat); the ledger leaves
            # it unattributed. _bookkeep_span closes it.
            bookkeep = timed(None, "trainer.bookkeep", epoch=e0).begin()
            # Fused dispatch (train + eval in one program) bills to
            # train_step; its first occurrence per program shape is the
            # compile. Serial: one window, dispatch -> results joined
            # (the historical accounting). Pipelined: the wall interval
            # dispatch(e) -> consume(e) CONTAINS other billed windows
            # (the previous span's checkpoint, the next span's
            # data_wait), so billing it whole would double-count and
            # push goodput_fraction past 1 — bill only the two
            # main-thread-blocking windows instead: the dispatch call
            # itself (trace + compile + enqueue, captured at dispatch)
            # plus the join above. Device time overlapped by host
            # bookkeeping is exactly the overlap the mode buys; it
            # surfaces as the other categories' windows, never twice.
            # (The join now precedes the successor's dispatch call, so
            # the two windows stay disjoint.)
            _billed = (
                (sp.dispatch_elapsed + join.seconds)
                if pipelined
                else (join.t1 - sp.t_dispatch)
            )
            _billed_cat = ledger.add_dispatch(
                "train_step", f"scan_k{k}", _billed,
            )
            sp.dispatch_span.end()
            # The fused program runs the validation pass(es) inside the
            # timed window; credit them to MFU. Pipelined throughput
            # windows chain consume-to-consume (they tile the loop's
            # wall clock); serial keeps the historical start-to-join
            # window.
            epoch_stats = timer.stop(
                e0, k * sp.n_steps * global_batch,
                eval_samples=k * len(val_idx),
            )
            if pipelined and _billed_cat != "compile":
                # Roofline truth-up: the goodput bill above is only the
                # host-BLOCKING part of the window (the overlap the
                # pipelined mode buys); the per-program MFU join needs
                # the wall window the dispatch actually occupied — the
                # consume-to-consume timer window just closed.
                ledger.amend_dispatch_window(
                    f"scan_k{k}", epoch_stats.seconds - _billed,
                )
            if pipelined:
                timer.start()
            flat = losses_host.reshape(-1)
            # log_every_n_steps cadence without one Python iteration
            # per step: visit only the multiples (identical records).
            n_log = max(1, cfg.train.log_every_n_steps)
            for i in range(
                (-(global_step + 1)) % n_log, flat.size, n_log
            ):
                self.tracker.log_metrics(
                    {"train_loss": float(flat[i])},
                    step=global_step + i + 1,
                )
            global_step += flat.size
            # Step-trigger faults on the scan path fire at the span
            # boundary — steps inside a fused dispatch are not
            # individually interruptible from the host.
            if plan.enabled:
                plan.maybe_fire(
                    "step", step=global_step,
                    pre_exit=state_ckptr.wait,
                )
            # Health pass over the span's per-step losses and grad
            # norms BEFORE any epoch bookkeeping: under a halting
            # policy the run stops here — no epoch_end, no checkpoint
            # of the diverged state. (Pipelined: the successor span
            # already in flight is abandoned by the raise — at most one
            # extra span of device work, never an extra checkpoint.)
            halt_finding = health.observe_span(
                flat, gnorms_host.reshape(-1),
                start_step=global_step - flat.size,
                epoch=e0, steps_per_epoch=max(1, flat.size // k),
            )
            if halt_finding is not None:
                # Close the epoch span BEFORE raising: the halted epoch
                # is exactly the one the operator opens the trace to
                # inspect.
                sp.epoch_span.end(halted=halt_finding.kind)
            HealthMonitor.raise_on(halt_finding)
            # Reference parity: the logged train_loss is the
            # EPOCH-AGGREGATED mean (Lightning epoch aggregation of
            # jobs/train_lightning_ddp.py:70), not the last batch —
            # one (train_loss, val_loss, val_acc, counts) entry per
            # epoch in the span.
            sub_epochs = []
            for i in range(k):
                ls, accs, c, tp, fp, fn = (
                    float(v) for v in val_host[i]
                )
                sub_epochs.append((
                    float(losses_host[i].mean())
                    if losses_host[i].size else None,
                    ls / c if c else float("nan"),
                    accs / c if c else float("nan"),
                    (tp, fp, fn),
                ))
            # The model's counters: one tree a span on the k == 1 path,
            # a leading epoch axis under the multi-epoch program.
            counted = [
                counter_metrics(
                    counters_host if multi_fused is None
                    else jax.tree.map(lambda c, i=i: c[i], counters_host)
                )
                for i in range(k)
            ] if jax.tree.leaves(counters_host) else None
            return _bookkeep_span(
                sp, sub_epochs, epoch_stats, flat.size, counted
            )

        def _consume_span(sp):
            """Join, then bookkeep (the serial order)."""
            return _finish_span(sp, _join_span(sp))

        try:
            epoch = start_epoch
            stop_early = False
            while epoch < target_epochs:
                # Pipelined early-stop guard: if the un-bookkept span
                # could trip the stop, consume it BEFORE dispatching
                # more work (serial fallback for exactly this span, so
                # the stop decision is never speculated past).
                if (
                    pending is not None
                    and cfg.train.early_stop_patience > 0
                    and es_stale + pending.k
                    >= cfg.train.early_stop_patience
                ):
                    _sp, pending = pending, None
                    stop_early = _consume_span(_sp)
                    if guard.requested:
                        self._preempt_exit(
                            guard, events, state_ckptr,
                            epochs_completed=consumed_through,
                        )
                    if stop_early:
                        break
                # Trainer fault hook at the epoch boundary (`crash` /
                # `hang` / `slow_epoch` clauses). A crash first joins
                # any in-flight resume-snapshot write so the death
                # leaves a deterministic resume point — torn-write
                # recovery has its own injector (`crash_save`).
                # (Pipelining is auto-disabled while a plan is armed,
                # so the hook always sees fully-bookkept prior epochs.)
                if plan.enabled:
                    plan.maybe_fire(
                        "epoch", epoch=epoch, pre_exit=state_ckptr.wait
                    )
                k = min(chunk, target_epochs - epoch) if use_scan else 1
                # Span boundary = the flight recorder's poll point: an
                # operator trigger starts (or a passed deadline stops)
                # a capture here, between dispatches, never inside one.
                flight.poll(epoch=epoch)
                profiler.maybe_start_span(epoch, k)
                # One span per dispatch unit: the trace's "trainer
                # epochs" row. Parenting is EXPLICIT (not thread-stack):
                # pipelined, span e is still open when span e+1 starts,
                # so stack-implicit parenting would chain epochs under
                # each other and leak the stack.
                epoch_span = tracer.start(
                    "trainer.epoch", component="trainer",
                    epoch=epoch, k=k, parent_id=fit_span.span_id,
                )
                # Pipelined throughput windows chain consume-to-consume
                # (started once here, re-armed by each consume); serial
                # keeps one window per span, started at the boundary.
                if not (pipelined and timer_running):
                    timer.start()
                    timer_running = True
                if use_scan:
                    # Goodput: joining the prefetch future (or assembling
                    # inline) is time the DEVICE spends waiting on data.
                    with timed(
                        "data_wait", "trainer.data_wait",
                        epoch=epoch, parent_id=epoch_span.span_id,
                    ):
                        if prefetched is not None:
                            n_steps, globs = prefetched.result()
                        else:
                            n_steps, globs = _assemble_span(epoch, k)
                    # Train span + full eval in ONE dispatch.
                    # Beat BEFORE the span's dispatch: the fused program
                    # can legitimately block for minutes (first-span
                    # compile, k fused epochs), and the monitor must see
                    # the rank reached the dispatch rather than ageing
                    # the previous span-end beat across the whole gap.
                    # (Size DCT_HEARTBEAT_STALL_SECONDS above the
                    # longest expected single dispatch.)
                    if heartbeat is not None:
                        heartbeat.beat(
                            step=global_step, epoch=epoch, phase="dispatch",
                        )
                    # Pipelined: wait for the span in flight BEFORE
                    # dispatching this one (its data is staged already).
                    # Its program's input state is then free and the
                    # state it bookkept last was let go, so this
                    # dispatch finds two states alive. Its read-back and
                    # bookkeeping wait until this span runs on the device.
                    joined = (
                        _join_span(pending)
                        if pipelined and pending is not None else None
                    )
                    # The dispatch window closes at block_until_ready
                    # below; a span of k epochs and a ragged remainder
                    # span are DIFFERENT XLA programs, so the ledger's
                    # compile detection keys on k.
                    # dct: begin-no-host-sync — the pipelined dispatch
                    # region: from here until the bookkeeping swap,
                    # nothing may join device results (device_get,
                    # float()/int() on arrays, .block_until_ready()) or
                    # the one-span overlap PR 5 bought collapses back to
                    # serial. The join belongs in _join_span, above for
                    # the span in flight and one iteration later for
                    # this one. Enforced by dct-lint rule `span-sync`.
                    _key = f"scan_k{k}"
                    # Dispatch to join: overlaps its successor under
                    # pipelining, so JSONL-only (spans.py).
                    dispatch_span = tracer.start(
                        "trainer.dispatch", component="trainer",
                        epoch=epoch, k=k, key=_key,
                        parent_id=epoch_span.span_id,
                    )
                    # Host-blocking cost of the dispatch call itself
                    # (jit trace + AOT load or XLA compile on the first
                    # span of a program shape; ~enqueue after) — the
                    # pipelined ledger bills this window separately
                    # from the consume-time join so category windows
                    # stay main-thread sequential (never double-counted).
                    with timed(
                        None, "trainer.dispatch_call", epoch=epoch,
                        key=_key, first=_key not in dispatched_keys,
                        parent_id=dispatch_span.span_id,
                    ) as dispatch_call:
                        dispatched_keys.add(_key)
                        # `key=` threads the goodput dispatch key into
                        # the AOT store so cache hit/miss states line up
                        # 1:1 with the compile.window accounting below.
                        if not batch_devices:
                            batch_devices = _device_ids(globs)
                        state, losses, val_sums, gnorms, counters = (
                            multi_fused or epoch_fused
                        )(state, *globs, *val_global, key=_key)
                    # Non-blocking bookkeeping: start the D2H copies of
                    # everything consume will read NOW, so by the time
                    # the span is bookkept the bytes are already on the
                    # host and device_get just unblocks.
                    for _buf in (
                        losses, gnorms, *val_sums, *jax.tree.leaves(counters)
                    ):
                        try:
                            _buf.copy_to_host_async()
                        except (AttributeError, RuntimeError):
                            break
                    # Prefetch the next span UNLESS early stopping is
                    # armed and could trigger within this span or the
                    # still-unbookkept previous one: the next span may
                    # never run, and a speculative multi-epoch H2D
                    # would sit in HBM through checkpointing/upload
                    # for nothing.
                    speculative_ok = not (
                        cfg.train.early_stop_patience > 0
                        and es_stale
                        + (pending.k if pending is not None else 0)
                        + k
                        >= cfg.train.early_stop_patience
                    )
                    nxt = epoch + k
                    if (
                        prefetch_pool is not None
                        and nxt < target_epochs
                        and speculative_ok
                    ):
                        prefetched = prefetch_pool.submit(
                            _assemble_span, nxt,
                            min(chunk, target_epochs - nxt),
                        )
                    else:
                        prefetched = None
                    cur = _SpanInFlight(
                        epoch0=epoch, k=k, n_steps=n_steps, state=state,
                        losses=losses, val_sums=val_sums, gnorms=gnorms,
                        counters=counters,
                        t_dispatch=dispatch_call.t0,
                        dispatch_elapsed=dispatch_call.seconds,
                        dispatch_span=dispatch_span,
                        epoch_span=epoch_span,
                    )
                    # dct: end-no-host-sync — serial mode joins its own
                    # span here; pipelined joined the PREVIOUS one above,
                    # before this dispatch, and bookkeeps it now.
                    if pipelined:
                        # Swap FIRST: if bookkeeping the previous span
                        # raises (health halt), the finally sweep still
                        # finds the in-flight successor via `pending`.
                        _sp, pending = pending, cur
                        stop_early = (
                            _finish_span(_sp, joined) if _sp is not None
                            else False
                        )
                    else:
                        stop_early = _consume_span(cur)
                else:
                    import numpy as _np

                    loss_sum = 0.0
                    n_steps = 0
                    n_updates = 0
                    # Data-pipeline fault hook (eager path): poison the
                    # epoch's first staged group.
                    poison = plan.enabled and bool(
                        plan.check("data", epoch=epoch)
                    )
                    group: list = []
                    for batch in train_loader.epoch(epoch):
                        group.append(batch)
                        if len(group) < accum:
                            continue
                        with timed("data_wait", "data.stage"):
                            if accum > 1:
                                bx = _np.concatenate([b.x for b in group])
                                by = _np.concatenate([b.y for b in group])
                                bw = _np.concatenate(
                                    [b.weight for b in group]
                                )
                            else:
                                bx, by, bw = (
                                    group[0].x, group[0].y,
                                    group[0].weight,
                                )
                            if poison:
                                poison = False
                                bx = _np.array(bx, copy=True)
                                bx[0, ...] = _np.nan
                            x, y, w = make_global_batch(self.mesh, bx, by, bw)
                            if not batch_devices:
                                batch_devices = _device_ids(x)
                        group = []
                        # The device_get of the loss is the step's real
                        # sync point — include it in the dispatch window.
                        with ledger.dispatch("train_step", key="eager_step"):
                            state, metrics = train_step(state, x, y, w)
                            m_host = jax.device_get(metrics)
                            loss_host = float(m_host["train_loss"])
                        global_step += 1
                        # Step-trigger faults (`crash@...:stepN` /
                        # `hang@...:stepN`): fired after the step's sync
                        # point, before this step's heartbeat — a hung
                        # rank stops beating exactly here, which is what
                        # the stall monitor exists to see.
                        if plan.enabled:
                            plan.maybe_fire(
                                "step", step=global_step,
                                pre_exit=state_ckptr.wait,
                            )
                        # Per-step health: a halting policy stops the
                        # run MID-epoch on the eager path (epoch span
                        # closed first so the halted epoch is on the
                        # trace).
                        finding = health.observe_step(
                            loss_host,
                            grad_norm=float(m_host["grad_norm"]),
                            step=global_step, epoch=epoch,
                        )
                        if finding is not None and finding.halt:
                            epoch_span.end(halted=finding.kind)
                        HealthMonitor.raise_on(finding)
                        n_steps += accum
                        n_updates += 1
                        loss_sum += loss_host
                        # Per-step liveness on the eager path (the
                        # writer's min_interval throttles the I/O).
                        if heartbeat is not None:
                            heartbeat.beat(
                                step=global_step, epoch=epoch, phase="train",
                            )
                        if global_step % cfg.train.log_every_n_steps == 0:
                            self.tracker.log_metrics(
                                {"train_loss": loss_host}, step=global_step
                            )
                        # Graceful preemption (eager path): the in-flight
                        # step just finished and synced — save a resume
                        # checkpoint NOW (epochs_completed = the last
                        # full epoch: resume restarts this one, losing
                        # under one epoch of progress) and exit
                        # PREEMPTED via the entry point.
                        if guard.requested:
                            epoch_span.end(preempted=True)
                            self._preempt_exit(
                                guard, events, state_ckptr,
                                state=jax.device_put(
                                    state, declared_shardings
                                ),
                                epochs_completed=epoch,
                                target_epochs=target_epochs,
                                opt_identity=opt_identity,
                            )
                    # A ragged tail (< accum batches) is dropped, matching
                    # the scan path's group-granular drop_last.
                    jax.block_until_ready(state.params)
                    epoch_stats = timer.stop(epoch, n_steps * global_batch)
                    epoch_loss = loss_sum / n_updates if n_updates else None

                    with ledger.dispatch("eval", key="eager_eval"), \
                            tracer.span(
                                "trainer.eval", component="trainer",
                                epoch=epoch,
                                parent_id=epoch_span.span_id,
                            ):
                        val_loss, val_acc, (tp, fp, fn) = self._evaluate(
                            state, eval_step, val_loader
                        )
                    stop_early = _bookkeep_span(
                        _SpanInFlight(
                            epoch0=epoch, k=1, n_steps=n_steps,
                            state=state, epoch_span=epoch_span,
                        ),
                        [(epoch_loss, val_loss, val_acc, (tp, fp, fn))],
                        epoch_stats, 0,
                    )
                epoch += k
                # Graceful preemption at the span boundary: the last
                # BOOKKEPT span's resume snapshot was just submitted —
                # first drain any still-in-flight span so its progress
                # is durable too (matching serial semantics: everything
                # dispatched gets consumed), then join the write and
                # exit PREEMPTED. With epoch_chunk=1 at most one epoch
                # of progress is in flight when SIGTERM lands, so the
                # resume loses at most that epoch.
                if guard.requested:
                    if pending is not None:
                        _sp, pending = pending, None
                        _consume_span(_sp)
                    self._preempt_exit(
                        guard, events, state_ckptr,
                        epochs_completed=consumed_through,
                    )
                if stop_early:
                    break
            # Pipelined tail: the loop exits on the epoch budget (or an
            # early stop) with the last dispatched span's results still
            # on device — bookkeep them now.
            if pending is not None:
                _sp, pending = pending, None
                stop_early = _consume_span(_sp) or stop_early
                if guard.requested:
                    self._preempt_exit(
                        guard, events, state_ckptr,
                        epochs_completed=consumed_through,
                    )
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
                try:
                    flight.close()
                finally:
                    profiler.close()
            finally:
                try:
                    state_ckptr.wait()
                finally:
                    try:
                        if prefetch_pool is not None:
                            prefetch_pool.shutdown(wait=True)
                    finally:
                        # The SIGTERM contract ends here either way:
                        # restore the previous handler so post-training
                        # code (and whatever embeds us) keeps its own
                        # semantics.
                        guard.uninstall()
                        # Terminal heartbeat: "done" stops the monitor
                        # ageing this rank; "preempted" and "failed"
                        # name ends an exit code alone cannot (the rank
                        # may be killed by fail-fast before it can exit).
                        if heartbeat is not None:
                            heartbeat.beat(
                                phase="done" if completed else (
                                    "preempted" if preempted else "failed"
                                ),
                                force=True,
                            )
                        if preempted:
                            events.emit(
                                "trainer", "fit_preempted",
                                epochs_run=len(history),
                            )
                        elif not completed:
                            events.emit(
                                "trainer", "fit_failed",
                                health=health.summary()["events"],
                            )
                        if not completed:
                            # The crashing/preempted epoch is exactly
                            # the window the operator opens the trace to
                            # inspect: record any span still in flight
                            # (pipelined, the un-bookkept successor's
                            # spans live in `pending`).
                            in_flight = [bookkeep, dispatch_span,
                                         epoch_span]
                            if pending is not None:
                                in_flight += [pending.dispatch_span,
                                              pending.epoch_span]
                            for _sp in in_flight:
                                if _sp is not None:
                                    _sp.end(error=not preempted)
                        # Fit span closes HERE, success or failure: a
                        # post-training tail error (artifact upload,
                        # tracker teardown) must not orphan the whole
                        # rank's span tree from its recorded root.
                        fit_span.end(
                            completed=completed,
                            preempted=preempted,
                            epochs_run=len(history),
                            val_loss=(
                                history[-1]["val_loss"]
                                if history else None
                            ),
                        )
                        # Hot loop over (success, crash, or preempt):
                        # drain buffered telemetry and drop both sinks
                        # to write-through, so every record emitted so
                        # far is durable and post-run emitters through
                        # the installed process defaults get
                        # read-after-emit visibility back.
                        events.set_write_through()
                        tracer.set_write_through()

        # Rank-0 post-train artifact upload, mirroring
        # jobs/train_lightning_ddp.py:146-164 (best, else last.ckpt fallback).
        with timed(
            "checkpoint", "trainer.upload", parent_id=fit_span.span_id
        ):
            best_path = ckptr.best_model_path
            if self.coordinator:
                if not os.path.exists(best_path):
                    best_path = ckptr.last_path
                if os.path.exists(best_path):
                    self.tracker.log_artifact(
                        best_path, artifact_path=self.cfg.tracking.artifact_path
                    )
                    # log_model parity (MLFlowLogger(log_model=True) logs the
                    # model object too, reference jobs/train_lightning_ddp.py:95):
                    # the checkpoint plus loader metadata under artifact path
                    # "model", so the registry carries a self-describing model
                    # artifact, not only the raw .ckpt.
                    import json as _json
                    import tempfile as _tempfile

                    with _tempfile.TemporaryDirectory() as td:
                        mlmodel = os.path.join(td, "MLmodel.json")
                        with open(mlmodel, "w") as f:
                            _json.dump(
                                {
                                    "flavor": "dct_tpu",
                                    "checkpoint": os.path.basename(best_path),
                                    "serving": "dct_tpu.serving.runtime",
                                    **meta,
                                },
                                f,
                                indent=2,
                            )
                        self.tracker.log_artifact(mlmodel, artifact_path="model")
                        self.tracker.log_artifact(best_path, artifact_path="model")

        # Run-end goodput accounting: logged to the tracker NEXT TO
        # val_loss (a goodput regression becomes queryable exactly like
        # an accuracy regression), emitted as a structured event, and
        # dumped in Prometheus text exposition for scrape-less rigs.
        goodput_summary = ledger.summary()
        self.tracker.log_metrics(ledger.tracker_metrics(), step=global_step)
        events.emit("trainer", "goodput_summary", **goodput_summary)
        # Compile/restart accounting (ROADMAP item 5's baseline): the
        # ledger's compile windows become compile.window events keyed by
        # the (family, config-hash, mesh) identity an AOT compilation
        # cache would use, and dct_compile_* series in the prom dump —
        # re-compiles of the SAME identity across restarts/workers are
        # the debt a persistent cache would erase.
        import dataclasses as _dataclasses

        from dct_tpu.observability.goodput import (
            compile_report,
            config_hash,
            mesh_descriptor,
        )

        compile_windows = compile_report(
            ledger.compile_windows,
            family=cfg.model.name,
            config_hash=config_hash(_dataclasses.asdict(cfg.model)),
            mesh=mesh_descriptor(self.mesh),
            # cache="hit" windows were deserialized executables, not XLA
            # compiles — the label a warm-relaunch e2e asserts on.
            cache_states=aot_store.states,
            # Roofline provenance: analytic FLOPs / bytes / peak HBM
            # captured at compile time ride the window record.
            costs=aot_store.costs,
        )
        if self.coordinator:
            for w in compile_windows:
                events.emit("compile", "compile.window", **w)
        # Roofline join (observability.roofline): the cost-model numbers
        # against the ledger's measured steady-state dispatch windows —
        # live per-program MFU, arithmetic intensity, and the compute-
        # vs-memory-bound placement, as roofline.report events and the
        # dct_program_* gauges in the metrics dump below.
        from dct_tpu.observability.roofline import program_report

        roofline_rep = program_report(
            aot_store.costs,
            ledger.dispatch_stats,
            n_chips=self.mesh.size,
            family=cfg.model.name,
            config_hash=config_hash(_dataclasses.asdict(cfg.model)),
            mesh=mesh_descriptor(self.mesh),
        )
        if self.coordinator:
            for r in roofline_rep:
                events.emit("roofline", "roofline.report", **r)
        # Retire the live per-epoch snapshot BEFORE the final dump
        # writes the terminal one under the same proc name — close()
        # removes the live file, the dump re-creates it as final.
        if live_metrics is not None:
            live_metrics.close()
        # An explicit DCT_METRICS_PROM must work even with the event log
        # disabled (textfile-collector-only rigs clear DCT_EVENTS_DIR).
        if self.coordinator and cfg.obs.enabled and (
            cfg.obs.metrics_path or cfg.obs.events_dir
        ):
            from dct_tpu.observability.dump import write_train_metrics_prom

            final_vl = (
                history[-1]["val_loss"] if history else float("nan")
            )
            write_train_metrics_prom(
                cfg.obs.metrics_path
                or os.path.join(cfg.obs.events_dir, "train_metrics.prom"),
                goodput_summary,
                run_id=events.run_id,
                samples_per_sec=timer.samples_per_sec,
                val_loss=final_vl,
                health=health.summary(),
                resilience={
                    "faults_injected": plan.fired_count,
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

        if self.coordinator:
            shadow = span_shadow_warning(history, span_end_vl_min, chunk)
            if shadow:
                print(shadow, file=sys.stderr, flush=True)
        final = history[-1] if history else {"val_loss": float("nan"), "val_acc": float("nan")}
        health_summary = health.summary()
        events.emit(
            "trainer", "fit_end",
            val_loss=final["val_loss"], val_acc=final["val_acc"],
            epochs_run=len(history),
            goodput_fraction=goodput_summary["goodput_fraction"],
            health=health_summary["events"],
        )
        steady = timer.history[1:] if len(timer.history) > 1 else timer.history
        return TrainResult(
            val_loss=final["val_loss"],
            val_acc=final["val_acc"],
            best_model_path=best_path,
            last_model_path=ckptr.last_path,
            history=history,
            samples_per_sec=timer.samples_per_sec,
            steady_samples_per_sec_per_chip=(
                sum(s.samples_per_sec_per_chip for s in steady) / len(steady)
                if steady else 0.0
            ),
            run_id=run_id,
            state=state,
            goodput=goodput_summary,
            run_correlation_id=events.run_id,
            health=health_summary,
            placement={
                "state": _device_ids(state), "batch": batch_devices,
            },
        )

    # ------------------------------------------------------------------
    def _end_tracking_quietly(self, status: str) -> None:
        try:
            self.tracker.end_run(status=status)
        except Exception:  # noqa: BLE001 — bookkeeping must not mask the exit
            pass

    # ------------------------------------------------------------------
    @staticmethod
    def _preempt_exit(
        guard,
        events,
        ckptr,
        *,
        epochs_completed: int,
        state=None,
        target_epochs: int | None = None,
        opt_identity: dict | None = None,
    ):
        """Honor a SIGTERM: make the resume checkpoint durable, put the
        preemption on the record, raise :class:`PreemptedError` (the
        entry point maps it to ``EXIT_PREEMPTED``).

        ``state=None`` means the span boundary just submitted the right
        snapshot asynchronously — joining it is the synchronous save;
        the eager path passes the live state for an explicit save.
        """
        if state is not None:
            ckptr.save(
                state,
                meta={
                    "epochs_completed": int(epochs_completed),
                    "target_epochs": int(target_epochs),
                    "optimizer": opt_identity,
                },
            )
        else:
            ckptr.wait()
        events.emit(
            "trainer", "preempt.signal_received",
            signal_time=guard.signal_time,
        )
        events.emit(
            "trainer", "preempt.checkpoint_saved",
            epochs_completed=int(epochs_completed), dir=ckptr.dirpath,
        )
        raise PreemptedError(
            f"SIGTERM honored: resume checkpoint durable at "
            f"epochs_completed={int(epochs_completed)}"
        )

    # ------------------------------------------------------------------
    @staticmethod
    def _stack_epoch(loader, epoch: int):
        """One epoch as [S, B_local, ...] host arrays for the scan path."""
        return loader.epoch_stacked(epoch)

    # ------------------------------------------------------------------
    def _evaluate(self, state, eval_step, val_loader):
        """-> (val_loss, val_acc, (tp, fp, fn)) from the global sums."""
        sums = [jnp.zeros(()) for _ in range(6)]
        for batch in val_loader.epoch(0):
            x, y, w = make_global_batch(self.mesh, batch.x, batch.y, batch.weight)
            for i, v in enumerate(eval_step(state, x, y, w)):
                sums[i] = sums[i] + v
        ls, accs, c, tp, fp, fn = (float(v) for v in jax.device_get(sums))
        if c == 0:
            return float("nan"), float("nan"), (0.0, 0.0, 0.0)
        return ls / c, accs / c, (tp, fp, fn)

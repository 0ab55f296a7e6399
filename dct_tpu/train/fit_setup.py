"""Everything ``Trainer.fit`` decides before its first epoch.

data -> windows / split -> loaders -> model -> state -> declared layout ->
resume / continuation -> checkpointers -> AOT identity + store -> programs
-> checkpoint meta -> staged validation, in that order, as ``prepare_fit``;
the result is one ``FitSetup`` the epoch loop and the close read. The
decisions that used to be testable only through a whole ``fit`` are pure
functions here: ``resolve_continuation``, ``cosine_decay_horizon``,
``aot_train_identity``. This module knows ``steps.py``, the checkpointers
and the telemetry object; it does not import ``trainer.py``.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import multihost_utils

from dct_tpu import compilecache
from dct_tpu.checkpoint.manager import (
    BestLastCheckpointer,
    TrainStateCheckpointer,
    needs_cross_process_gather,
)
from dct_tpu.data.dataset import load_processed_dataset
from dct_tpu.data.pipeline import BatchLoader, contiguous_split, train_val_split
from dct_tpu.data.windows import make_windows
from dct_tpu.etl.preprocess import read_etl_state
from dct_tpu.models.registry import get_model, is_causal_model, is_sequence_model
from dct_tpu.observability import lineage as _lineage
from dct_tpu.observability.goodput import config_hash, mesh_descriptor
from dct_tpu.ops.attention import make_attention_fn
from dct_tpu.parallel.mesh import make_global_epoch, process_data_block
from dct_tpu.parallel.sharding_rules import (
    dtype_rules_digest,
    rules_digest,
    shard_state_with_rules,
    state_shardings,
)
from dct_tpu.train import mpmd_trainer
from dct_tpu.train.state import create_train_state, make_lr_schedule
from dct_tpu.train.steps import (
    make_epoch_train_eval_step,
    make_eval_step,
    make_train_step,
)
from dct_tpu.utils.profiling import transformer_train_flops

#: ``TrainConfig`` fields that steer the loop and never reach the compiled
#: program: a relaunch flips ``resume`` and must still hit the AOT store.
LOOP_CONTROL_FIELDS = (
    "resume", "epochs", "log_every_n_steps",
    "early_stop_patience", "early_stop_min_delta",
    "prefetch_spans",
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


def resolve_continuation(
    saved_meta: dict | None,
    epochs: int,
    *,
    restored_step: int | None = None,
    steps_per_epoch: int = 1,
) -> tuple[int, int]:
    """``(start_epoch, target_epochs)`` from the resume tier's meta.

    Continuous-training semantics (the reference re-trains from scratch
    daily — its fit() never gets a ckpt_path, reference
    jobs/train_lightning_ddp.py:143):

    - no checkpoint (``saved_meta is None``) -> train epochs [0, epochs)
    - interrupted prior run  -> finish to its saved target
    - COMPLETED prior run    -> continue for ``epochs`` MORE epochs on the
      (possibly refreshed) data, keeping optimizer state — each DAG run
      extends the same optimization trajectory
    - pre-meta checkpoint (no ``epochs_completed``) -> the start is
      derived from the restored step counter, ``restored_step``.
    """
    if saved_meta is None:
        return 0, epochs
    if "epochs_completed" in saved_meta:
        start_epoch = int(saved_meta["epochs_completed"])
    else:
        start_epoch = int(restored_step) // max(steps_per_epoch, 1)
    saved_target = int(saved_meta.get("target_epochs", epochs))
    if start_epoch >= saved_target:
        return start_epoch, start_epoch + epochs
    return start_epoch, saved_target


def cosine_decay_horizon(
    train_cfg, *, updates_per_epoch: int, prior_epochs: int
) -> int:
    """The decay horizon baked into the LR schedule. A configured
    ``decay_steps`` is taken as given; cosine with ``decay_steps <= 0`` is
    auto: decay over the FULL trajectory. The optimizer's restored update
    count already includes prior runs (``prior_epochs`` of them), so a
    continuation sized only to THIS run's budget would start at (or
    clamp to) the floor LR and train nothing."""
    if train_cfg.lr_schedule == "cosine" and train_cfg.decay_steps <= 0:
        return max(
            1,
            (prior_epochs + train_cfg.epochs) * updates_per_epoch
            - train_cfg.warmup_steps,
        )
    return train_cfg.decay_steps


def aot_train_identity(
    train_cfg, *, decay_resolved: int, shard_rules: str, dtype_rules: str,
    donate: bool,
) -> dict:
    """The train side of the AOT store's program identity: every
    ``TrainConfig`` field whose constants are baked into the executable
    (optimizer chain, lr/schedule with its RESOLVED decay horizon,
    precision, sharding, accumulation), the digests of the partition-rule
    and dtype-rule tables, and the resolved donation mode — serial mode
    donates the input state, and a donating executable loaded into the
    pipelined loop would free a buffer the checkpoint tier still reads.
    ``LOOP_CONTROL_FIELDS`` are deliberately OUT."""
    identity = {
        k: v
        for k, v in dataclasses.asdict(train_cfg).items()
        if k not in LOOP_CONTROL_FIELDS
    }
    identity["decay_resolved"] = int(decay_resolved)
    # The partition-rule table is part of the program: a layout change
    # (DCT_SHARD_RULES, a family-table edit) compiles a DIFFERENT
    # executable — it must miss; the same layout must warm-relaunch,
    # sharded exactly like DP.
    identity["shard_rules"] = shard_rules
    # Same contract for the PRECISION table: the dtype rules pick which
    # param leaves run the step in bf16 (cast inside the traced loss
    # body, train/steps.py), so the compiled program differs whenever
    # they do — a precision change must be a loud cache miss, never a
    # stale full-width (or half-width) executable. "off" when unset keys
    # identically to every pre-rules artifact.
    identity["dtype_rules"] = dtype_rules
    identity["donate"] = bool(donate)
    return identity


@dataclass
class FitSetup:
    """What ``prepare_fit`` resolved, read by the epoch loop and the close."""

    global_batch: int
    train_loader: BatchLoader
    val_loader: BatchLoader
    n_val: int
    state: object
    declared_shardings: object
    start_epoch: int
    target_epochs: int
    opt_identity: dict
    state_ckptr: TrainStateCheckpointer
    ckptr: BestLastCheckpointer
    params_cross_process: bool
    use_scan: bool
    accum: int
    pipelined: bool
    aot_store: object
    # Scan path: the fused epoch program and the staged validation
    # stacks. Eager path: the per-batch steps.
    epoch_fused: object = None
    val_global: tuple = ()
    train_step: object = None
    eval_step: object = None
    meta: dict = dataclasses.field(default_factory=dict)
    run_id: str | None = None
    flops_per_sample: float | None = None


def read_data_provenance(cfg, lin) -> dict:
    """Data-generation provenance for the always-on loop's freshness
    accounting (dct_tpu.continuous): the incremental ETL stamps a
    generation + arrival_ts into etl_state.json, read here BEFORE the
    parquet load — so a checkpoint's stamped generation never claims rows
    a concurrent ETL published after our snapshot. Also declares the
    snapshot as this run's lineage input. Only called when this fit
    loads the data itself: a caller-provided array set has no provable
    tie to the processed dir."""
    etl_state = read_etl_state(cfg.data.processed_dir)
    if not etl_state.get("generation"):
        return {}
    provenance = {
        "data_generation": int(etl_state["generation"]),
        "data_arrival_ts": float(etl_state.get("arrival_ts") or 0.0),
    }
    # Stream-fed generations carry the committed offset vector: the
    # checkpoint names the exact log positions its rows came from, the
    # same way ``data_generation`` names the parquet snapshot.
    if etl_state.get("stream_offsets") is not None:
        provenance["stream_offsets"] = [
            int(o) for o in etl_state["stream_offsets"]
        ]
    # The ETL stamped its snapshot's lineage node id into the state file
    # — adopt it (no parquet re-hash) and put the provenance dict on the
    # graph record. A pre-lineage state file (no stamp) re-addresses the
    # snapshot dir by content, landing on the same node id the ETL would
    # have minted.
    snap_nid = etl_state.get("lineage_node")
    if lin.enabled and not snap_nid:
        snap_nid = lin.node(
            "dataset_snapshot",
            path=os.path.join(cfg.data.processed_dir, "data.parquet"),
            attrs={"generation": int(etl_state["generation"])},
        )
    elif lin.enabled and snap_nid:
        lin.node(
            "dataset_snapshot",
            sha256=snap_nid.split(":", 1)[-1],
            attrs=provenance,
        )
    _lineage.set_run_inputs([snap_nid])
    return provenance


def build_loaders(cfg, mesh, data):
    """-> ``(data, sequence, train_loader, val_loader, n_val, global_batch)``."""
    # Sequence models train on sliding windows of the same stream; the
    # row-wise contract (and everything downstream: split, loader,
    # checkpointing) is unchanged because WindowArrays mirrors
    # WeatherArrays.
    sequence = is_sequence_model(cfg.model.name)
    if sequence:
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
    global_batch = cfg.train.batch_size * mesh.shape["data"]
    # Loader sharding follows the MESH, not the raw process count: DP
    # processes own distinct blocks of each global batch; processes that
    # only split the model/seq axes share their data rows and must feed
    # identical blocks (process_data_block encodes both cases).
    n_blocks, block_id = process_data_block(mesh)
    train_loader = BatchLoader(
        data, train_idx, global_batch=global_batch, shuffle=True,
        seed=cfg.train.seed, num_processes=n_blocks, process_id=block_id,
    )
    val_loader = BatchLoader(
        data, val_idx, global_batch=global_batch, shuffle=False,
        seed=cfg.train.seed, num_processes=n_blocks, process_id=block_id,
    )
    return data, sequence, train_loader, val_loader, len(val_idx), global_batch


def build_state(cfg, mesh, data, sequence, state_ckptr, updates_per_epoch):
    """Model, LR schedule, train state placed by the partition rules, and
    the declared layout -> ``(state, declared_shardings, resolved_decay)``."""
    compute_dtype = jnp.bfloat16 if cfg.train.bf16_compute else jnp.float32
    if sequence:
        model = get_model(
            cfg.model,
            input_dim=data.input_dim,
            compute_dtype=compute_dtype,
            attn_fn=make_attention_fn(mesh),
            mesh=mesh,
        )
        example_shape = (1, cfg.model.seq_len, data.input_dim)
    else:
        model = get_model(
            cfg.model, input_dim=data.input_dim, compute_dtype=compute_dtype
        )
        example_shape = None
    # The decay horizon actually baked into the schedule (auto mode
    # resolves it from the restored trajectory): part of the AOT store's
    # program identity — the schedule's constants live inside the
    # compiled executable.
    lr_schedule = None
    resolved_decay = cfg.train.decay_steps
    if cfg.train.lr_schedule != "constant" or cfg.train.warmup_steps > 0:
        prior_epochs = 0
        auto = cfg.train.lr_schedule == "cosine" and cfg.train.decay_steps <= 0
        if auto and cfg.train.resume and state_ckptr.exists():
            prior_epochs = int(
                state_ckptr.load_meta().get("epochs_completed", 0)
            )
        resolved_decay = cosine_decay_horizon(
            cfg.train, updates_per_epoch=updates_per_epoch,
            prior_epochs=prior_epochs,
        )
        lr_schedule = make_lr_schedule(
            cfg.train.lr,
            schedule=cfg.train.lr_schedule,
            warmup_steps=cfg.train.warmup_steps,
            decay_steps=resolved_decay,
            end_lr_fraction=cfg.train.end_lr_fraction,
        )
    state = create_train_state(
        model, input_dim=data.input_dim, lr=cfg.train.lr,
        seed=cfg.train.seed, example_shape=example_shape,
        lr_schedule=lr_schedule, weight_decay=cfg.train.weight_decay,
        grad_clip_norm=cfg.train.grad_clip_norm,
        optimizer=cfg.train.optimizer, momentum=cfg.train.momentum,
    )
    # Declarative partition rules: the per-family rule table (env-
    # overridable via DCT_SHARD_RULES) gives tensor-parallel placement
    # for the transformer family, full replication for the MLP (no
    # patterns match). TP/SP axes may span processes: the checkpoint
    # tier assembles such params with a cross-process allgather
    # (checkpoint.manager.to_host), called on EVERY rank before the
    # coordinator-gated write.
    state = shard_state_with_rules(
        state, mesh, shard_opt=cfg.train.shard_opt_state,
        shard_params=cfg.train.shard_params, family=cfg.model.name,
    )
    # The DECLARED layout. The jitted step's OUTPUT shardings can drift
    # from it — under ZeRO-1, XLA keeps the weight update (and therefore
    # the output params) sharded over ``data`` instead of all-gathering
    # — and the resume tier saves per-process local shards of whatever
    # layout the state actually has. Checkpoints must be written in the
    # declared layout, or a resumed process (whose fresh template is the
    # declared layout) cannot match the saved shards to its topology.
    # The first bookkept span's output is reconciled against this layout
    # and any drift emitted as a loud ``shard.layout_mismatch`` event
    # (EpochLoop.bookkeep).
    declared_shardings = state_shardings(
        state, mesh, shard_opt=cfg.train.shard_opt_state,
        shard_params=cfg.train.shard_params, family=cfg.model.name,
    )
    return state, declared_shardings, resolved_decay


def restore_or_start(cfg, mesh, state, state_ckptr, train_loader):
    """Resume from the per-process resume tier when asked and present
    -> ``(state, start_epoch, target_epochs, opt_identity)``."""
    opt_identity = optimizer_identity(cfg.train)
    saved = None
    if cfg.train.resume and not state_ckptr.exists():
        # Cross-topology pivot: an MPMD session's per-stage checkpoints
        # (train_state_mpmd/stage<k>/, ISSUE 13) re-map into the stacked
        # SPMD layout — bitwise, pure data movement — and this run
        # resumes the same trajectory. An untileable stage map (manifest
        # stages != this model's n_stages) refuses loudly inside the
        # adoption.
        manifest = mpmd_trainer.read_manifest(cfg.data.models_dir)
        # Family-gated: a manifest left by a PP session must not crash
        # an unrelated family's resume in the same models_dir (that run
        # trains fresh, exactly as before the hook).
        if manifest and manifest.get("family") == cfg.model.name:
            mpmd_trainer.adopt_mpmd_checkpoint(cfg.data.models_dir, state)
    if cfg.train.resume and state_ckptr.exists():
        saved = state_ckptr.load_meta()
        saved_opt = saved.get("optimizer")
        if saved_opt is not None and saved_opt != opt_identity:
            # Named refusal BEFORE restore: opt_state trees of different
            # optimizer configs can be structurally isomorphic (same leaf
            # count/shapes), so the manager's count/shape check would
            # let a cross-restore through and the run would train from
            # mismatched moments.
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
            state_ckptr.restore(state), mesh,
            shard_opt=cfg.train.shard_opt_state,
            shard_params=cfg.train.shard_params,
            family=cfg.model.name,
        )
    start_epoch, target_epochs = resolve_continuation(
        saved, cfg.train.epochs,
        # Only a pre-meta checkpoint needs the step counter read back.
        restored_step=(
            int(jax.device_get(state.step))
            if saved is not None and "epochs_completed" not in saved
            else None
        ),
        steps_per_epoch=train_loader.num_batches,
    )
    if cfg.train.resume and jax.process_count() > 1:
        # All ranks must agree on start_epoch or the SPMD step counts
        # diverge and collectives deadlock. Fail loudly instead.
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
    if start_epoch >= target_epochs:
        # Only reachable with epochs <= 0: the continuation semantics
        # always extend the target past a completed run. Fail LOUDLY —
        # returning nan metrics here would let the DAG's verify_model
        # gate "pass" on a stale checkpoint having trained nothing
        # (VERDICT r1 weak-point 6).
        raise RuntimeError(
            f"Nothing to train: start_epoch={start_epoch} >= "
            f"target_epochs={target_epochs} (DCT_EPOCHS="
            f"{cfg.train.epochs}). Set a positive epoch budget."
        )
    return state, start_epoch, target_epochs, opt_identity


def open_aot_store(cfg, mesh, *, input_dim, resolved_decay, pipelined, emit):
    """AOT executable store (compilecache): the fused epoch programs
    load-or-miss against <models_dir>/aot (override:
    DCT_COMPILE_CACHE_AOT_DIR) — a resume snapshot's layout carries its
    pre-compiled steps. The identity is the compile-accounting key
    (family, model-config hash, resolved mesh) PLUS ``aot_train_identity``
    and the input width. Disabled = a transparent pass-through."""
    return compilecache.store_from_env(
        os.environ.get("DCT_COMPILE_CACHE_AOT_DIR")
        or os.path.join(cfg.data.models_dir, "aot"),
        family=cfg.model.name,
        config_hash=config_hash(dataclasses.asdict(cfg.model)),
        mesh=mesh_descriptor(mesh),
        extra={
            **aot_train_identity(
                cfg.train,
                decay_resolved=resolved_decay,
                shard_rules=rules_digest(cfg.model.name),
                dtype_rules=dtype_rules_digest(),
                donate=not pipelined,
            ),
            "input_dim": input_dim,
        },
        emit=emit,
    )


def prepare_fit(cfg, mesh, data, tel, *, tracker) -> FitSetup:
    """Run the setup in order; ``tel`` is the run's ``RunTelemetry``."""
    # Lineage ledger (installed as the process default alongside the
    # event log): checkpoints this run publishes get ``consumed`` edges
    # to the dataset snapshot declared by ``read_data_provenance``.
    lin = _lineage.ledger_from_config(cfg.obs, rank=jax.process_index())
    _lineage.set_run_inputs([])
    data_provenance: dict = {}
    if data is None:
        data_provenance = read_data_provenance(cfg, lin)
        data = load_processed_dataset(
            cfg.data.processed_dir,
            feature_suffix=cfg.data.feature_suffix,
            label_column=cfg.data.label_column,
        )
    data, sequence, train_loader, val_loader, n_val, global_batch = (
        build_loaders(cfg, mesh, data)
    )
    # Per-process state dir, constructed before the LR schedule: a
    # resumed run must size its cosine horizon from the restored
    # trajectory, not this run's budget alone.
    state_ckptr = TrainStateCheckpointer(
        os.path.join(
            cfg.data.models_dir, "train_state", f"p{jax.process_index()}"
        )
    )
    accum = max(1, cfg.train.grad_accum_steps)
    updates_per_epoch = train_loader.num_batches // accum
    if cfg.train.grad_accum_steps > 1 and updates_per_epoch == 0:
        raise ValueError(
            f"grad_accum_steps={cfg.train.grad_accum_steps} exceeds the "
            f"{train_loader.num_batches} batches per epoch — every "
            "epoch would run ZERO optimizer updates"
        )
    state, declared_shardings, resolved_decay = build_state(
        cfg, mesh, data, sequence, state_ckptr, updates_per_epoch
    )
    state, start_epoch, target_epochs, opt_identity = restore_or_start(
        cfg, mesh, state, state_ckptr, train_loader
    )
    use_scan = cfg.train.use_scan
    # Span pipelining (the dispatch-gap work): with prefetch_spans >= 1,
    # epoch e+1 is DISPATCHED before epoch e's bookkeeping runs, so the
    # health pass, tracker/event logging, and both checkpoint tiers'
    # writes all overlap device compute instead of serializing the hot
    # loop (EpochLoop.scan_epoch has the order). The serial mode stays:
    # the fault-injection drills assert its exact crash / checkpoint
    # ordering, so an armed fault plan selects it.
    pipelined = (
        use_scan
        and cfg.train.prefetch_spans >= 1
        and not tel.plan.enabled
    )
    setup = FitSetup(
        global_batch=global_batch,
        train_loader=train_loader,
        val_loader=val_loader,
        n_val=n_val,
        state=state,
        declared_shardings=declared_shardings,
        start_epoch=start_epoch,
        target_epochs=target_epochs,
        opt_identity=opt_identity,
        state_ckptr=state_ckptr,
        ckptr=BestLastCheckpointer(cfg.data.models_dir),
        params_cross_process=needs_cross_process_gather(state.params),
        use_scan=use_scan,
        accum=accum,
        pipelined=pipelined,
        aot_store=open_aot_store(
            cfg, mesh, input_dim=data.input_dim,
            resolved_decay=resolved_decay, pipelined=pipelined,
            emit=tel.events.emit,
        ),
    )
    if use_scan:
        # Span stacks are single-use in the trainer, so donating them
        # frees a full span of HBM before the step's activations peak.
        # The STATE is donated only in serial mode: pipelined
        # bookkeeping still reads the previous span's output state
        # (checkpoint gather + resume snapshot) while the next span
        # computes from it, so that buffer must survive the dispatch —
        # the second resident state is the documented price of the
        # overlap.
        setup.epoch_fused = setup.aot_store.wrap(make_epoch_train_eval_step(
            donate=not pipelined,
            accum_steps=accum, donate_stacks=True,
            with_grad_norms=True,
        ))
    else:
        # The eager path stays for the step-granular fault and health
        # drills and as the tests' reference for the scan path.
        setup.train_step = make_train_step(
            accum_steps=accum, with_grad_norm=True
        )
        setup.eval_step = make_eval_step()

    # Self-describing checkpoint meta: the FULL model config (whichever
    # family), plus the data-derived facts — enough to rebuild the model
    # from the checkpoint alone.
    meta = {
        **dataclasses.asdict(cfg.model),
        "model": cfg.model.name,
        "input_dim": data.input_dim,
        "feature_names": list(data.feature_names),
        # Which ETL generation this trajectory extension trained on
        # (empty pre-incremental-ETL): the loop's evaluator reads it
        # off the packaged meta to attribute promotion freshness.
        **data_provenance,
    }
    meta.pop("name", None)
    setup.meta = meta
    setup.run_id = tracker.start_run(params={
        **meta, "lr": cfg.train.lr,
        "batch_size": cfg.train.batch_size,
        "epochs": cfg.train.epochs,
        "seed": cfg.train.seed,
        # The split this run was validated on: the deploy side's eval
        # harness must rebuild EXACTLY it (prepare_package stamps both
        # into the package manifest).
        "val_fraction": cfg.data.val_fraction,
        "global_batch": global_batch,
    })
    if cfg.model.name in ("weather_transformer", "weather_transformer_pp"):
        setup.flops_per_sample = transformer_train_flops(
            d_model=cfg.model.d_model, d_ff=cfg.model.d_ff,
            seq_len=cfg.model.seq_len, n_heads=cfg.model.n_heads,
            n_layers=cfg.model.n_layers, input_dim=data.input_dim,
            batch=1, num_classes=cfg.model.num_classes,
        )
    # Pre-staged validation arrays (order is fixed): stacked AND
    # transferred to device once, reused every epoch.
    if use_scan:
        setup.val_global = make_global_epoch(
            mesh, *val_loader.epoch_stacked(0)
        )
    return setup

"""Configuration for the TPU-native continuous-training framework.

The reference has no config system at all: hyperparameters are hardcoded
(lr 0.01 at jobs/train_lightning_ddp.py:88, batch 4 at :122, epochs 10 at
:132, split 0.8 at :117, seed 42 at :14, hidden 64 / dropout 0.2 at :57-61)
and the only runtime knobs are env vars interpolated by docker-compose
(MASTER_ADDR/MASTER_PORT/NODE_RANK/WORLD_SIZE at docker-compose.yml:121-124,
MLFLOW_TRACKING_URI at jobs/train_lightning_ddp.py:94).

Here every hyperparameter is a dataclass field whose default equals the
reference value (so a bare ``RunConfig()`` reproduces the parity config) and
every field can be overridden from the environment with a ``DCT_``-prefixed
variable (``DCT_EPOCHS=3``), while the reference's env-var names are honored
unprefixed at the DAG boundary (``WORLD_SIZE``, ``MASTER_ADDR``, ...).
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any


def _env(name: str, default: Any, cast: type) -> Any:
    raw = os.environ.get(name)
    if raw is None:
        return default
    if cast is bool:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    return cast(raw)


@dataclass
class DataConfig:
    """Filesystem + split contract.

    Mirrors the reference's data contract: Spark writes a parquet *directory*
    ``<processed_dir>/data.parquet`` (jobs/preprocess.py:44-51); the trainer
    reads it, selects ``*_norm`` feature columns and the ``label_encoded``
    target (jobs/train_lightning_ddp.py:37-46), and splits 80/20
    (jobs/train_lightning_ddp.py:117-119).
    """

    processed_dir: str = "data/processed"
    raw_csv: str = "data/raw/weather.csv"
    models_dir: str = "data/models"
    val_fraction: float = 0.2
    feature_suffix: str = "_norm"
    label_column: str = "label_encoded"

    @classmethod
    def from_env(cls) -> "DataConfig":
        c = cls()
        c.processed_dir = _env("DCT_PROCESSED_DIR", c.processed_dir, str)
        c.raw_csv = _env("DCT_RAW_CSV", c.raw_csv, str)
        c.models_dir = _env("DCT_MODELS_DIR", c.models_dir, str)
        c.val_fraction = _env("DCT_VAL_FRACTION", c.val_fraction, float)
        return c


@dataclass
class ModelConfig:
    """Flagship model: the rain classifier MLP.

    Reference architecture: Linear(input_dim, 64) -> ReLU -> Dropout(0.2)
    -> Linear(64, 2)  (jobs/train_lightning_ddp.py:57-62).
    ``input_dim`` is inferred from data at runtime
    (jobs/train_lightning_ddp.py:125), so it is optional here.
    """

    name: str = "weather_mlp"
    input_dim: int | None = None
    hidden_dim: int = 64
    num_classes: int = 2
    dropout: float = 0.2
    # Transformer-family fields (unused by the MLP): window length consumed
    # from the weather stream, encoder width/depth, attention heads.
    seq_len: int = 32
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    # MoE-family fields (weather_moe): expert count, switch-routing
    # capacity factor, load-balance loss weight, dispatch engine
    # ('einsum' | 'sorted' | 'auto' — models/moe.py module docstring).
    n_experts: int = 4
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    moe_dispatch: str = "auto"
    # 'auto' dispatch crossover: elements of the one-hot [kN, E, C]
    # tensor past which the sorted engine is picked. A guess: no cell
    # has timed either engine on the chip (ROADMAP D3, D6).
    moe_auto_threshold: int = 1 << 21
    # 1 = switch (top-1); 2+ = GShard-style top-k with normalized gates.
    router_top_k: int = 1
    # Pipeline-parallel family (weather_transformer_pp): stage count over
    # the mesh's ``pipe`` axis; microbatches default to the stage count.
    n_stages: int = 2
    n_microbatches: int | None = None
    # Causal family (weather_transformer_causal): forecast horizon. 1 =
    # next-step (reference-style single label); H > 1 = DIRECT
    # multi-horizon — every position predicts steps t+1..t+H at once
    # (no autoregressive feedback), labels [B, S, H].
    horizon: int = 1
    # Activation rematerialization for the transformer families: store
    # only block boundaries forward, recompute internals backward — the
    # HBM-for-FLOPs trade (jax.checkpoint) that unlocks long sequences.
    remat: bool = False
    # Causal family: sliding-window local attention — position t attends
    # to the last `attn_window` positions only (0 = full causal). Works
    # on every attention path incl. both SP engines. It binds every
    # attention layer, unless ``layer_types`` names "sliding_attention"
    # layers: then it binds those, and the "full_attention" layers are
    # full causal.
    attn_window: int = 0
    # Transformer families: grouped-query attention — K/V carry this many
    # heads (0 = classic MHA, = n_heads), each serving
    # n_heads/n_kv_heads query heads. The KV-bandwidth lever: smaller
    # projections, KV HBM reads divided by the group size in the flash
    # kernel, smaller KV payloads on the SP engines' collectives.
    n_kv_heads: int = 0
    # Causal families: a head's width where the model states one that is
    # not d_model / n_heads (0 = that quotient); the output projection
    # then reads n_heads x head_dim.
    head_dim: int = 0
    # Transformer families: position encoding — "sincos" (additive fixed
    # table, the default) or "rope" (rotary embeddings applied to q/k
    # inside attention; relative-position structure, the standard choice
    # for long-context extrapolation). RoPE composes with both SP
    # engines (global positions, rotation happens before the seq-sharded
    # op) and with GQA.
    pos_embed: str = "sincos"
    rope_theta: float = 10000.0
    # Rotation of the "full_attention" layers stretched past the trained
    # length: "" (plain, every layer) | "yarn" (per-frequency blend of the
    # frequencies divided by ``rope_factor`` and the plain ones, between
    # the dims that turn ``rope_beta_fast`` / ``rope_beta_slow`` times in
    # ``rope_original_len`` positions; cos and sin times
    # ``rope_attention_factor``, 0 = 0.1 ln(factor) + 1;
    # models/transformer.py:yarn_tables). "sliding_attention" layers
    # always rotate plainly.
    rope_scaling: str = ""
    rope_factor: float = 1.0
    rope_original_len: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 0.0
    # Causal families: what one kind of attention layer does not share
    # with the model, ";"-separated per kind, "kind:key=value,..." with
    # keys n_heads, rope_theta and rotary_fraction (the rotated share of
    # each head: its first dims), e.g.
    # "sliding_attention:n_heads=64,rope_theta=10000;full_attention:
    # rotary_fraction=0.5"; a key left out is the model's
    # (models/transformer.py:AttnKind). ``attn_gate`` "per_head": a sigmoid
    # gate on each attention head's output, from the layer's normed input,
    # before the output projection ("" = none).
    attn_kinds: str = ""
    attn_gate: str = ""
    # The transformer block's form, each default the block the families
    # started with: normalisation ("layernorm" | "rmsnorm") and its
    # epsilon, the dense MLP ("gelu" | "swiglu": a gated MLP with three
    # matrices), biases on the projections, an RMS norm of q and k over
    # each head before the rotation.
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    mlp: str = "gelu"
    use_bias: bool = True
    qk_norm: bool = False
    # Hybrid family (weather_hybrid_moe_causal): the operator of each
    # layer, comma-separated, one entry a layer ("full_attention" |
    # "sliding_attention", the same attention under ``attn_window`` |
    # "conv", the gated short convolution of ``conv_kernel`` taps); the
    # first ``num_dense_layers`` layers carry the dense MLP of width
    # ``d_ff``, the others ``n_experts`` routed experts of width
    # ``moe_d_ff`` chosen ``router_top_k`` at a time, by
    # ``router_scoring``: "sigmoid" (sigmoid scores plus a selection
    # bias) or "softmax" (the top of the softmax probabilities,
    # renormalised over the chosen; no bias; the balance loss
    # ``router_aux_weight`` x E x sum_i f_i P_i joins the objective).
    # ``experts_held`` (0 = all) and
    # ``first_expert`` name the share of the experts this job holds of
    # an expert-parallel layer: the router stays ``n_experts`` wide.
    # The routed weights are the chosen scores over (their sum +
    # ``router_gate_eps``) times ``routed_scaling``; ``moe_shared_d_ff``
    # > 0 adds one shared gated MLP of that width every token passes;
    # ``bias_update_speed`` u > 0 runs the selection bias's balancing
    # update after every optimizer step (b += u * sign(mean load - load),
    # DeepSeek-V3's auxiliary-loss-free rule; 0 leaves the bias put).
    # "latent_attention" layers take their widths from ``kv_lora_rank``
    # (the compressed key/value latent), ``qk_nope_head_dim`` +
    # ``qk_rope_head_dim`` (a head's queries and keys: the part without
    # position and the rotated part, one rotated key shared by all heads)
    # and ``v_head_dim``.
    layer_types: str = ""
    num_dense_layers: int = 0
    conv_kernel: int = 3
    moe_d_ff: int = 0
    experts_held: int = 0
    first_expert: int = 0
    routed_scaling: float = 1.0
    router_gate_eps: float = 1e-6
    moe_shared_d_ff: int = 0
    bias_update_speed: float = 0.0
    router_scoring: str = "sigmoid"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    @classmethod
    def from_env(cls) -> "ModelConfig":
        c = cls()
        c.name = _env("DCT_MODEL", c.name, str)
        c.hidden_dim = _env("DCT_HIDDEN_DIM", c.hidden_dim, int)
        c.num_classes = _env("DCT_NUM_CLASSES", c.num_classes, int)
        c.dropout = _env("DCT_DROPOUT", c.dropout, float)
        c.seq_len = _env("DCT_SEQ_LEN", c.seq_len, int)
        c.d_model = _env("DCT_D_MODEL", c.d_model, int)
        c.n_heads = _env("DCT_N_HEADS", c.n_heads, int)
        c.n_layers = _env("DCT_N_LAYERS", c.n_layers, int)
        c.d_ff = _env("DCT_D_FF", c.d_ff, int)
        c.n_experts = _env("DCT_N_EXPERTS", c.n_experts, int)
        c.capacity_factor = _env("DCT_CAPACITY_FACTOR", c.capacity_factor, float)
        c.router_aux_weight = _env(
            "DCT_ROUTER_AUX_WEIGHT", c.router_aux_weight, float
        )
        c.moe_dispatch = _env("DCT_MOE_DISPATCH", c.moe_dispatch, str)
        c.moe_auto_threshold = _env(
            "DCT_MOE_AUTO_THRESHOLD", c.moe_auto_threshold, int
        )
        c.router_top_k = _env("DCT_ROUTER_TOP_K", c.router_top_k, int)
        c.n_stages = _env("DCT_N_STAGES", c.n_stages, int)
        mb = os.environ.get("DCT_N_MICROBATCHES")
        c.n_microbatches = int(mb) if mb else c.n_microbatches
        c.horizon = _env("DCT_HORIZON", c.horizon, int)
        c.remat = _env("DCT_REMAT", c.remat, bool)
        c.attn_window = _env("DCT_ATTN_WINDOW", c.attn_window, int)
        c.n_kv_heads = _env("DCT_N_KV_HEADS", c.n_kv_heads, int)
        c.head_dim = _env("DCT_HEAD_DIM", c.head_dim, int)
        c.pos_embed = _env(
            "DCT_POS_EMBED", c.pos_embed, str
        ).strip().lower()
        c.rope_theta = _env("DCT_ROPE_THETA", c.rope_theta, float)
        c.rope_scaling = _env(
            "DCT_ROPE_SCALING", c.rope_scaling, str
        ).strip().lower()
        c.rope_factor = _env("DCT_ROPE_FACTOR", c.rope_factor, float)
        c.rope_original_len = _env(
            "DCT_ROPE_ORIGINAL_LEN", c.rope_original_len, int
        )
        c.rope_beta_fast = _env("DCT_ROPE_BETA_FAST", c.rope_beta_fast, float)
        c.rope_beta_slow = _env("DCT_ROPE_BETA_SLOW", c.rope_beta_slow, float)
        c.rope_attention_factor = _env(
            "DCT_ROPE_ATTENTION_FACTOR", c.rope_attention_factor, float
        )
        c.attn_kinds = _env("DCT_ATTN_KINDS", c.attn_kinds, str)
        c.attn_gate = _env(
            "DCT_ATTN_GATE", c.attn_gate, str
        ).strip().lower()
        c.norm = _env("DCT_NORM", c.norm, str).strip().lower()
        c.norm_eps = _env("DCT_NORM_EPS", c.norm_eps, float)
        c.mlp = _env("DCT_MLP", c.mlp, str).strip().lower()
        c.use_bias = _env("DCT_USE_BIAS", c.use_bias, bool)
        c.qk_norm = _env("DCT_QK_NORM", c.qk_norm, bool)
        c.layer_types = _env("DCT_LAYER_TYPES", c.layer_types, str)
        c.num_dense_layers = _env(
            "DCT_NUM_DENSE_LAYERS", c.num_dense_layers, int
        )
        c.conv_kernel = _env("DCT_CONV_KERNEL", c.conv_kernel, int)
        c.moe_d_ff = _env("DCT_MOE_D_FF", c.moe_d_ff, int)
        c.experts_held = _env("DCT_EXPERTS_HELD", c.experts_held, int)
        c.first_expert = _env("DCT_FIRST_EXPERT", c.first_expert, int)
        c.routed_scaling = _env("DCT_ROUTED_SCALING", c.routed_scaling, float)
        c.router_gate_eps = _env(
            "DCT_ROUTER_GATE_EPS", c.router_gate_eps, float
        )
        c.moe_shared_d_ff = _env(
            "DCT_MOE_SHARED_D_FF", c.moe_shared_d_ff, int
        )
        c.bias_update_speed = _env(
            "DCT_BIAS_UPDATE_SPEED", c.bias_update_speed, float
        )
        c.router_scoring = _env(
            "DCT_ROUTER_SCORING", c.router_scoring, str
        ).strip().lower()
        c.kv_lora_rank = _env("DCT_KV_LORA_RANK", c.kv_lora_rank, int)
        c.qk_nope_head_dim = _env(
            "DCT_QK_NOPE_HEAD_DIM", c.qk_nope_head_dim, int
        )
        c.qk_rope_head_dim = _env(
            "DCT_QK_ROPE_HEAD_DIM", c.qk_rope_head_dim, int
        )
        c.v_head_dim = _env("DCT_V_HEAD_DIM", c.v_head_dim, int)
        return c


@dataclass
class TrainConfig:
    """Optimization loop parity config.

    Reference: Adam(lr=0.01) (jobs/train_lightning_ddp.py:88), batch_size 4
    *per rank* (:122), max_epochs 10 (:132), seed 42 (:14),
    log_every_n_steps 5 (:139).
    """

    epochs: int = 10
    # Per-device batch size; the global batch is batch_size * data-parallel
    # size, matching the reference's per-rank DataLoader(batch_size=4).
    batch_size: int = 4
    lr: float = 0.01
    # Optimizer family: adam (parity; weight_decay>0 upgrades to AdamW),
    # adamw, sgd (+momentum), adafactor (factored second moments — the
    # TPU choice when optimizer memory matters), lion. The reference is
    # locked to Adam (jobs/train_lightning_ddp.py:88).
    optimizer: str = "adam"
    momentum: float = 0.0  # sgd only
    # LR schedule: 'constant' (reference parity) or 'cosine'; optional
    # linear warmup. decay_steps 0 = auto (the run's total update count).
    lr_schedule: str = "constant"
    warmup_steps: int = 0
    decay_steps: int = 0
    end_lr_fraction: float = 0.0
    # Decoupled weight decay (AdamW); 0 keeps plain Adam (reference
    # parity — torch.optim.Adam has no decoupled decay).
    weight_decay: float = 0.0
    # Global-norm gradient clipping (Lightning gradient_clip_val
    # semantics); 0 = off, parity default.
    grad_clip_norm: float = 0.0
    seed: int = 42
    log_every_n_steps: int = 5
    # Improvement over the reference (which never resumes,
    # jobs/train_lightning_ddp.py:143): resume from latest full train state.
    resume: bool = False
    # bfloat16 compute on the MXU; params stay f32. Reference is f32 CPU.
    bf16_compute: bool = True
    # lax.scan the whole epoch as one XLA program (one dispatch/epoch).
    # Numerically identical to the eager per-step loop; disable only for
    # datasets too large to stage an epoch in HBM.
    use_scan: bool = True
    # Weight-update (ZeRO-1 style) sharding: split Adam moments' leading
    # dim over the data axis; XLA reduce-scatters grads into the shards
    # and all-gathers updates. Memory win at scale; off for parity.
    shard_opt_state: bool = False
    # FSDP/ZeRO-3: shard the PARAMS (and their Adam moments) over the
    # data axis too; each rank stores 1/N of every weight and XLA
    # all-gathers on use. Layout-only — the trajectory is unchanged.
    shard_params: bool = False
    # Gradient accumulation: microbatches summed per optimizer update
    # (effective batch = batch_size * data_parallel * this) — capability
    # the reference lacks; 1 = parity behavior.
    grad_accum_steps: int = 1
    # Early stopping on val_loss: stop after this many epochs without
    # improvement (0 = off, reference parity — Lightning users pair
    # EarlyStopping with the ModelCheckpoint the reference configures).
    early_stop_patience: int = 0
    early_stop_min_delta: float = 0.0
    # Spans kept in flight ahead of the host loop (scan path only).
    # 1 (default): the next span's host assembly + H2D staging runs on a
    # worker thread while the current span computes, AND the previous
    # span's bookkeeping (metric device_gets, health pass, tracker/event
    # logging, checkpoint writes) overlaps the current span's compute —
    # the dispatched-span results are consumed one span late, so at most
    # one span of device work is in flight past the bookkeeping. Costs
    # one extra resident copy of the train state (the fused step cannot
    # donate its input state while the checkpoint tier still reads it).
    # 0: strictly serial — assemble, dispatch, block, bookkeep, repeat
    # (restores state donation; use when HBM is the binding constraint).
    # Values above 1 clamp to 1 (deeper pipelines would let early-stop /
    # health decisions trail arbitrarily far behind the device).
    # Auto-disabled while a DCT_FAULT_SPEC is armed so fault-injection
    # drills observe the exact serial crash/checkpoint ordering.
    prefetch_spans: int = 1

    @classmethod
    def from_env(cls) -> "TrainConfig":
        c = cls()
        c.epochs = _env("DCT_EPOCHS", c.epochs, int)
        c.batch_size = _env("DCT_BATCH_SIZE", c.batch_size, int)
        c.lr = _env("DCT_LR", c.lr, float)
        c.optimizer = _env("DCT_OPTIMIZER", c.optimizer, str)
        c.momentum = _env("DCT_MOMENTUM", c.momentum, float)
        c.lr_schedule = _env("DCT_LR_SCHEDULE", c.lr_schedule, str)
        c.warmup_steps = _env("DCT_WARMUP_STEPS", c.warmup_steps, int)
        c.decay_steps = _env("DCT_DECAY_STEPS", c.decay_steps, int)
        c.end_lr_fraction = _env(
            "DCT_END_LR_FRACTION", c.end_lr_fraction, float
        )
        c.weight_decay = _env("DCT_WEIGHT_DECAY", c.weight_decay, float)
        c.grad_clip_norm = _env("DCT_GRAD_CLIP_NORM", c.grad_clip_norm, float)
        c.seed = _env("DCT_SEED", c.seed, int)
        c.log_every_n_steps = _env("DCT_LOG_EVERY_N_STEPS", c.log_every_n_steps, int)
        c.resume = _env("DCT_RESUME", c.resume, bool)
        c.bf16_compute = _env("DCT_BF16_COMPUTE", c.bf16_compute, bool)
        c.use_scan = _env("DCT_USE_SCAN", c.use_scan, bool)
        c.shard_opt_state = _env("DCT_SHARD_OPT_STATE", c.shard_opt_state, bool)
        c.shard_params = _env("DCT_SHARD_PARAMS", c.shard_params, bool)
        c.grad_accum_steps = _env("DCT_GRAD_ACCUM_STEPS", c.grad_accum_steps, int)
        c.early_stop_patience = _env(
            "DCT_EARLY_STOP_PATIENCE", c.early_stop_patience, int
        )
        c.early_stop_min_delta = _env(
            "DCT_EARLY_STOP_MIN_DELTA", c.early_stop_min_delta, float
        )
        c.prefetch_spans = _env("DCT_PREFETCH_SPANS", c.prefetch_spans, int)
        return c


@dataclass
class MeshConfig:
    """Device-mesh layout.

    The reference's only parallelism is 2-rank DDP over a Docker bridge
    (docker-compose.yml:115-151). Here parallelism is a named mesh: ``data``
    is the DDP analog; ``model`` (tensor) and ``seq`` (sequence/context) are
    first-class axes used by the transformer family and ring attention.
    Sizes of -1 mean "all remaining devices".
    """

    data: int = -1
    model: int = 1
    seq: int = 1
    pipe: int = 1

    @classmethod
    def from_env(cls) -> "MeshConfig":
        c = cls()
        c.data = _env("DCT_MESH_DATA", c.data, int)
        c.model = _env("DCT_MESH_MODEL", c.model, int)
        c.seq = _env("DCT_MESH_SEQ", c.seq, int)
        c.pipe = _env("DCT_MESH_PIPE", c.pipe, int)
        return c


@dataclass
class DistributedConfig:
    """Multi-process rendezvous, honoring the reference's env contract.

    The reference rendezvous is Lightning's LightningEnvironment reading
    MASTER_ADDR / MASTER_PORT / NODE_RANK / WORLD_SIZE
    (docker-compose.yml:121-124,140-143) to form a gloo TCP store at
    pytorch-master:29500. The TPU-native analog is
    ``jax.distributed.initialize(coordinator_address, num_processes,
    process_id)``; we derive its arguments from the same env vars so the
    orchestration layer (DAGs / compose files) carries over unchanged.
    """

    coordinator_address: str | None = None
    num_processes: int = 1
    process_id: int = 0

    @classmethod
    def from_env(cls) -> "DistributedConfig":
        c = cls()
        # Native names win; reference-compat names are the fallback.
        world = os.environ.get("DCT_NUM_PROCESSES") or os.environ.get("WORLD_SIZE")
        rank = os.environ.get("DCT_PROCESS_ID") or os.environ.get("NODE_RANK")
        # "" means unset, consistently with world/rank above — launchers
        # blank these vars to neutralize inherited overrides.
        coord = os.environ.get("DCT_COORDINATOR_ADDRESS") or None
        if coord is None:
            master_addr = os.environ.get("MASTER_ADDR")
            master_port = os.environ.get("MASTER_PORT", "29500")
            if master_addr:
                coord = f"{master_addr}:{master_port}"
        c.coordinator_address = coord
        c.num_processes = int(world) if world else 1
        c.process_id = int(rank) if rank else 0
        return c


@dataclass
class TrackingConfig:
    """Experiment tracking contract.

    Reference: MLFlowLogger(experiment_name="weather_forecasting",
    tracking_uri=env MLFLOW_TRACKING_URI default http://mlflow-server:5000)
    (jobs/train_lightning_ddp.py:92-96); best checkpoint uploaded to artifact
    path "best_checkpoints" from rank 0 (:146-164). Those names are load-
    bearing: the deploy DAGs query them (dags/azure_auto_deploy.py:32-39).
    """

    experiment: str = "weather_forecasting"
    tracking_uri: str | None = None
    artifact_path: str = "best_checkpoints"

    @classmethod
    def from_env(cls) -> "TrackingConfig":
        c = cls()
        c.experiment = _env("DCT_EXPERIMENT", c.experiment, str)
        c.tracking_uri = os.environ.get("MLFLOW_TRACKING_URI", c.tracking_uri)
        return c


@dataclass
class ProfileConfig:
    """Tracing/profiling window (absent in the reference — SURVEY §5.1:
    TensorBoard is installed but nothing writes it; the pipeline DAG's logs
    check warns on an empty dir, dags/pipeline.py:229-240).

    When enabled, the coordinator traces ONE epoch with ``jax.profiler``
    into a TensorBoard-compatible directory; per-epoch throughput metrics
    are logged to the tracker regardless.
    """

    enabled: bool = False
    trace_dir: str = "logs/profile"
    # Which epoch to trace (0-based). Default 1: epoch 0 pays compilation,
    # which would swamp the steady-state timeline.
    epoch: int = 1
    # On-demand flight recorder (observability/capture.py): touch this
    # file (or write a seconds value into it) and every rank starts a
    # jax.profiler capture at its next span boundary — no restart, no
    # pre-planned window. Each distinct file mtime fires once. "" turns
    # the file trigger off (SIGUSR2 still works when sigusr2 is set).
    trigger_path: str = "logs/profile.trigger"
    # Default capture length (seconds) when the trigger carries none;
    # the capture stops at the first span boundary past the deadline.
    capture_s: float = 5.0
    # Arm SIGUSR2 as a capture trigger (main thread only; worker-thread
    # trainers degrade to the file trigger automatically).
    sigusr2: bool = True

    @classmethod
    def from_env(cls) -> "ProfileConfig":
        c = cls()
        c.enabled = _env("DCT_PROFILE", c.enabled, bool)
        c.trace_dir = _env("DCT_TRACE_DIR", c.trace_dir, str)
        c.epoch = _env("DCT_PROFILE_EPOCH", c.epoch, int)
        c.trigger_path = _env("DCT_PROFILE_TRIGGER", c.trigger_path, str)
        c.capture_s = _env("DCT_PROF_CAPTURE_S", c.capture_s, float)
        c.sigusr2 = _env("DCT_PROF_SIGUSR2", c.sigusr2, bool)
        return c


@dataclass
class ObservabilityConfig:
    """The operator plane (dct_tpu.observability): structured event log,
    goodput/badput ledger, rank heartbeats, Prometheus metrics dump.

    ON by default — observability that must be remembered per-run is
    observability that is absent during the incident. All sinks live
    under ``logs/`` (gitignored) unless redirected; every writer
    degrades to a no-op on OS errors, so a full disk never fails a run.

    ``run_id`` is the run-correlation ID stamped on every event record:
    normally minted by the DAG/launcher and delivered via ``DCT_RUN_ID``
    so all ranks of one continuous-training cycle agree; a process that
    was never launched mints its own.
    """

    enabled: bool = True
    events_dir: str = "logs/events"
    run_id: str | None = None
    heartbeat_dir: str = "logs/heartbeats"
    # Same-phase heartbeats inside this window are throttled (writes are
    # tiny, but per-step beats must not become an I/O hot loop).
    heartbeat_interval: float = 5.0
    # A heartbeat older than this marks its rank stalled to the monitor.
    heartbeat_stall_seconds: float = 120.0
    # End-of-run Prometheus text dump; "" = <events_dir>/train_metrics.prom.
    metrics_path: str = ""
    # Distributed-tracing span files; "" = <events_dir>/spans. The
    # trace_id is the run-correlation ID; parent spans propagate to
    # child processes via DCT_SPAN_ID (observability/spans.py).
    spans_dir: str = ""
    # Training-health policy (observability/health.py): halt the run on
    # a non-finite loss / on a loss-or-grad-norm spike, or (default)
    # warn via health.* events and keep training. The z-score detector
    # compares each step against a rolling window of recent history.
    halt_on_nan: bool = False
    halt_on_spike: bool = False
    spike_zscore: float = 8.0
    spike_window: int = 16
    # Telemetry write batching (events + spans; observability/buffered.py).
    # 0 = write-through: every record reaches the OS before emit returns
    # (the historical per-record durability, minus the open()-per-record
    # syscall tax — a persistent handle is kept either way). > 0 = batch
    # appends for up to this many seconds (or telemetry_flush_records
    # lines), flushed on trainer exit paths, fault firing, and atexit;
    # a SIGKILL can cost at most that window of telemetry. Heartbeat
    # files are NEVER buffered — a buffered liveness signal is a dead
    # one — they are throttled by heartbeat_interval instead.
    telemetry_flush_s: float = 0.25
    telemetry_flush_records: int = 128
    # Cross-process metrics plane (observability/{metrics,aggregate}.py;
    # docs/OBSERVABILITY.md "Metrics plane"): processes publish atomic
    # registry snapshots under this dir and any /metrics scrape merges
    # the live siblings into fleet totals. "" = plane off (the serving
    # CLI jobs/serve.py arms logs/metrics by default; library-built
    # servers stay local-only unless the env opts in).
    metrics_dir: str = ""
    # Min seconds between snapshot publishes per process (the hot-path
    # throttle; an idle-process timer republishes on the same cadence).
    metrics_publish_s: float = 2.0
    # A LIVE process's snapshot older than this stops counting (dead
    # pids drop immediately; `final` batch snapshots never age out).
    metrics_stale_s: float = 30.0
    # SLO monitoring over the aggregated series (observability/slo.py
    # grammar): e.g. "availability:0.999;latency:0.25@0.95;goodput:0.5;
    # freshness:3600". Evaluated at scrape time by whichever process
    # answers /metrics; alerts emit slo.alert events + dct_slo_* gauges.
    slo_spec: str = "availability:0.999;latency:0.5@0.95"
    # Multi-window burn-rate rule: alert only when BOTH windows burn
    # error budget above the threshold (1.0 = exactly budget rate).
    slo_fast_window_s: float = 300.0
    slo_slow_window_s: float = 3600.0
    slo_burn_threshold: float = 1.0
    # Telemetry history plane (observability/timeseries.py; ISSUE 17):
    # every SnapshotPublisher also appends its snapshots to per-process
    # segment files under this dir. "" = plane off (the default — the
    # instantaneous metrics plane is untouched).
    ts_dir: str = ""
    # Comma-separated fnmatch patterns selecting the recorded families.
    ts_families: str = ""
    # Segment seal thresholds: points per raw segment / max segment age.
    ts_seg_points: int = 240
    ts_seg_s: float = 600.0
    # Active-segment republish cadence (appends between flushes are
    # memory-only — the armed-publish overhead budget lives here).
    ts_flush_s: float = 10.0
    # Sealed raw segments older than downsample_s fold into the coarse
    # ds tier (ds_res_s-wide bins); anything older than retention_s is
    # deleted at compaction time.
    ts_retention_s: float = 10800.0
    ts_downsample_s: float = 900.0
    ts_ds_res_s: float = 60.0
    # Online anomaly detection over the history store (detect.py):
    # EWMA/z-score change detection, edge-triggered like the SLO
    # monitor. Armed only when ts_dir is set.
    anomaly: bool = True
    anomaly_z: float = 4.0
    anomaly_alpha: float = 0.3
    anomaly_min_points: int = 8
    anomaly_window_s: float = 30.0
    anomaly_poll_s: float = 2.0
    # Auto-assembled incident bundles (incident.py): anomaly / SLO
    # triggers snapshot the surrounding window + events + lineage into
    # incidents/<stamp>-<signal>/. "" dir = sibling of ts_dir.
    incident: bool = True
    incident_dir: str = ""
    incident_window_s: float = 120.0
    incident_cooldown_s: float = 300.0
    # Fire the PR 14 flight recorder into each bundle (profile/).
    incident_profile: bool = False
    incident_profile_s: float = 2.0

    @classmethod
    def from_env(cls) -> "ObservabilityConfig":
        c = cls()
        c.enabled = _env("DCT_OBSERVABILITY", c.enabled, bool)
        c.events_dir = _env("DCT_EVENTS_DIR", c.events_dir, str)
        c.run_id = os.environ.get("DCT_RUN_ID") or c.run_id
        c.heartbeat_dir = _env("DCT_HEARTBEAT_DIR", c.heartbeat_dir, str)
        c.heartbeat_interval = _env(
            "DCT_HEARTBEAT_INTERVAL", c.heartbeat_interval, float
        )
        c.heartbeat_stall_seconds = _env(
            "DCT_HEARTBEAT_STALL_SECONDS", c.heartbeat_stall_seconds, float
        )
        c.metrics_path = _env("DCT_METRICS_PROM", c.metrics_path, str)
        c.spans_dir = _env("DCT_SPANS_DIR", c.spans_dir, str)
        c.halt_on_nan = _env("DCT_HALT_ON_NAN", c.halt_on_nan, bool)
        c.halt_on_spike = _env("DCT_HALT_ON_SPIKE", c.halt_on_spike, bool)
        c.spike_zscore = _env("DCT_SPIKE_ZSCORE", c.spike_zscore, float)
        c.spike_window = _env("DCT_SPIKE_WINDOW", c.spike_window, int)
        c.telemetry_flush_s = _env(
            "DCT_TELEMETRY_FLUSH_S", c.telemetry_flush_s, float
        )
        c.telemetry_flush_records = _env(
            "DCT_TELEMETRY_FLUSH_RECORDS", c.telemetry_flush_records, int
        )
        c.metrics_dir = _env("DCT_METRICS_DIR", c.metrics_dir, str)
        c.metrics_publish_s = _env(
            "DCT_METRICS_PUBLISH_S", c.metrics_publish_s, float
        )
        c.metrics_stale_s = _env(
            "DCT_METRICS_STALE_S", c.metrics_stale_s, float
        )
        c.slo_spec = _env("DCT_SLO_SPEC", c.slo_spec, str)
        c.slo_fast_window_s = _env(
            "DCT_SLO_FAST_WINDOW_S", c.slo_fast_window_s, float
        )
        c.slo_slow_window_s = _env(
            "DCT_SLO_SLOW_WINDOW_S", c.slo_slow_window_s, float
        )
        c.slo_burn_threshold = _env(
            "DCT_SLO_BURN_THRESHOLD", c.slo_burn_threshold, float
        )
        c.ts_dir = _env("DCT_TS_DIR", c.ts_dir, str)
        c.ts_families = _env("DCT_TS_FAMILIES", c.ts_families, str)
        c.ts_seg_points = _env("DCT_TS_SEG_POINTS", c.ts_seg_points, int)
        c.ts_seg_s = _env("DCT_TS_SEG_S", c.ts_seg_s, float)
        c.ts_flush_s = _env("DCT_TS_FLUSH_S", c.ts_flush_s, float)
        c.ts_retention_s = _env("DCT_TS_RETENTION_S", c.ts_retention_s, float)
        c.ts_downsample_s = _env(
            "DCT_TS_DOWNSAMPLE_S", c.ts_downsample_s, float
        )
        c.ts_ds_res_s = _env("DCT_TS_DS_RES_S", c.ts_ds_res_s, float)
        c.anomaly = _env("DCT_ANOMALY", c.anomaly, bool)
        c.anomaly_z = _env("DCT_ANOMALY_Z", c.anomaly_z, float)
        c.anomaly_alpha = _env("DCT_ANOMALY_ALPHA", c.anomaly_alpha, float)
        c.anomaly_min_points = _env(
            "DCT_ANOMALY_MIN_POINTS", c.anomaly_min_points, int
        )
        c.anomaly_window_s = _env(
            "DCT_ANOMALY_WINDOW_S", c.anomaly_window_s, float
        )
        c.anomaly_poll_s = _env(
            "DCT_ANOMALY_POLL_S", c.anomaly_poll_s, float
        )
        c.incident = _env("DCT_INCIDENT", c.incident, bool)
        c.incident_dir = _env("DCT_INCIDENT_DIR", c.incident_dir, str)
        c.incident_window_s = _env(
            "DCT_INCIDENT_WINDOW_S", c.incident_window_s, float
        )
        c.incident_cooldown_s = _env(
            "DCT_INCIDENT_COOLDOWN_S", c.incident_cooldown_s, float
        )
        c.incident_profile = _env(
            "DCT_INCIDENT_PROFILE", c.incident_profile, bool
        )
        c.incident_profile_s = _env(
            "DCT_INCIDENT_PROFILE_S", c.incident_profile_s, float
        )
        return c


@dataclass
class ResilienceConfig:
    """Self-healing knobs (dct_tpu.resilience; docs/ROBUSTNESS.md):
    supervised relaunch-and-resume, graceful preemption, fault
    injection, and transient-network retry policy.

    The supervisor-side knobs (``max_restarts``, backoff) govern
    whoever babysits the world — :meth:`LocalProcessLauncher.supervise`
    or the ``python -m dct_tpu.resilience.supervise`` CLI; the rank-side
    knobs (``graceful_preemption``, ``fault_spec``) govern the trainer.
    ``startup_debt_s`` is supervisor-set plumbing
    (``DCT_STARTUP_RECOVERY_DEBT_S``): the wall clock lost to the failed
    attempts, booked by the relaunched trainer as ``startup_recovery``
    badput so the cycle's goodput accounting stays honest.
    """

    max_restarts: int = 2
    restart_backoff_s: float = 5.0
    restart_backoff_factor: float = 2.0
    restart_jitter: float = 0.1
    preempt_grace_s: float = 30.0
    # Honor SIGTERM cooperatively: finish the in-flight step, save a
    # resume checkpoint, exit EXIT_PREEMPTED (75). Off = die like the
    # reference does.
    graceful_preemption: bool = True
    # Deterministic fault plan (resilience.faults grammar), e.g.
    # "crash@rank1:epoch2,slow_save". Empty = no faults.
    fault_spec: str = ""
    fault_sleep_s: float = 3.0
    # Transient-network retry policy (tracking client, deploy rollout).
    retry_max_attempts: int = 3
    retry_backoff_s: float = 0.5
    # Supervisor-set: lost wall clock to book as startup_recovery badput.
    startup_debt_s: float = 0.0

    @classmethod
    def from_env(cls) -> "ResilienceConfig":
        c = cls()
        c.max_restarts = _env("DCT_MAX_RESTARTS", c.max_restarts, int)
        c.restart_backoff_s = _env(
            "DCT_RESTART_BACKOFF_S", c.restart_backoff_s, float
        )
        c.restart_backoff_factor = _env(
            "DCT_RESTART_BACKOFF_FACTOR", c.restart_backoff_factor, float
        )
        c.restart_jitter = _env("DCT_RESTART_JITTER", c.restart_jitter, float)
        c.preempt_grace_s = _env(
            "DCT_PREEMPT_GRACE_S", c.preempt_grace_s, float
        )
        c.graceful_preemption = _env(
            "DCT_GRACEFUL_PREEMPTION", c.graceful_preemption, bool
        )
        c.fault_spec = _env("DCT_FAULT_SPEC", c.fault_spec, str)
        c.fault_sleep_s = _env("DCT_FAULT_SLEEP_S", c.fault_sleep_s, float)
        c.retry_max_attempts = _env(
            "DCT_RETRY_MAX_ATTEMPTS", c.retry_max_attempts, int
        )
        c.retry_backoff_s = _env(
            "DCT_RETRY_BACKOFF_S", c.retry_backoff_s, float
        )
        c.startup_debt_s = _env(
            "DCT_STARTUP_RECOVERY_DEBT_S", c.startup_debt_s, float
        )
        return c


@dataclass
class EvaluationConfig:
    """Continuous-evaluation knobs (dct_tpu.evaluation; docs/EVALUATION.md):
    the champion/challenger offline eval harness, the statistical
    promotion gates between rollout stages, and the drift detectors.

    The gate's null hypothesis is "the challenger is NOT worse": by
    default a cycle promotes unless the evidence says it regressed
    (``require_improvement`` flips that to "promote only on proven
    improvement"). All stochastic machinery (the paired bootstrap) is
    seeded from ``seed`` so a gate decision is reproducible from its
    evidence. ``DCT_DRIFT_THRESHOLD`` (the ETL-side stats gate in
    etl/preprocess.py) is a different, older knob; the deploy-side
    detectors here use PSI/KS against the snapshot stamped into the
    deploy package.
    """

    # Consult a PromotionGate between rollout stages (shadow -> canary
    # -> full). Off = the reference's timer-only walk.
    gate_enabled: bool = True
    # Mean per-example loss delta (champion - challenger) the challenger
    # must exceed to count as an improvement.
    min_improvement: float = 0.0
    # Mean regression tolerated before the gate blocks (challenger mean
    # loss may exceed champion's by at most this).
    max_regression: float = 0.0
    # One-sided confidence required of the paired bootstrap before a
    # delta counts as evidence (0.95 = the regression must be outside
    # the bootstrap's 95% band).
    confidence: float = 0.95
    bootstrap_samples: int = 1000
    # Bootstrap RNG seed: gate decisions must be deterministic.
    seed: int = 42
    # Worst tolerated per-slice loss regression (e.g. the rain slice may
    # not get this much worse even if the aggregate improved).
    max_slice_regression: float = 0.25
    # Promote only on statistically-significant improvement (default:
    # promote unless significantly worse — continuous-training default).
    require_improvement: bool = False
    # Examples per forward pass in the offline harness.
    eval_batch: int = 1024
    # 'numpy' = the serving twin (identical math to the deployed
    # score.py); 'jax' = jitted batched apply sharded over the mesh
    # data axis (the training-side inference path, for dataset-scale
    # eval splits on accelerator rigs).
    engine: str = "numpy"
    # Missing prerequisites (no champion, no eval data, unreadable
    # package): promote with a warning (True) or hold (False). A real
    # failing evaluation always blocks regardless.
    fail_open: bool = True
    # Gate-decision ledger consumed by /metrics; "" = <events_dir>/
    # gate_ledger.json.
    ledger_path: str = ""
    # Drift detectors: PSI above this flags a feature (industry rule of
    # thumb: 0.1 moderate, 0.2 major); KS D-statistic threshold; bins
    # for the stamped quantile snapshot; shadow-stage prediction
    # disagreement rate above which the shadow->canary gate holds.
    psi_threshold: float = 0.2
    ks_threshold: float = 0.15
    drift_bins: int = 10
    max_disagreement: float = 0.25

    @classmethod
    def from_env(cls) -> "EvaluationConfig":
        c = cls()
        c.gate_enabled = _env("DCT_GATE", c.gate_enabled, bool)
        c.min_improvement = _env(
            "DCT_GATE_MIN_IMPROVEMENT", c.min_improvement, float
        )
        c.max_regression = _env(
            "DCT_GATE_MAX_REGRESSION", c.max_regression, float
        )
        c.confidence = _env("DCT_GATE_CONFIDENCE", c.confidence, float)
        c.bootstrap_samples = _env(
            "DCT_GATE_BOOTSTRAP", c.bootstrap_samples, int
        )
        c.seed = _env("DCT_GATE_SEED", c.seed, int)
        c.max_slice_regression = _env(
            "DCT_GATE_MAX_SLICE_REGRESSION", c.max_slice_regression, float
        )
        c.require_improvement = _env(
            "DCT_GATE_REQUIRE_IMPROVEMENT", c.require_improvement, bool
        )
        c.eval_batch = _env("DCT_GATE_EVAL_BATCH", c.eval_batch, int)
        c.engine = _env("DCT_GATE_ENGINE", c.engine, str).strip().lower()
        c.fail_open = _env("DCT_GATE_FAIL_OPEN", c.fail_open, bool)
        c.ledger_path = _env("DCT_GATE_LEDGER", c.ledger_path, str)
        c.psi_threshold = _env("DCT_DRIFT_PSI", c.psi_threshold, float)
        c.ks_threshold = _env("DCT_DRIFT_KS", c.ks_threshold, float)
        c.drift_bins = _env("DCT_DRIFT_BINS", c.drift_bins, int)
        c.max_disagreement = _env(
            "DCT_DRIFT_MAX_DISAGREEMENT", c.max_disagreement, float
        )
        return c


@dataclass
class ServingConfig:
    """High-throughput serving tier knobs (dct_tpu.serving;
    docs/SERVING.md): the dynamic micro-batcher behind both HTTP server
    modes, the scoring worker pool, and the load-generation bench.

    The batcher merges compatible in-flight requests into one stacked
    forward — up to ``max_batch`` rows, waiting at most
    ``batch_window_ms`` past the oldest queued request for co-arrivals.
    ``batch_window_ms=0`` (default) is purely opportunistic: whatever
    is queued when a worker frees up merges, and an idle server adds
    zero latency; raise it to trade p50 for bigger batches under
    open-loop trickle traffic. Batched scoring is bit-identical to
    per-request scoring (serving/batching.py module docstring).
    """

    # Flush cap in ROWS (a request always flushes whole).
    max_batch: int = 64
    # Co-arrival deadline window in milliseconds (0 = opportunistic).
    batch_window_ms: float = 0.0
    # Scoring worker threads draining the batch queue (numpy releases
    # the GIL inside stacked GEMMs; 0 = score inline on the handler
    # thread through the same code path).
    workers: int = 2
    # Serving PROCESSES sharing one port via SO_REUSEPORT (ServerPool):
    # one Python process tops out at its GIL, N processes multiply the
    # ceiling. 1 = no fork (the safe default inside threaded hosts);
    # raise it on dedicated serving entry points (jobs/serve.py).
    processes: int = 1
    # 'numpy' (default; bit-identity guarantee) | 'jax' (jitted registry
    # model — the throughput choice for transformer/MoE on accelerator
    # rigs; matches numpy to ~2e-6, the harness's engine-parity band).
    engine: str = "numpy"
    # Zero-copy payload parsing: ndarray straight from the raw JSON
    # envelope bytes, no intermediate Python lists (runtime.
    # parse_envelope_array); non-rectangular payloads fall back to
    # json.loads transparently. Off = always json.loads.
    fast_parse: bool = True
    # Load generator (serving/loadgen.py): open-loop target qps (0 = closed loop), per-level wall
    # budget, requests per concurrency level, and the sweep's levels.
    loadgen_qps: float = 0.0
    loadgen_duration_s: float = 2.0
    loadgen_requests: int = 300
    loadgen_concurrency: str = "1,4,16"
    # --- elasticity (docs/SERVING.md §elasticity) ---------------------
    # Admission control: bounded queues + priority shedding. Off by
    # default — a library-built server keeps PR 7 semantics unless the
    # operator arms the control loop.
    admit: bool = False
    # Request header carrying the priority class (high|normal|low).
    priority_header: str = "x-dct-priority"
    # Queue budget in ROWS: low sheds at 50%, normal at 80%, high at
    # the cap (admission.CLASS_BUDGET_FRACTIONS).
    admit_max_queue: int = 256
    # Queue-wait budget (ms) estimated from the batcher's recent
    # service rate; 0 disables the wait leg (depth-only shedding).
    admit_wait_ms: float = 500.0
    # Base Retry-After for shed 429s; consecutive sheds of a class
    # escalate it exponentially with jitter (the PR 3 retry curve).
    retry_after_s: float = 0.25
    # Closed-loop autoscaler: scales ServerPool PROCESSES (pool mode)
    # or batcher WORKER threads (in-process) between min/max off the
    # queue-depth / SLO-burn / shed signals.
    autoscale: bool = False
    scale_min: int = 1
    scale_max: int = 4
    # Queue-rows thresholds: sustained >= up scales out, <= down scales
    # in (between them the controller holds).
    scale_up_queue: float = 32.0
    scale_down_queue: float = 2.0
    scale_poll_s: float = 1.0
    # Consecutive agreeing polls before a scale step (anti-flap).
    scale_hysteresis: int = 2
    # Seconds after any scale event before the next may fire.
    scale_cooldown_s: float = 5.0
    # Self-healing pool: respawn budget before the circuit breaks and
    # the pool exits nonzero (exponential backoff between respawns).
    max_restarts: int = 3

    @classmethod
    def from_env(cls) -> "ServingConfig":
        c = cls()
        c.max_batch = _env("DCT_SERVE_MAX_BATCH", c.max_batch, int)
        c.batch_window_ms = _env(
            "DCT_SERVE_BATCH_WINDOW_MS", c.batch_window_ms, float
        )
        c.workers = _env("DCT_SERVE_WORKERS", c.workers, int)
        c.processes = _env("DCT_SERVE_PROCS", c.processes, int)
        c.engine = _env("DCT_SERVE_ENGINE", c.engine, str).strip().lower()
        c.fast_parse = _env("DCT_SERVE_FAST_PARSE", c.fast_parse, bool)
        c.loadgen_qps = _env(
            "DCT_SERVE_LOADGEN_QPS", c.loadgen_qps, float
        )
        c.loadgen_duration_s = _env(
            "DCT_SERVE_LOADGEN_DURATION_S", c.loadgen_duration_s, float
        )
        c.loadgen_requests = _env(
            "DCT_SERVE_LOADGEN_REQUESTS", c.loadgen_requests, int
        )
        c.loadgen_concurrency = _env(
            "DCT_SERVE_LOADGEN_CONCURRENCY", c.loadgen_concurrency, str
        )
        c.admit = _env("DCT_SERVE_ADMIT", c.admit, bool)
        c.priority_header = _env(
            "DCT_SERVE_PRIORITY_HEADER", c.priority_header, str
        ).strip().lower()
        c.admit_max_queue = _env(
            "DCT_SERVE_ADMIT_MAX_QUEUE", c.admit_max_queue, int
        )
        c.admit_wait_ms = _env(
            "DCT_SERVE_ADMIT_WAIT_MS", c.admit_wait_ms, float
        )
        c.retry_after_s = _env(
            "DCT_SERVE_RETRY_AFTER_S", c.retry_after_s, float
        )
        c.autoscale = _env("DCT_SERVE_AUTOSCALE", c.autoscale, bool)
        c.scale_min = _env("DCT_SERVE_SCALE_MIN", c.scale_min, int)
        c.scale_max = _env("DCT_SERVE_SCALE_MAX", c.scale_max, int)
        c.scale_up_queue = _env(
            "DCT_SERVE_SCALE_UP_Q", c.scale_up_queue, float
        )
        c.scale_down_queue = _env(
            "DCT_SERVE_SCALE_DOWN_Q", c.scale_down_queue, float
        )
        c.scale_poll_s = _env(
            "DCT_SERVE_SCALE_POLL_S", c.scale_poll_s, float
        )
        c.scale_hysteresis = _env(
            "DCT_SERVE_SCALE_HYSTERESIS", c.scale_hysteresis, int
        )
        c.scale_cooldown_s = _env(
            "DCT_SERVE_SCALE_COOLDOWN_S", c.scale_cooldown_s, float
        )
        c.max_restarts = _env(
            "DCT_SERVE_MAX_RESTARTS", c.max_restarts, int
        )
        return c

    def concurrency_levels(self) -> list[int]:
        """The loadgen sweep's concurrency levels, parsed and sanitized
        (bad tokens dropped; at least level 1 always present)."""
        levels = []
        for tok in str(self.loadgen_concurrency).split(","):
            tok = tok.strip()
            if tok.isdigit() and int(tok) > 0:
                levels.append(int(tok))
        return sorted(set(levels)) or [1]


@dataclass
class LoopConfig:
    """Always-on overlapped cycles (dct_tpu.continuous;
    docs/CONTINUOUS.md): ingest watcher, continuous training rounds,
    and the concurrent evaluator that promotes mid-run.

    The loop replaces the episodic DAG clock (ROADMAP item 3): ETL,
    training, gating and deploy overlap instead of serializing, so
    data-arrival -> deployed-model freshness is bounded by stage
    latency, not cycle latency. Budgets (``max_*``) exist for smokes
    and benches; production leaves them 0 (run until SIGTERM).
    """

    # Ingest watcher poll cadence over the raw staging CSV (stat-based
    # pre-check; content digest decides no-op vs delta vs rebuild).
    poll_s: float = 2.0
    # Evaluator poll cadence over the deploy-tier best checkpoint.
    eval_poll_s: float = 2.0
    # Epochs per training round — the loop's train quantum. Small keeps
    # fresh data's wait-for-round short; each round EXTENDS the same
    # optimizer trajectory (DCT_RESUME semantics).
    epochs_per_round: int = 2
    # 'supervised' = each round runs under the PR 3 supervisor
    # (crash/hang/preemption healing, compile-cache continuity across
    # relaunches); 'inline' = Trainer.fit in-process (benches/tests).
    train_mode: str = "supervised"
    # Rollout soak per stage (shadow/canary dwell) for mid-run
    # promotions — the loop's evaluator overlaps these with training.
    soak_s: float = 5.0
    # Local endpoint name the loop promotes into.
    endpoint: str = "weather-loop"
    # Challenger package root (one package dir per promotion attempt;
    # slot-referenced packages are retained, stale ones pruned).
    packages_dir: str = "data/loop_packages"
    # Stop budgets: 0 = unbounded (production always-on).
    max_rounds: int = 0
    max_wall_s: float = 0.0
    max_promotions: int = 0

    @classmethod
    def from_env(cls) -> "LoopConfig":
        c = cls()
        c.poll_s = _env("DCT_LOOP_POLL_S", c.poll_s, float)
        c.eval_poll_s = _env("DCT_LOOP_EVAL_POLL_S", c.eval_poll_s, float)
        c.epochs_per_round = _env(
            "DCT_LOOP_EPOCHS_PER_ROUND", c.epochs_per_round, int
        )
        c.train_mode = _env(
            "DCT_LOOP_TRAIN_MODE", c.train_mode, str
        ).strip().lower()
        c.soak_s = _env("DCT_LOOP_SOAK_S", c.soak_s, float)
        c.endpoint = _env("DCT_LOOP_ENDPOINT", c.endpoint, str)
        c.packages_dir = _env("DCT_LOOP_PACKAGES_DIR", c.packages_dir, str)
        c.max_rounds = _env("DCT_LOOP_MAX_ROUNDS", c.max_rounds, int)
        c.max_wall_s = _env("DCT_LOOP_MAX_WALL_S", c.max_wall_s, float)
        c.max_promotions = _env(
            "DCT_LOOP_MAX_PROMOTIONS", c.max_promotions, int
        )
        return c


@dataclass
class StreamConfig:
    """Streaming ingest data plane (dct_tpu.stream; docs/STREAMING.md):
    per-tenant partitioned event logs, consumer-group offsets, and the
    exactly-once stream ETL.

    ``mode`` (``DCT_INGEST_MODE``) selects the continuous loop's ingest
    source: ``poll`` keeps the CSV stat-polling watcher (the default,
    reference-shaped path), ``stream`` consumes the partitioned event
    log under ``dir``/``topic`` through consumer group ``group``.
    Backpressure bounds consumer lag: when the slowest registered group
    falls more than ``lag_budget`` records behind, producers ``block``
    (up to ``block_timeout_s``, then shed) or ``shed`` outright —
    unbounded lag is unexpressible.
    """

    mode: str = "poll"
    dir: str = "data/stream"
    topic: str = "events"
    partitions: int = 1
    segment_records: int = 4096
    segment_bytes: int = 1 << 22
    group: str = "etl"
    backpressure: str = "block"
    lag_budget: int = 50000
    block_timeout_s: float = 30.0
    # Records consumed per ETL pass (one pass = one parquet part).
    max_batch: int = 8192
    # Stream-watcher poll cadence. Deliberately MUCH tighter than the
    # CSV watcher's DCT_LOOP_POLL_S: a no-change stream poll reads two
    # sidecar JSONs (~µs), where the CSV path's change-processing
    # re-hashes the whole staging file — the cheap pre-check is what
    # buys sub-second arrival→trainable freshness.
    poll_s: float = 0.1

    @classmethod
    def from_env(cls) -> "StreamConfig":
        c = cls()
        c.mode = _env("DCT_INGEST_MODE", c.mode, str).strip().lower()
        c.dir = _env("DCT_STREAM_DIR", c.dir, str)
        c.topic = _env("DCT_STREAM_TOPIC", c.topic, str)
        c.partitions = max(
            1, _env("DCT_STREAM_PARTITIONS", c.partitions, int)
        )
        c.segment_records = _env(
            "DCT_STREAM_SEGMENT_RECORDS", c.segment_records, int
        )
        c.segment_bytes = _env(
            "DCT_STREAM_SEGMENT_BYTES", c.segment_bytes, int
        )
        c.group = _env("DCT_STREAM_GROUP", c.group, str)
        c.backpressure = _env(
            "DCT_STREAM_BACKPRESSURE", c.backpressure, str
        ).strip().lower()
        c.lag_budget = _env("DCT_STREAM_LAG_BUDGET", c.lag_budget, int)
        c.block_timeout_s = _env(
            "DCT_STREAM_BLOCK_TIMEOUT_S", c.block_timeout_s, float
        )
        c.max_batch = _env("DCT_STREAM_MAX_BATCH", c.max_batch, int)
        c.poll_s = _env("DCT_STREAM_POLL_S", c.poll_s, float)
        return c


@dataclass
class SchedulerConfig:
    """Multi-tenant workload scheduler (dct_tpu.scheduler;
    docs/SCHEDULER.md): N always-on tenants sharing one pod with
    chip-time quota, priority classes, and fault isolation.

    ``spec`` is the tenant roster — inline JSON or a ``tenants.json``
    path (grammar in scheduler/spec.py). Training rounds time-share the
    chips through round leases granted by strict priority class then
    weighted deficit; ``concurrent`` leases may run at once (1 = the
    whole pod is one shared mesh, the default). A starved higher-class
    waiter preempts a running lower-class round gracefully after
    ``preempt_wait_s`` (0 = never preempt — strictly boundary-granted).
    ``shared_cache`` pins one compile/AOT store under ``root`` so
    same-family tenants amortize each other's compiles. Budgets
    (``max_*``) exist for smokes and benches; production leaves them 0.
    """

    spec: str = ""
    root: str = "data/tenants"
    concurrent: int = 1
    poll_s: float = 0.5
    preempt_wait_s: float = 0.0
    shared_cache: bool = True
    max_wall_s: float = 0.0
    max_rounds: int = 0

    @classmethod
    def from_env(cls) -> "SchedulerConfig":
        c = cls()
        c.spec = _env("DCT_TENANTS", c.spec, str)
        c.root = _env("DCT_SCHED_ROOT", c.root, str)
        c.concurrent = max(1, _env("DCT_SCHED_CONCURRENT", c.concurrent, int))
        c.poll_s = _env("DCT_SCHED_POLL_S", c.poll_s, float)
        c.preempt_wait_s = _env(
            "DCT_SCHED_PREEMPT_WAIT_S", c.preempt_wait_s, float
        )
        c.shared_cache = _env(
            "DCT_SCHED_SHARED_CACHE", c.shared_cache, bool
        )
        c.max_wall_s = _env("DCT_SCHED_MAX_WALL_S", c.max_wall_s, float)
        c.max_rounds = _env("DCT_SCHED_MAX_ROUNDS", c.max_rounds, int)
        return c


@dataclass
class MpmdConfig:
    """MPMD pipeline-parallel trainer knobs (dct_tpu.parallel.mpmd;
    docs/PARALLELISM.md §MPMD): distinct per-stage programs on disjoint
    device slices with explicit inter-stage transfers.

    ``stages`` is the stage map — a stage count (``"2"``, devices split
    evenly) or explicit per-stage device counts (``"2,1,1"`` — stages
    may be heterogeneous). The grammar is validated LOUDLY at parse
    time (:func:`dct_tpu.parallel.mpmd.parse_stage_spec`), like
    ``DCT_SHARD_RULES``: a typo'd stage map raises, it never silently
    trains single-stage. ``schedule`` picks the per-stage op order:
    ``1f1b`` (PipeDream-flush — bubble confined to fill/drain, steady
    state saturated) or ``gpipe`` (all-forward-then-all-backward, the
    A/B comparator). ``microbatches`` 0 = 2x the stage count.
    """

    stages: str = "2"
    microbatches: int = 0
    schedule: str = "1f1b"
    transfer_timeout_s: float = 120.0
    port_base: int = 29600

    @classmethod
    def from_env(cls) -> "MpmdConfig":
        c = cls()
        c.stages = _env("DCT_MPMD_STAGES", c.stages, str)
        c.microbatches = _env("DCT_MPMD_MICROBATCHES", c.microbatches, int)
        c.schedule = _env(
            "DCT_MPMD_SCHEDULE", c.schedule, str
        ).strip().lower()
        c.transfer_timeout_s = _env(
            "DCT_MPMD_TRANSFER_TIMEOUT_S", c.transfer_timeout_s, float
        )
        c.port_base = _env("DCT_MPMD_PORT_BASE", c.port_base, int)
        return c

    def to_spec(self, *, n_devices: int | None = None):
        """Parse/validate into an :class:`dct_tpu.parallel.mpmd
        .MpmdSpec` — every malformed clause raises ``MpmdSpecError``
        naming the offending knob."""
        from dct_tpu.parallel.mpmd import spec_from_env_values

        return spec_from_env_values(
            self.stages, self.microbatches, self.schedule,
            self.transfer_timeout_s, self.port_base, n_devices=n_devices,
        )


@dataclass
class RunConfig:
    """Top-level bundle passed to the Trainer."""

    data: DataConfig = field(default_factory=DataConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    dist: DistributedConfig = field(default_factory=DistributedConfig)
    tracking: TrackingConfig = field(default_factory=TrackingConfig)
    profile: ProfileConfig = field(default_factory=ProfileConfig)
    obs: ObservabilityConfig = field(default_factory=ObservabilityConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    evaluation: EvaluationConfig = field(default_factory=EvaluationConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    loop: LoopConfig = field(default_factory=LoopConfig)
    stream: StreamConfig = field(default_factory=StreamConfig)
    sched: SchedulerConfig = field(default_factory=SchedulerConfig)
    mpmd: MpmdConfig = field(default_factory=MpmdConfig)

    @classmethod
    def from_env(cls) -> "RunConfig":
        return cls(
            data=DataConfig.from_env(),
            model=ModelConfig.from_env(),
            train=TrainConfig.from_env(),
            mesh=MeshConfig.from_env(),
            dist=DistributedConfig.from_env(),
            tracking=TrackingConfig.from_env(),
            profile=ProfileConfig.from_env(),
            obs=ObservabilityConfig.from_env(),
            resilience=ResilienceConfig.from_env(),
            evaluation=EvaluationConfig.from_env(),
            serving=ServingConfig.from_env(),
            loop=LoopConfig.from_env(),
            stream=StreamConfig.from_env(),
            sched=SchedulerConfig.from_env(),
            mpmd=MpmdConfig.from_env(),
        )

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# ======================================================================
# The DCT_* environment registry — the contract of record.
#
# Every DCT_-prefixed environment variable any first-party code reads
# (or exports into a child process) is declared here with a one-line
# description, and mirrored in `.env.example`. The `env-registry`
# dct-lint rule (docs/ANALYSIS.md) holds the three surfaces equal:
# an undeclared read, an undocumented entry, and a dead entry are all
# findings. Entries that are dataclass knobs above carry no extra
# authority — the dict exists so the knob surface (bench, DAG plumbing,
# launcher-exported IDs included) has ONE greppable index that cannot
# silently drift from the code.
# ======================================================================

ENV_REGISTRY: dict[str, str] = {
    # --- data / filesystem contract --------------------------------
    "DCT_PROCESSED_DIR": "Spark/pandas ETL output dir (parquet)",
    "DCT_RAW_CSV": "raw weather CSV the ETL ingests",
    "DCT_MODELS_DIR": "deploy-tier checkpoints + train_state root",
    "DCT_VAL_FRACTION": "held-out validation fraction (reference 0.2)",
    # --- model family ----------------------------------------------
    "DCT_MODEL": "registry model name (weather_mlp | transformers | moe)",
    "DCT_HIDDEN_DIM": "MLP hidden width (reference 64)",
    "DCT_NUM_CLASSES": "classifier classes (reference 2: rain/no-rain)",
    "DCT_DROPOUT": "dropout rate (reference 0.2)",
    "DCT_SEQ_LEN": "sequence families: window length",
    "DCT_D_MODEL": "transformer encoder width",
    "DCT_N_HEADS": "attention heads",
    "DCT_N_LAYERS": "encoder blocks",
    "DCT_D_FF": "feed-forward width",
    "DCT_N_EXPERTS": "MoE expert count",
    "DCT_CAPACITY_FACTOR": "MoE switch-routing capacity factor",
    "DCT_ROUTER_AUX_WEIGHT": "MoE load-balance aux-loss weight",
    "DCT_MOE_DISPATCH": "MoE dispatch engine: einsum | sorted | auto",
    "DCT_MOE_AUTO_THRESHOLD": "auto dispatch crossover (one-hot elements)",
    "DCT_ROUTER_TOP_K": "MoE top-k routing (1 = switch)",
    "DCT_N_STAGES": "pipeline-parallel stage count",
    "DCT_N_MICROBATCHES": "GPipe microbatches (default = stages)",
    "DCT_HORIZON": "causal family: forecast horizon H",
    "DCT_REMAT": "activation rematerialization on/off",
    "DCT_ATTN_WINDOW": "sliding-window local attention (0 = full causal)",
    "DCT_N_KV_HEADS": "grouped-query attention KV heads (0 = MHA)",
    "DCT_HEAD_DIM": "causal families: a head's width (0 = d_model / n_heads)",
    "DCT_POS_EMBED": "position encoding: sincos | rope",
    "DCT_ROPE_THETA": "rotary frequency base (default 10000)",
    "DCT_ROPE_SCALING": "rotation of the full-attention layers: '' (plain) | yarn",
    "DCT_ROPE_FACTOR": "yarn: the stretch of the trained length (default 1)",
    "DCT_ROPE_ORIGINAL_LEN": "yarn: the trained length the ramp is measured in",
    "DCT_ROPE_BETA_FAST": "yarn: turns at which a dim keeps its frequency (default 32)",
    "DCT_ROPE_BETA_SLOW": "yarn: turns at which a dim is fully interpolated (default 1)",
    "DCT_ROPE_ATTENTION_FACTOR": "yarn: factor on cos and sin (0 = 0.1 ln(factor) + 1)",
    "DCT_ATTN_KINDS": "per kind of attention layer: kind:n_heads=..,rope_theta=..,rotary_fraction=..[;kind:...]",
    "DCT_ATTN_GATE": "sigmoid gate on each attention head's output: '' | per_head",
    "DCT_NORM": "block normalisation: layernorm | rmsnorm",
    "DCT_NORM_EPS": "normalisation epsilon (default 1e-6)",
    "DCT_MLP": "dense MLP: gelu | swiglu (gated, three matrices)",
    "DCT_USE_BIAS": "biases on the block's projections (default 1)",
    "DCT_QK_NORM": "RMS norm of q and k per head before the rotation",
    "DCT_LAYER_TYPES": "hybrid family: operator per layer, comma list of full_attention | latent_attention | conv",
    "DCT_NUM_DENSE_LAYERS": "hybrid family: leading layers with the dense MLP",
    "DCT_CONV_KERNEL": "gated short convolution taps (default 3)",
    "DCT_MOE_D_FF": "hybrid family: routed experts' width (0 = d_ff)",
    "DCT_EXPERTS_HELD": "experts this job holds of each layer (0 = all)",
    "DCT_FIRST_EXPERT": "first expert of the held share",
    "DCT_ROUTED_SCALING": "hybrid family: factor on the routed experts' weights (default 1)",
    "DCT_ROUTER_GATE_EPS": "hybrid family: term beside the chosen scores' sum (default 1e-6)",
    "DCT_MOE_SHARED_D_FF": "hybrid family: width of the shared expert every token passes (0 = none)",
    "DCT_BIAS_UPDATE_SPEED": "hybrid family: step of the selection bias's balancing update (0 = off)",
    "DCT_ROUTER_SCORING": "hybrid family: router scores, sigmoid (+ selection bias) | softmax (+ balance loss)",
    "DCT_KV_LORA_RANK": "latent attention: width of the compressed key/value latent",
    "DCT_QK_NOPE_HEAD_DIM": "latent attention: a head's query/key width without position",
    "DCT_QK_ROPE_HEAD_DIM": "latent attention: the rotated query/key width (one key shared by all heads)",
    "DCT_V_HEAD_DIM": "latent attention: a head's value width",
    # --- optimization loop -----------------------------------------
    "DCT_EPOCHS": "epoch budget per cycle (reference 10)",
    "DCT_BATCH_SIZE": "per-device batch size (reference 4 per rank)",
    "DCT_LR": "learning rate (reference 0.01)",
    "DCT_OPTIMIZER": "adam | adamw | sgd | adafactor | lion",
    "DCT_MOMENTUM": "sgd/adafactor momentum",
    "DCT_LR_SCHEDULE": "constant | cosine",
    "DCT_WARMUP_STEPS": "linear LR warmup steps",
    "DCT_DECAY_STEPS": "cosine decay horizon (0 = auto full trajectory)",
    "DCT_END_LR_FRACTION": "cosine floor as a fraction of peak LR",
    "DCT_WEIGHT_DECAY": "decoupled weight decay (>0 makes Adam AdamW)",
    "DCT_GRAD_CLIP_NORM": "global-norm gradient clipping (0 = off)",
    "DCT_SEED": "data split + init RNG seed (reference 42)",
    "DCT_LOG_EVERY_N_STEPS": "per-step train_loss logging cadence",
    "DCT_RESUME": "1 = extend the optimizer trajectory from train_state",
    "DCT_BF16_COMPUTE": "bfloat16 MXU compute (params stay f32)",
    "DCT_USE_SCAN": "lax.scan the epoch into one dispatch",
    "DCT_SHARD_OPT_STATE": "ZeRO-1 weight-update sharding over data axis",
    "DCT_SHARD_PARAMS": "FSDP/ZeRO-3 param + moment sharding",
    "DCT_SHARD_RULES": "partition-rule overrides: pattern=axes[;...] (docs/PARALLELISM.md)",
    "DCT_DTYPE_RULES": "mixed-precision compute rules: pattern=dtype[;...] (f32 masters; docs/PARALLELISM.md)",
    "DCT_GRAD_ACCUM_STEPS": "microbatches summed per optimizer update",
    "DCT_EARLY_STOP_PATIENCE": "epochs without val_loss improvement (0 = off)",
    "DCT_EARLY_STOP_MIN_DELTA": "improvement threshold for early stop",
    "DCT_PREFETCH_SPANS": "1 = pipelined span consume; 0 = strict serial",
    # --- mesh / distributed topology -------------------------------
    "DCT_MESH_DATA": "mesh data axis size (-1 = remaining devices)",
    "DCT_MESH_MODEL": "mesh tensor-parallel axis size",
    "DCT_MESH_SEQ": "mesh sequence-parallel axis size",
    "DCT_MESH_PIPE": "mesh pipeline axis size",
    "DCT_NUM_PROCESSES": "jax.distributed world size (WORLD_SIZE compat)",
    "DCT_PROCESS_ID": "jax.distributed process index (NODE_RANK compat)",
    "DCT_COORDINATOR_ADDRESS": "host:port rendezvous (MASTER_ADDR compat)",
    "DCT_WORLD_SIZE": "supervise CLI: ranks per supervised world",
    "DCT_ICI_MESH": "ICI-aware torus device layout on real TPU meshes",
    "DCT_SP_ENGINE": "sequence-parallel engine: ring | a2a (Ulysses)",
    "DCT_RING_STRIPED": "zigzag layout for the causal ring: auto|on|off",
    # --- attention kernels -----------------------------------------
    "DCT_FLASH": "Pallas flash attention: auto | on | off | interpret",
    "DCT_FLASH_BWD": "flash backward: kernel | remat escape hatch",
    # --- launcher / orchestration plumbing -------------------------
    "DCT_TRAIN_HOSTS": "comma-separated trainer hosts the DAG launches onto",
    "DCT_EXEC_TEMPLATE": "remote-exec template ({host}, {cmd})",
    "DCT_TRAIN_COMMAND": "override the DAG's per-host training command",
    "DCT_REPO_ROOT": "repo root for DAG task processes",
    "DCT_DEPLOY_TARGET": "deploy DAGs: azure | local endpoint surface",
    "DCT_KEEP_CHECKPOINTS": "pipeline DAG cleanup: newest ckpts to keep",
    "DCT_ETL_ENGINE": "ETL engine: spark | pandas fallback",
    "DCT_ETL_INCREMENTAL": "digest no-op + append-only delta ETL (default on)",
    "DCT_ETL_REBUILD_TOL": "basis-stats shift forcing a full ETL rebuild",
    # --- always-on loop (dct_tpu.continuous; docs/CONTINUOUS.md) ----
    "DCT_LOOP_POLL_S": "ingest watcher poll cadence over the raw CSV (s)",
    "DCT_LOOP_EVAL_POLL_S": "evaluator poll cadence over the best ckpt (s)",
    "DCT_LOOP_EPOCHS_PER_ROUND": "epochs per continuous training round",
    "DCT_LOOP_TRAIN_MODE": "round runner: supervised (PR 3) | inline",
    "DCT_LOOP_SOAK_S": "mid-run rollout soak per stage (s)",
    "DCT_LOOP_ENDPOINT": "local endpoint the loop promotes into",
    "DCT_LOOP_PACKAGES_DIR": "challenger package root for mid-run promotions",
    "DCT_LOOP_MAX_ROUNDS": "loop stop budget: training rounds (0 = unbounded)",
    "DCT_LOOP_MAX_WALL_S": "loop stop budget: wall seconds (0 = unbounded)",
    "DCT_LOOP_MAX_PROMOTIONS": "loop stop budget: promotions (0 = unbounded)",
    "DCT_LOOP_DAG_HOURS": "always-on DAG: one task occupancy before re-trigger",
    "DCT_LOOP_SMOKE_WAIT_S": "continuous-loop CI smoke: wall budget (s)",
    # --- streaming ingest data plane (dct_tpu.stream; docs/STREAMING.md) -
    "DCT_INGEST_MODE": "loop ingest source: poll (CSV stat-poll) | stream (event log)",
    "DCT_STREAM_DIR": "partitioned event-log root (per tenant)",
    "DCT_STREAM_TOPIC": "topic name under the stream root",
    "DCT_STREAM_PARTITIONS": "partitions per topic (single-writer each)",
    "DCT_STREAM_SEGMENT_RECORDS": "records per segment before the atomic seal",
    "DCT_STREAM_SEGMENT_BYTES": "bytes per segment before the atomic seal",
    "DCT_STREAM_GROUP": "consumer group the stream ETL commits under",
    "DCT_STREAM_BACKPRESSURE": "over-budget producer action: block | shed | off",
    "DCT_STREAM_LAG_BUDGET": "bounded-lag budget (records) before backpressure",
    "DCT_STREAM_BLOCK_TIMEOUT_S": "blocked-producer wait before shedding (s)",
    "DCT_STREAM_MAX_BATCH": "records per stream-ETL pass (one parquet part)",
    "DCT_STREAM_POLL_S": "stream-watcher poll cadence (s; idle poll is two sidecar reads)",
    "DCT_STREAM_SMOKE_WAIT_S": "streaming CI smoke: wall budget (s)",
    # --- multi-tenant scheduler (dct_tpu.scheduler; docs/SCHEDULER.md) -
    "DCT_TENANTS": "tenant roster: inline JSON or tenants.json path",
    "DCT_SCHED_ROOT": "per-tenant run-dir root (+ shared cache home)",
    "DCT_SCHED_CONCURRENT": "round leases running at once (1 = one shared mesh)",
    "DCT_SCHED_POLL_S": "scheduler monitor cadence (budgets, preemption)",
    "DCT_SCHED_PREEMPT_WAIT_S": "starved higher-class wait before graceful preempt (0 = never)",
    "DCT_SCHED_SHARED_CACHE": "pin one compile/AOT store for same-family tenants",
    "DCT_SCHED_MAX_WALL_S": "scheduler stop budget: wall seconds (0 = unbounded)",
    "DCT_SCHED_MAX_ROUNDS": "scheduler stop budget: total leases (0 = unbounded)",
    "DCT_SCHED_DAG_HOURS": "multi-tenant DAG: one task occupancy before re-trigger",
    "DCT_SCHED_SMOKE_WAIT_S": "scheduler CI smoke: wall budget (s)",
    # --- MPMD pipeline trainer (dct_tpu.parallel.mpmd; docs/PARALLELISM.md §MPMD) -
    "DCT_MPMD_STAGES": "stage map: stage count or per-stage device counts (loud parse)",
    "DCT_MPMD_MICROBATCHES": "microbatches per optimizer step (0 = 2x stages)",
    "DCT_MPMD_SCHEDULE": "per-stage op order: 1f1b | gpipe",
    "DCT_MPMD_TRANSFER_TIMEOUT_S": "inter-stage transfer wait before loud failure (s)",
    "DCT_MPMD_PORT_BASE": "multi-process transfer plane base port (stage k = base+k)",
    "DCT_MPMD_STAGE_ID": "worker plumbing: this process's stage index (NODE_RANK fallback)",
    "DCT_MPMD_SMOKE_WAIT_S": "MPMD CI smoke: wall budget (s)",
    "DCT_SPARK_MASTER_HOST": "Spark master hostname for the ETL DAG",
    "DCT_SOAK_SECONDS": "auto-deploy DAG: canary soak dwell",
    "DCT_ENDPOINT_NAME": "serve the named LOCAL rollout endpoint",
    "DCT_LOCAL_ENDPOINT_STATE": "local endpoint traffic-state JSON path",
    # --- observability ---------------------------------------------
    "DCT_OBSERVABILITY": "master switch for the operator plane",
    "DCT_EVENTS_DIR": "structured event log (+ spans, prom dump) dir",
    "DCT_RUN_ID": "launcher-minted run-correlation ID (exported to ranks)",
    "DCT_SPAN_ID": "parent span ID exported to child processes",
    "DCT_HEARTBEAT_DIR": "per-rank heartbeat files",
    "DCT_HEARTBEAT_INTERVAL": "same-phase heartbeat throttle (s)",
    "DCT_HEARTBEAT_STALL_SECONDS": "heartbeat age that marks a rank stalled",
    "DCT_METRICS_PROM": "end-of-run Prometheus textfile dump path",
    "DCT_SPANS_DIR": "distributed-tracing span files dir",
    "DCT_LINEAGE": "content-addressed provenance ledger (on by default)",
    "DCT_LINEAGE_DIR": "lineage ledger dir (default: the events dir)",
    "DCT_SERVE_TRACE": "opt-in per-request serving.score spans",
    "DCT_SERVE_LOG": "per-request serving access log",
    "DCT_HALT_ON_NAN": "halt training on non-finite loss",
    "DCT_HALT_ON_SPIKE": "halt on loss/grad-norm z-score spike",
    "DCT_SPIKE_ZSCORE": "spike detector z threshold",
    "DCT_SPIKE_WINDOW": "spike detector rolling window",
    "DCT_TELEMETRY_FLUSH_S": "event/span write-batch window (0 = through)",
    "DCT_TELEMETRY_FLUSH_RECORDS": "record cap forcing an early flush",
    "DCT_METRICS_DIR": "metrics-plane snapshot dir ('' = plane off)",
    "DCT_METRICS_PUBLISH_S": "min seconds between snapshot publishes",
    "DCT_METRICS_STALE_S": "live snapshot age that stops counting (s)",
    "DCT_SLO_SPEC": "SLO specs over the aggregated series (slo.py grammar)",
    "DCT_SLO_FAST_WINDOW_S": "burn-rate fast window (s)",
    "DCT_SLO_SLOW_WINDOW_S": "burn-rate slow window (s)",
    "DCT_SLO_BURN_THRESHOLD": "alert when BOTH windows burn above this",
    "DCT_PROFILE": "jax.profiler one-epoch trace window",
    "DCT_TRACE_DIR": "profiler trace output dir",
    "DCT_PROFILE_EPOCH": "which epoch to trace (0-based)",
    "DCT_PROFILE_TRIGGER": "flight-recorder trigger file ('' = off)",
    "DCT_PROF_CAPTURE_S": "flight-recorder default capture length (s)",
    "DCT_PROF_SIGUSR2": "arm SIGUSR2 as an on-demand capture trigger",
    "DCT_ROOFLINE": "XLA cost-model roofline accounting on/off",
    "DCT_HBM_GBPS": "per-chip HBM bandwidth override for roofline math",
    "DCT_TS_DIR": "telemetry history store dir ('' = plane off)",
    "DCT_TS_FAMILIES": "fnmatch patterns of recorded dct_* families",
    "DCT_TS_SEG_POINTS": "points per raw segment before sealing",
    "DCT_TS_SEG_S": "max raw segment age before sealing (s)",
    "DCT_TS_FLUSH_S": "active-segment republish cadence (s)",
    "DCT_TS_RETENTION_S": "segment age deleted at compaction (s)",
    "DCT_TS_DOWNSAMPLE_S": "raw-segment age folded to the ds tier (s)",
    "DCT_TS_DS_RES_S": "downsampled-tier bin width (s)",
    "DCT_ANOMALY": "EWMA/z-score anomaly detection over the history",
    "DCT_ANOMALY_Z": "anomaly z-score trigger threshold",
    "DCT_ANOMALY_ALPHA": "EWMA baseline smoothing factor",
    "DCT_ANOMALY_MIN_POINTS": "baseline samples before detection arms",
    "DCT_ANOMALY_WINDOW_S": "history window per detector read (s)",
    "DCT_ANOMALY_POLL_S": "detector poll cadence (s)",
    "DCT_INCIDENT": "auto-assembled incident bundles on anomaly/SLO",
    "DCT_INCIDENT_DIR": "bundle root ('' = sibling of DCT_TS_DIR)",
    "DCT_INCIDENT_WINDOW_S": "history/event window per bundle (s)",
    "DCT_INCIDENT_COOLDOWN_S": "min seconds between same-signal bundles",
    "DCT_INCIDENT_PROFILE": "fire the flight recorder into each bundle",
    "DCT_INCIDENT_PROFILE_S": "incident profile capture length (s)",
    # --- resilience ------------------------------------------------
    "DCT_MAX_RESTARTS": "supervised relaunch budget",
    "DCT_RESTART_BACKOFF_S": "first relaunch backoff",
    "DCT_RESTART_BACKOFF_FACTOR": "backoff growth per restart",
    "DCT_RESTART_JITTER": "relative backoff jitter",
    "DCT_PREEMPT_GRACE_S": "SIGTERM -> SIGKILL escalation window",
    "DCT_GRACEFUL_PREEMPTION": "SIGTERM: finish step, save, exit 75",
    "DCT_FAULT_SPEC": "deterministic chaos plan (faults.py grammar)",
    "DCT_FAULT_SLEEP_S": "slow_save / slow_epoch fault duration",
    "DCT_RETRY_MAX_ATTEMPTS": "tracking/deploy transient-network retries",
    "DCT_RETRY_BACKOFF_S": "network retry backoff",
    "DCT_STARTUP_RECOVERY_DEBT_S": "supervisor-set lost-wall-clock badput",
    "DCT_LAUNCH_TIMEOUT_S": "supervise CLI: per-attempt launch timeout",
    # --- evaluation / promotion gates / drift ----------------------
    "DCT_GATE": "consult the promotion gate between rollout stages",
    "DCT_GATE_MIN_IMPROVEMENT": "mean loss delta counted as improvement",
    "DCT_GATE_MAX_REGRESSION": "mean regression tolerated before blocking",
    "DCT_GATE_CONFIDENCE": "one-sided bootstrap confidence",
    "DCT_GATE_BOOTSTRAP": "paired-bootstrap resamples",
    "DCT_GATE_SEED": "bootstrap RNG seed (decisions deterministic)",
    "DCT_GATE_MAX_SLICE_REGRESSION": "worst tolerated per-slice regression",
    "DCT_GATE_REQUIRE_IMPROVEMENT": "strict mode: promote only on proof",
    "DCT_GATE_EVAL_BATCH": "harness examples per forward pass",
    "DCT_GATE_ENGINE": "eval engine: numpy serving twin | jax",
    "DCT_GATE_FAIL_OPEN": "missing prerequisites promote (1) or hold (0)",
    "DCT_GATE_LEDGER": "gate-decision ledger path for /metrics",
    "DCT_DRIFT_PSI": "per-feature PSI threshold vs stamped snapshot",
    "DCT_DRIFT_KS": "per-feature two-sample KS D threshold",
    "DCT_DRIFT_BINS": "quantile bins in the stamped snapshot",
    "DCT_DRIFT_MAX_DISAGREEMENT": "shadow prediction-disagreement hold rate",
    "DCT_DRIFT_THRESHOLD": "ETL-side daily-stats drift gate (older knob)",
    "DCT_MIRROR_CAPTURE": "mirrored shadow-response capture JSONL path",
    # --- tracking --------------------------------------------------
    "DCT_EXPERIMENT": "tracking experiment name",
    "DCT_TRACKING_DIR": "LocalTracking file-store root",
    # --- batch inference / serving ---------------------------------
    "DCT_CKPT": "checkpoint to score (default: newest best)",
    "DCT_PREDICTIONS": "batch-inference output parquet",
    "DCT_PREDICT_CHUNK": "rows/windows scored per forward pass",
    "DCT_PREDICT_ENGINE": "predict engine: numpy | jax",
    "DCT_PREDICT_DTYPE": "jax predict compute dtype (e.g. bfloat16)",
    "DCT_SERVE_HOST": "HTTP serving bind host",
    "DCT_SERVE_PORT": "HTTP serving port",
    "DCT_SERVE_MAX_BATCH": "micro-batcher flush cap in rows",
    "DCT_SERVE_BATCH_WINDOW_MS": "co-arrival deadline window (0 = opportunistic)",
    "DCT_SERVE_WORKERS": "scoring worker threads (0 = inline)",
    "DCT_SERVE_PROCS": "SO_REUSEPORT serving processes (1 = no fork)",
    "DCT_SERVE_ENGINE": "batched scorer: numpy (bit-identical) | jax (jitted)",
    "DCT_SERVE_FAST_PARSE": "zero-copy JSON envelope parsing on/off",
    "DCT_QUANT_DTYPE": "package quantization default: int8 | bf16 (docs/SERVING.md)",
    "DCT_QUANT_PROB_BOUND": "quantized-vs-f32 max-abs-prob parity bound",
    "DCT_SERVE_LOADGEN_QPS": "loadgen open-loop target qps (0 = closed loop)",
    "DCT_SERVE_LOADGEN_DURATION_S": "loadgen per-level wall budget (s)",
    "DCT_SERVE_LOADGEN_REQUESTS": "loadgen requests per concurrency level",
    "DCT_SERVE_LOADGEN_CONCURRENCY": "loadgen sweep levels (comma-separated)",
    # Elastic serving controls (docs/SERVING.md §elasticity).
    "DCT_SERVE_ADMIT": "priority admission control on/off",
    "DCT_SERVE_PRIORITY_HEADER": "request header carrying high|normal|low",
    "DCT_SERVE_ADMIT_MAX_QUEUE": "admission queue budget in rows",
    "DCT_SERVE_ADMIT_WAIT_MS": "admission queue-wait budget (ms; 0 = off)",
    "DCT_SERVE_RETRY_AFTER_S": "base Retry-After for shed 429s",
    "DCT_SERVE_AUTOSCALE": "closed-loop capacity autoscaler on/off",
    "DCT_SERVE_SCALE_MIN": "autoscaler floor (procs or workers)",
    "DCT_SERVE_SCALE_MAX": "autoscaler ceiling (procs or workers)",
    "DCT_SERVE_SCALE_UP_Q": "queue rows that vote scale-up",
    "DCT_SERVE_SCALE_DOWN_Q": "queue rows that vote scale-down",
    "DCT_SERVE_SCALE_POLL_S": "autoscaler poll interval (s)",
    "DCT_SERVE_SCALE_HYSTERESIS": "consecutive agreeing polls per scale step",
    "DCT_SERVE_SCALE_COOLDOWN_S": "min seconds between scale events",
    "DCT_SERVE_MAX_RESTARTS": "pool respawn budget before circuit-break",
    "DCT_SERVE_PROC_INDEX": "pool-exported child index (set by ServerPool)",
    # --- peaks / caches / native -----------------------------------
    "DCT_PEAK_TFLOPS": "per-chip peak TFLOPs override for MFU math",
    # Compile cache + AOT executables (dct_tpu.compilecache;
    # docs/OBSERVABILITY.md §compile): sub-second relaunch/spin-up. The
    # directory is JAX's own JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache — there is no DCT_ knob for it.
    "DCT_COMPILE_CACHE": (
        "compile cache mode: off | auto (JAX_COMPILATION_CACHE_DIR arms) | on"
    ),
    "DCT_COMPILE_CACHE_AOT": "AOT executable store on/off (default on)",
    "DCT_COMPILE_CACHE_AOT_DIR": "AOT store root override (default <models>/aot)",
    "DCT_COMPILE_CACHE_MIN_COMPILE_S": "min compile seconds worth caching (0 = all)",
    "DCT_COMPILE_CACHE_WARM_SIZES": "packaging scorer pre-compile batch sizes",
    "DCT_NATIVE": "enable the native (C++) extension build",
    "DCT_CXX": "C++ compiler for the native build",
}

"""Model registry.

The reference hardcodes its single model class inline in the training script
(jobs/train_lightning_ddp.py:51, re-declared again inside the generated
score.py at dags/azure_manual_deploy.py:59-77). Here models are registered by
name so the trainer, the serving package, and the DAGs all resolve the same
definition from config — no copy-pasted architectures.
"""

from __future__ import annotations

from typing import Callable

from flax import linen as nn

from dct_tpu.config import ModelConfig

MODEL_REGISTRY: dict[str, Callable[..., nn.Module]] = {}
# Models that consume [B, S, F] windows instead of [B, F] rows; the Trainer
# switches the data path (make_windows) and init shape on this trait.
SEQUENCE_MODELS: set[str] = set()
# Causal per-position families: windows carry [N, S] next-step labels and
# the model emits [B, S, classes] logits.
CAUSAL_MODELS: set[str] = set()


def register_model(name: str, *, sequence: bool = False, causal: bool = False):
    def deco(builder: Callable[..., nn.Module]):
        MODEL_REGISTRY[name] = builder
        if sequence:
            SEQUENCE_MODELS.add(name)
        if causal:
            CAUSAL_MODELS.add(name)
        return builder

    return deco


def is_sequence_model(name: str) -> bool:
    return name in SEQUENCE_MODELS


def is_causal_model(name: str) -> bool:
    return name in CAUSAL_MODELS


def get_model(cfg: ModelConfig, *, input_dim: int | None = None, **kwargs) -> nn.Module:
    if cfg.name not in MODEL_REGISTRY:
        raise KeyError(
            f"Unknown model '{cfg.name}'. Registered: {sorted(MODEL_REGISTRY)}"
        )
    if cfg.pos_embed not in ("sincos", "rope"):
        # Loud, like the other attention knobs: a typo ("Rope", "rotary")
        # would otherwise silently train with sincos while the operator
        # believes RoPE is on — and serving would mirror the mistake.
        raise ValueError(
            f"pos_embed={cfg.pos_embed!r} must be 'sincos' or 'rope'"
        )
    dim = cfg.input_dim if input_dim is None else input_dim
    if dim is None:
        raise ValueError("input_dim must be provided (inferred from data)")
    return MODEL_REGISTRY[cfg.name](cfg, input_dim=dim, **kwargs)


@register_model("weather_mlp")
def _build_mlp(cfg: ModelConfig, *, input_dim: int, compute_dtype=None):
    import jax.numpy as jnp

    from dct_tpu.models.mlp import WeatherMLP

    return WeatherMLP(
        input_dim=input_dim,
        hidden_dim=cfg.hidden_dim,
        num_classes=cfg.num_classes,
        dropout=cfg.dropout,
        compute_dtype=compute_dtype or jnp.float32,
    )


@register_model("weather_gru", sequence=True)
def _build_gru(
    cfg: ModelConfig, *, input_dim: int, compute_dtype=None, attn_fn=None,
    mesh=None,
):
    # attn_fn/mesh are part of the sequence-model builder interface (the
    # Trainer supplies a mesh-aware attention kernel and the device mesh);
    # recurrence has no use for either.
    del attn_fn, mesh
    import jax.numpy as jnp

    from dct_tpu.models.gru import WeatherGRU

    return WeatherGRU(
        input_dim=input_dim,
        hidden_dim=cfg.hidden_dim,
        n_layers=cfg.n_layers,
        num_classes=cfg.num_classes,
        dropout=cfg.dropout,
        compute_dtype=compute_dtype or jnp.float32,
    )


@register_model("weather_moe", sequence=True)
def _build_moe(
    cfg: ModelConfig, *, input_dim: int, compute_dtype=None, attn_fn=None,
    mesh=None,
):
    import jax.numpy as jnp

    from dct_tpu.models.moe import WeatherMoE

    return WeatherMoE(
        input_dim=input_dim,
        seq_len=cfg.seq_len,
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_layers=cfg.n_layers,
        d_ff=cfg.d_ff,
        n_experts=cfg.n_experts,
        capacity_factor=cfg.capacity_factor,
        router_aux_weight=cfg.router_aux_weight,
        num_classes=cfg.num_classes,
        dropout=cfg.dropout,
        attn_fn=attn_fn,
        compute_dtype=compute_dtype or jnp.float32,
        dispatch=cfg.moe_dispatch,
        mesh=mesh,
        top_k=cfg.router_top_k,
        auto_threshold=cfg.moe_auto_threshold,
        n_kv_heads=cfg.n_kv_heads if cfg.n_kv_heads > 0 else None,
        pos_embed=cfg.pos_embed,
    )


def _block_form(cfg: ModelConfig) -> dict:
    """The block's form as WeatherTransformer's keyword arguments."""
    return dict(
        norm=cfg.norm, norm_eps=cfg.norm_eps, mlp=cfg.mlp,
        use_bias=cfg.use_bias, qk_norm=cfg.qk_norm,
        rope_theta=cfg.rope_theta, head_dim=cfg.head_dim or None,
        attn_kinds=_attn_kinds(cfg.attn_kinds), attn_gate=cfg.attn_gate,
    )


_ATTN_KIND_KEYS = {"n_heads": int, "rope_theta": float,
                   "rotary_fraction": float}


def _attn_kinds(text: str) -> tuple:
    """``attn_kinds``' grammar (config.py) as pairs of kind and
    :class:`AttnKind`, in the order given; "" = ()."""
    from dct_tpu.models.transformer import AttnKind

    kinds = []
    for clause in filter(None, (c.strip() for c in text.split(";"))):
        kind, _, fields = clause.partition(":")
        kind = kind.strip()
        if kind not in ("full_attention", "sliding_attention"):
            raise ValueError(
                f"attn_kinds clause {clause!r}: the kind must be "
                "'full_attention' or 'sliding_attention'")
        values = {}
        for item in filter(None, (f.strip() for f in fields.split(","))):
            key, _, value = item.partition("=")
            key = key.strip()
            if key not in _ATTN_KIND_KEYS:
                raise ValueError(
                    f"attn_kinds clause {clause!r}: unknown key {key!r} "
                    f"(known: {', '.join(_ATTN_KIND_KEYS)})")
            values[key] = _ATTN_KIND_KEYS[key](value)
        kinds.append((kind, AttnKind(**values)))
    return tuple(kinds)


def _rope_scaling(cfg: ModelConfig) -> tuple:
    """``yarn_tables``' keyword arguments as a tuple of pairs, or ()."""
    if cfg.rope_scaling == "":
        return ()
    if cfg.rope_scaling != "yarn":
        raise ValueError(
            f"rope_scaling={cfg.rope_scaling!r} must be '' or 'yarn'")
    return tuple(sorted(dict(
        factor=cfg.rope_factor, original_len=cfg.rope_original_len,
        beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
        attention_factor=cfg.rope_attention_factor,
    ).items()))


def _causal_transformer(cfg, *, input_dim, compute_dtype, mesh,
                        layer_types=(), **extra):
    """WeatherTransformer as the causal per-position families build it:
    their own causal attention from the mesh (the Trainer-supplied attn_fn
    is non-causal), the per-position head, the block's form from ``cfg``.

    ``attn_window`` binds every attention layer, unless ``layer_types``
    names "sliding_attention" layers: then those attend under it and the
    "full_attention" layers beside them are full causal."""
    import jax.numpy as jnp

    from dct_tpu.models.transformer import WeatherTransformer
    from dct_tpu.ops.attention import make_attention_fn

    window = cfg.attn_window if cfg.attn_window > 0 else None
    sliding = "sliding_attention" in layer_types
    if sliding and window is None:
        raise ValueError(
            "'sliding_attention' layers need attn_window > 0")
    return WeatherTransformer(
        input_dim=input_dim,
        seq_len=cfg.seq_len,
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_layers=cfg.n_layers,
        d_ff=cfg.d_ff,
        num_classes=cfg.num_classes,
        dropout=cfg.dropout,
        attn_fn=make_attention_fn(
            mesh, causal=True, window=None if sliding else window),
        sliding_attn_fn=make_attention_fn(
            mesh, causal=True, window=window) if sliding else None,
        rope_scaling=_rope_scaling(cfg),
        layer_types=layer_types,
        per_position=True,
        horizon=cfg.horizon,
        remat=cfg.remat,
        compute_dtype=compute_dtype or jnp.float32,
        n_kv_heads=cfg.n_kv_heads if cfg.n_kv_heads > 0 else None,
        pos_embed=cfg.pos_embed,
        **_block_form(cfg),
        **extra,
    )


@register_model("weather_transformer_causal", sequence=True, causal=True)
def _build_transformer_causal(
    cfg: ModelConfig, *, input_dim: int, compute_dtype=None, attn_fn=None,
    mesh=None,
):
    """Decoder-style causal forecaster: per-position next-step supervision
    through CAUSAL attention — the product path for the causal flash
    kernel and the causal ring (the non-causal families never exercise
    them)."""
    del attn_fn
    return _causal_transformer(
        cfg, input_dim=input_dim, compute_dtype=compute_dtype, mesh=mesh
    )


@register_model("weather_hybrid_moe_causal", sequence=True, causal=True)
def _build_hybrid_moe_causal(
    cfg: ModelConfig, *, input_dim: int, compute_dtype=None, attn_fn=None,
    mesh=None,
):
    """The causal per-position family with a per-layer operator
    (``layer_types``: attention, full or under the window, latent attention
    or the gated short convolution) and routed experts after
    ``num_dense_layers`` dense layers: the block of
    ``weather_transformer_causal`` by its fields, one chip's share of the
    experts (``experts_held``, ``first_expert``) computed without an
    exchange. A softmax router carries the balance loss at
    ``router_aux_weight``; a sigmoid router has none."""
    del attn_fn
    moe = dict(
        d_ff=cfg.moe_d_ff or cfg.d_ff, n_experts=cfg.n_experts,
        aux_weight=(
            cfg.router_aux_weight if cfg.router_scoring == "softmax"
            else 0.0),
        scoring=cfg.router_scoring,
        dispatch="grouped", top_k=cfg.router_top_k,
        experts_held=cfg.experts_held, first_expert=cfg.first_expert,
        routed_scale=cfg.routed_scaling, gate_eps=cfg.router_gate_eps,
        shared_d_ff=cfg.moe_shared_d_ff,
        bias_update_speed=cfg.bias_update_speed,
    )
    latent = dict(
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_head_dim,
        qk_rope_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
    )
    return _causal_transformer(
        cfg, input_dim=input_dim, compute_dtype=compute_dtype, mesh=mesh,
        layer_types=tuple(
            t.strip() for t in cfg.layer_types.split(",") if t.strip()
        ),
        conv_kernel=cfg.conv_kernel,
        latent=tuple(sorted(latent.items())),
        moe=tuple(sorted(moe.items())),
        num_dense_layers=cfg.num_dense_layers,
    )


@register_model("weather_transformer_pp", sequence=True)
def _build_transformer_pp(
    cfg: ModelConfig, *, input_dim: int, compute_dtype=None, attn_fn=None,
    mesh=None,
):
    # The passed attn_fn may be mesh-bound (ring over ``seq``); stages run
    # inside the pipeline shard_map where nesting it is illegal — the PP
    # family always uses the single-shard dense/blockwise/flash path.
    del attn_fn
    import jax.numpy as jnp

    from dct_tpu.models.transformer import WeatherTransformerPP
    from dct_tpu.ops.attention import make_attention_fn

    return WeatherTransformerPP(
        input_dim=input_dim,
        seq_len=cfg.seq_len,
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_layers=cfg.n_layers,
        d_ff=cfg.d_ff,
        num_classes=cfg.num_classes,
        dropout=cfg.dropout,
        n_stages=cfg.n_stages,
        n_microbatches=cfg.n_microbatches,
        attn_fn=make_attention_fn(None),
        mesh=mesh,
        remat=cfg.remat,
        compute_dtype=compute_dtype or jnp.float32,
        n_kv_heads=cfg.n_kv_heads if cfg.n_kv_heads > 0 else None,
        pos_embed=cfg.pos_embed,
    )


@register_model("weather_transformer", sequence=True)
def _build_transformer(
    cfg: ModelConfig, *, input_dim: int, compute_dtype=None, attn_fn=None,
    mesh=None,
):
    del mesh  # attention distribution arrives pre-bound in attn_fn
    import jax.numpy as jnp

    from dct_tpu.models.transformer import WeatherTransformer

    return WeatherTransformer(
        input_dim=input_dim,
        seq_len=cfg.seq_len,
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_layers=cfg.n_layers,
        d_ff=cfg.d_ff,
        num_classes=cfg.num_classes,
        dropout=cfg.dropout,
        attn_fn=attn_fn,
        remat=cfg.remat,
        compute_dtype=compute_dtype or jnp.float32,
        n_kv_heads=cfg.n_kv_heads if cfg.n_kv_heads > 0 else None,
        pos_embed=cfg.pos_embed,
    )

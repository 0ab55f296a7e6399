"""Transformer family: long-context sequence models over the weather stream.

The reference scales only the batch axis of a tabular MLP (SURVEY §2.3); this
family adds the capability its design lacks — sequence models whose context
is sharded over the mesh — built TPU-first:

- attention is pluggable (:mod:`dct_tpu.ops.attention`): dense for short
  contexts, blockwise for long single-chip contexts, ring attention over the
  ``seq`` mesh axis for contexts larger than one chip;
- tensor parallelism is expressed by PARAM NAMES: projection modules are
  named ``qkv_proj`` / ``o_proj`` / ``ffn_in`` / ``ffn_out`` and
  :mod:`dct_tpu.parallel.sharding_rules` maps those names to
  ``PartitionSpec``s over the ``model`` axis (megatron-style column/row
  split — one all-reduce per block, inserted by XLA, riding ICI);
- everything is a pure function of (params, x, rng): same train step, same
  Trainer, same checkpoint/tracking path as the flagship MLP.

``WeatherTransformer`` is the concrete member: a pre-LN encoder over a
window of ``seq_len`` past weather rows, mean-pooled into the same
2-class rain head as the reference's classifier (same loss, same metrics).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from dct_tpu.models.mlp import TorchStyleDense, torch_linear_init


def sincos_positions(seq_len: int, d_model: int) -> np.ndarray:
    """Fixed sinusoidal position table [S, D] (no param => nothing to shard)."""
    pos = np.arange(seq_len)[:, None].astype(np.float32)
    i = np.arange(d_model // 2)[None, :].astype(np.float32)
    ang = pos / np.power(10000.0, 2.0 * i / d_model)
    out = np.zeros((seq_len, d_model), np.float32)
    out[:, 0::2] = np.sin(ang)
    out[:, 1::2] = np.cos(ang)
    return out


def rope_tables(
    seq_len: int, head_dim: int, base: float = 10000.0
) -> tuple[np.ndarray, np.ndarray]:
    """Rotary-embedding cos/sin tables [S, Dh/2] (RoFormer/Llama-style,
    rotate-half pairing) at frequency base ``base``. Static numpy — nothing
    to shard, and the tables bake into the compiled program as constants."""
    half = head_dim // 2
    inv = 1.0 / np.power(
        np.float32(base), np.arange(half, dtype=np.float32) / half
    )
    ang = np.arange(seq_len, dtype=np.float32)[:, None] * inv[None, :]
    return np.cos(ang), np.sin(ang)


def make_norm(kind: str, eps: float, dtype, name: str) -> nn.Module:
    """The block's normalisation by name: ``layernorm`` (scale and bias)
    or ``rmsnorm`` (x / sqrt(mean(x^2) + eps) * scale, no bias)."""
    if kind == "layernorm":
        return nn.LayerNorm(epsilon=eps, dtype=dtype, name=name)
    if kind == "rmsnorm":
        return nn.RMSNorm(epsilon=eps, dtype=dtype, name=name)
    raise ValueError(f"norm={kind!r} must be 'layernorm' or 'rmsnorm'")


def apply_rope(x, cos, sin):
    """Rotate q or k [..., T, Dh] by per-position angles ([T, Dh/2] cos/sin,
    broadcast over batch/head axes). Positions are GLOBAL sequence
    positions, so the rotation composes unchanged with both SP engines
    (it runs on the full array before the seq-sharded attention op) and
    with GQA (k rotates at its grouped head count)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = jnp.asarray(cos, x.dtype)
    sin = jnp.asarray(sin, x.dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )


class MultiHeadAttention(nn.Module):
    """MHA with injected attention kernel. Projections are single fused
    qkv (column-parallel over ``model``) + output (row-parallel).

    ``n_kv_heads`` < ``n_heads`` selects grouped-query attention (GQA):
    K/V carry fewer heads, each serving ``n_heads/n_kv_heads`` query
    heads — the standard KV-bandwidth lever (smaller qkv projection,
    KV HBM reads divided by the group size in the Pallas kernel, smaller
    KV payloads on the SP engines' collectives). The fused output dim is
    laid out GROUP-major ``(G, Hg + 2, Dh)`` (G = kv heads, Hg = q heads
    per group): a ``model``-axis shard of the kernel's output dim is
    GROUP-aligned, so each tensor-parallel shard owns whole groups —
    q heads together with their kv head, no resharding before attention.
    With ``n_kv_heads == n_heads`` this degenerates to exactly the
    classic ``(H, 3, Dh)`` layout, so MHA checkpoints are unchanged."""

    d_model: int
    n_heads: int
    attn_fn: object  # (q [B,H,T,D], k/v [B,G,T,D]) -> [B,H,T,D]
    dtype: jnp.dtype = jnp.float32
    n_kv_heads: int | None = None
    rope: bool = False
    rope_theta: float = 10000.0
    use_bias: bool = True
    # RMS norm of q and k over each head's dims (own weight each),
    # before the rotation.
    qk_norm: bool = False
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        head_dim = self.d_model // self.n_heads
        g = self.n_kv_heads or self.n_heads
        if self.n_heads % g:
            raise ValueError(
                f"n_kv_heads ({g}) must divide n_heads ({self.n_heads})"
            )
        hg = self.n_heads // g
        qkv = TorchStyleDense(
            (self.n_heads + 2 * g) * head_dim, dtype=self.dtype,
            use_bias=self.use_bias, name="qkv_proj",
        )(x)
        qkv = qkv.reshape(b, t, g, hg + 2, head_dim)
        # [B, T, G, Hg+2, Dh]: per group, Hg q heads then one k and one v.
        q = qkv[:, :, :, :hg].reshape(b, t, self.n_heads, head_dim)
        q = jnp.swapaxes(q, 1, 2)  # [B, H, T, Dh]
        k = jnp.swapaxes(qkv[:, :, :, hg], 1, 2)  # [B, G, T, Dh]
        v = jnp.swapaxes(qkv[:, :, :, hg + 1], 1, 2)
        if self.qk_norm:
            q = make_norm("rmsnorm", self.norm_eps, self.dtype, "q_norm")(q)
            k = make_norm("rmsnorm", self.norm_eps, self.dtype, "k_norm")(k)
        if self.rope:
            if head_dim % 2:
                raise ValueError(
                    f"rope needs an even head_dim (got {head_dim})"
                )
            cos, sin = rope_tables(t, head_dim, self.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        o = self.attn_fn(q, k, v)  # [B, H, T, D]
        o = jnp.moveaxis(o, 1, 2).reshape(b, t, self.d_model)
        return TorchStyleDense(
            self.d_model, dtype=self.dtype, use_bias=self.use_bias,
            name="o_proj",
        )(o)


class LatentAttention(nn.Module):
    """Multi-head latent attention: keys and values come out of ONE
    compressed latent a position, and a head's queries and keys are wider
    than its values.

    ``q = x W_q`` gives each head ``[q_nope | q_rope]`` (``qk_nope_dim`` +
    ``qk_rope_dim`` wide; no query compression). ``x W_kva`` gives
    ``[c_kv | k_rope]``: the latent, ``kv_lora_rank`` wide, and ONE rotated
    key part of ``qk_rope_dim`` that every head shares. ``RMSnorm(c_kv)
    W_kvb`` gives each head ``[k_nope | v]`` (``qk_nope_dim`` +
    ``v_head_dim``). The rotation (rotate-half pairing, as
    :func:`apply_rope`) acts on ``q_rope`` and ``k_rope`` only; ``k =
    [k_nope | k_rope]``, and the scores are scaled by one over the square
    root of the whole query width. ``attn_fn`` takes q and k at that width
    and v at its own (``[B, H, T, 192]`` against ``[B, H, T, 128]`` in the
    published layer); the output projection reads ``n_heads x
    v_head_dim``."""

    d_model: int
    n_heads: int
    attn_fn: object
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    dtype: jnp.dtype = jnp.float32
    rope_theta: float = 10000.0
    use_bias: bool = False
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, rank, d_v = self.n_heads, self.kv_lora_rank, self.v_head_dim
        d_nope, d_rope = self.qk_nope_dim, self.qk_rope_dim
        if d_rope % 2:
            raise ValueError(f"rope needs an even width (got {d_rope})")
        dense = functools.partial(
            TorchStyleDense, dtype=self.dtype, use_bias=self.use_bias
        )
        with jax.named_scope("mla.project"):
            q = dense(h * (d_nope + d_rope), name="q_proj")(x)
            q = jnp.swapaxes(q.reshape(b, t, h, d_nope + d_rope), 1, 2)
            latent = dense(rank + d_rope, name="kv_a_proj")(x)
            c_kv = make_norm("rmsnorm", self.norm_eps, self.dtype, "kv_norm")(
                latent[..., :rank]
            )
            kv = dense(h * (d_nope + d_v), name="kv_b_proj")(c_kv)
            kv = jnp.swapaxes(kv.reshape(b, t, h, d_nope + d_v), 1, 2)
            cos, sin = rope_tables(t, d_rope, self.rope_theta)
            q = jnp.concatenate(
                [q[..., :d_nope], apply_rope(q[..., d_nope:], cos, sin)], -1
            )  # [B, H, T, d_nope + d_rope]
            # One rotated key part a position, the same for every head.
            k_rope = apply_rope(latent[:, None, :, rank:], cos, sin)
            k = jnp.concatenate(
                [kv[..., :d_nope],
                 jnp.broadcast_to(k_rope, (b, h, t, d_rope))], -1
            )
            v = kv[..., d_nope:]  # [B, H, T, d_v]
        with jax.named_scope("mla.attend"):
            o = self.attn_fn(q, k, v)  # [B, H, T, d_v]
        with jax.named_scope("mla.out"):
            o = jnp.moveaxis(o, 1, 2).reshape(b, t, h * d_v)
            return dense(self.d_model, name="o_proj")(o)


class GatedShortConv(nn.Module):
    """The gated short convolution operator
    (:func:`dct_tpu.ops.shortconv.gated_short_conv`) between its two
    projections: ``in_proj`` d_model -> 3 x d_model (column-parallel by
    name would split B, C and X apart, so no rule matches it), a depthwise
    kernel of ``kernel_size`` taps a channel, ``out_proj`` d_model ->
    d_model."""

    d_model: int
    kernel_size: int = 3
    dtype: jnp.dtype = jnp.float32
    use_bias: bool = False

    @nn.compact
    def __call__(self, x):
        from dct_tpu.ops.shortconv import gated_short_conv

        with jax.named_scope("shortconv"):
            bcx = TorchStyleDense(
                3 * self.d_model, dtype=self.dtype, use_bias=self.use_bias,
                name="in_proj",
            )(x)
            taps = self.param(
                "conv_kernel",
                lambda k, sh, dt=jnp.float32: torch_linear_init()(
                    k, sh, dt, fan_in=self.kernel_size
                ),
                (self.d_model, self.kernel_size),
                jnp.float32,
            )
            y = gated_short_conv(bcx, jnp.asarray(taps, self.dtype))
            return TorchStyleDense(
                self.d_model, dtype=self.dtype, use_bias=self.use_bias,
                name="out_proj",
            )(y)


class ResidualDropout(nn.Module):
    """Dropout on a residual branch, its keep-mask an array of its own.

    ``nn.Dropout`` leaves the mask an expression: XLA fuses the threefry
    bit generation into whatever consumes it, the GEMMs on either side of
    the branch among them, forward and backward, and a GEMM evaluates a
    fused producer again for every tile pass of the product (6-10 ms a
    fusion and step at 8,192 x 3072, PERF.md section 6, PR 34). Here the
    mask is drawn once a call, the same threefry Bernoulli at the same
    rate from the same ``dropout`` rng collection, and pinned by an
    optimization barrier: one boolean array in HBM that the forward
    applies and the backward reads as a saved residual. Under ``nn.remat``
    the block's backward draws it once more, from the same key.

    Static values alone decide the path: at ``rate`` 0 or outside training
    the call returns its input and traces no rng and no barrier. A training
    call sows what it kept and what it saw into ``counters``
    (``dropout_kept``, ``dropout_total``; train/steps.py sums them over an
    epoch) and the mask itself into ``intermediates`` for whoever asks."""

    rate: float

    @nn.compact
    def __call__(self, x, train: bool = False):
        if not train or self.rate == 0.0:
            return x
        keep_prob = 1.0 - self.rate
        keep = lax.optimization_barrier(
            jax.random.bernoulli(self.make_rng("dropout"), keep_prob, x.shape)
        )
        self.sow("intermediates", "keep", keep)
        # Exact a call (25M elements pass float32's integers); float32
        # from there on, as an epoch's sum passes int32.
        self.sow(
            "counters", "dropout_kept",
            jnp.asarray(keep.sum(dtype=jnp.int32), jnp.float32),
        )
        self.sow("counters", "dropout_total", jnp.float32(x.size))
        return jnp.where(keep, x / keep_prob, jnp.zeros_like(x))


class TransformerBlock(nn.Module):
    """One pre-norm block: ``x + Op(norm(x))`` then ``x + FFN(norm(x))``.

    The defaults are the block the family started with (LayerNorm, biased
    projections, softmax attention, gelu MLP). The fields select the
    others: ``norm`` / ``norm_eps``, ``use_bias``, ``qk_norm`` and
    ``rope_theta`` for the attention operator, ``op="conv"`` for the gated
    short convolution in attention's place, ``op="latent_attention"`` for
    :class:`LatentAttention` at the widths ``latent`` gives (its keyword
    arguments as a tuple of pairs), ``mlp="swiglu"`` for the gated
    MLP, and ``moe`` (the keyword arguments of
    :class:`dct_tpu.models.moe.MoEFFN` as a tuple of pairs) for routed
    experts in the dense MLP's place."""

    d_model: int
    n_heads: int
    d_ff: int
    dropout: float
    attn_fn: object
    dtype: jnp.dtype = jnp.float32
    n_kv_heads: int | None = None
    rope: bool = False
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    mlp: str = "gelu"
    use_bias: bool = True
    qk_norm: bool = False
    rope_theta: float = 10000.0
    op: str = "full_attention"
    conv_kernel: int = 3
    latent: tuple = ()
    moe: tuple | None = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        # ``train`` is positional-or-keyword (not kw-only) so nn.remat's
        # static_argnums can reach it (WeatherTransformer's remat path).
        h = make_norm(self.norm, self.norm_eps, self.dtype, "ln_attn")(x)
        if self.op == "conv":
            h = GatedShortConv(
                self.d_model, self.conv_kernel, dtype=self.dtype,
                use_bias=self.use_bias, name="conv",
            )(h)
        elif self.op == "full_attention":
            h = MultiHeadAttention(
                self.d_model, self.n_heads, self.attn_fn, dtype=self.dtype,
                n_kv_heads=self.n_kv_heads, rope=self.rope,
                rope_theta=self.rope_theta, use_bias=self.use_bias,
                qk_norm=self.qk_norm, norm_eps=self.norm_eps, name="attn",
            )(h)
        elif self.op == "latent_attention":
            h = LatentAttention(
                self.d_model, self.n_heads, self.attn_fn, dtype=self.dtype,
                rope_theta=self.rope_theta, use_bias=self.use_bias,
                norm_eps=self.norm_eps, name="attn", **dict(self.latent),
            )(h)
        else:
            raise ValueError(
                f"layer type {self.op!r} must be 'full_attention', "
                "'latent_attention' or 'conv'"
            )
        h = ResidualDropout(self.dropout, name="drop_attn")(h, train)
        x = x + h
        h = make_norm(self.norm, self.norm_eps, self.dtype, "ln_ffn")(x)
        dense = functools.partial(
            TorchStyleDense, dtype=self.dtype, use_bias=self.use_bias
        )
        if self.moe is not None:
            from dct_tpu.models.moe import MoEFFN

            h = MoEFFN(
                d_model=self.d_model, dtype=self.dtype, name="moe",
                **dict(self.moe),
            )(h)
        elif self.mlp == "swiglu":
            with jax.named_scope("dense_mlp"):
                h = nn.silu(dense(self.d_ff, name="ffn_gate")(h)) * dense(
                    self.d_ff, name="ffn_in"
                )(h)
                h = dense(self.d_model, name="ffn_out")(h)
        elif self.mlp == "gelu":
            h = nn.gelu(dense(self.d_ff, name="ffn_in")(h))
            h = dense(self.d_model, name="ffn_out")(h)
        else:
            raise ValueError(f"mlp={self.mlp!r} must be 'gelu' or 'swiglu'")
        h = ResidualDropout(self.dropout, name="drop_ffn")(h, train)
        return x + h


class _StageBlocks(nn.Module):
    """One pipeline stage: ``layers_per_stage`` identical pre-LN blocks.

    Deterministic (no dropout): the PP family applies dropout OUTSIDE the
    pipelined region so stages need no rng threading through shard_map.
    """

    d_model: int
    n_heads: int
    d_ff: int
    layers_per_stage: int
    attn_fn: object
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    n_kv_heads: int | None = None
    rope: bool = False

    @nn.compact
    def __call__(self, h):
        block_cls = (
            nn.remat(TransformerBlock, static_argnums=(2,))
            if self.remat
            else TransformerBlock
        )
        for i in range(self.layers_per_stage):
            h = block_cls(
                self.d_model, self.n_heads, self.d_ff, 0.0, self.attn_fn,
                dtype=self.dtype, n_kv_heads=self.n_kv_heads,
                rope=self.rope, name=f"block_{i}",
            )(h, False)
        return h


class WeatherTransformerPP(nn.Module):
    """Pipeline-parallel transformer: ``n_layers`` grouped into
    ``n_stages`` homogeneous stages streamed GPipe-style over the mesh's
    ``pipe`` axis (:func:`dct_tpu.parallel.pipeline.pipeline_apply`).

    Stage params live in ONE stacked pytree param named ``pp_stages``
    (leading dim = stage), which the sharding rules place
    ``P("pipe", <TP name-rule spec>)`` — each pipeline device holds one
    stage, and the stage's projection kernels keep their megatron-style
    ``model``-axis split. Composes with DP (microbatch rows shard over
    ``data``) AND TP: pipeline_apply's shard_map is manual only over
    pipe/data, so the model axis stays auto and the compiler inserts the
    per-block TP collectives inside each stage. Attention is the
    single-shard dense/blockwise/flash path (no seq axis). Embedding,
    dropout, final LN and the classifier head run outside the pipelined
    region (replicated).

    Without a mesh (or ``pipe`` = 1, or the batch-1 flax init trace) the
    stages apply sequentially — the same function, used by tests as the
    pipeline oracle.
    """

    input_dim: int
    seq_len: int
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    num_classes: int = 2
    dropout: float = 0.1
    n_stages: int = 2
    n_microbatches: int | None = None
    attn_fn: object = None
    mesh: object = None
    remat: bool = False
    compute_dtype: jnp.dtype = jnp.float32
    n_kv_heads: int | None = None
    pos_embed: str = "sincos"

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        from dct_tpu.ops.attention import make_attention_fn
        from dct_tpu.parallel.pipeline import pipeline_apply

        if self.n_layers % self.n_stages:
            raise ValueError(
                f"n_layers={self.n_layers} must divide into "
                f"n_stages={self.n_stages} homogeneous stages"
            )
        attn_fn = self.attn_fn or make_attention_fn(None)
        ct = self.compute_dtype
        stage_mod = _StageBlocks(
            self.d_model, self.n_heads, self.d_ff,
            self.n_layers // self.n_stages, attn_fn, dtype=ct,
            remat=self.remat, n_kv_heads=self.n_kv_heads,
            rope=self.pos_embed == "rope",
        )

        def init_stages(rng):
            zeros = jnp.zeros((1, self.seq_len, self.d_model), ct)
            rngs = jax.random.split(rng, self.n_stages)
            return jax.vmap(
                lambda r: stage_mod.init(r, zeros)["params"]
            )(rngs)

        stacked = self.param("pp_stages", init_stages)

        x = jnp.asarray(x, ct)
        h = TorchStyleDense(self.d_model, dtype=ct, name="in_proj")(x)
        if self.pos_embed != "rope":  # rope rotates q/k inside attention
            h = h + jnp.asarray(
                sincos_positions(self.seq_len, self.d_model), ct
            )
        h = nn.Dropout(rate=self.dropout, deterministic=not train)(h)

        mesh = self.mesh
        b = h.shape[0]
        pipe = mesh.shape.get("pipe", 1) if mesh is not None else 1
        m = self.n_microbatches or max(pipe, 1)
        dp = mesh.shape.get("data", 1) if mesh is not None else 1
        if pipe > 1 and b % m == 0 and (b // m) % dp == 0:
            h = pipeline_apply(
                lambda p, a: stage_mod.apply({"params": p}, a),
                stacked, h, mesh=mesh, n_microbatches=m,
                data_axis="data" if dp > 1 else None,
            )
        elif pipe > 1 and b >= m * dp:
            # A real batch that cannot tile the configured pipeline is a
            # sizing bug: running the sequential path with P('pipe')
            # params would all-gather every stage each step and silently
            # discard the pipelining the user configured.
            raise ValueError(
                f"batch {b} does not tile n_microbatches={m} x data={dp} "
                f"for the pipe={pipe} mesh; adjust batch_size or "
                "n_microbatches"
            )
        else:
            # Sequential oracle: batch-1 init trace or pipe=1.
            for i in range(self.n_stages):
                p_i = jax.tree.map(lambda a, i=i: a[i], stacked)
                h = stage_mod.apply({"params": p_i}, h)

        h = nn.LayerNorm(dtype=ct, name="ln_out")(h)
        pooled = h.mean(axis=1)
        logits = TorchStyleDense(self.num_classes, dtype=ct, name="head")(
            pooled
        )
        return jnp.asarray(logits, jnp.float32)


class WeatherTransformer(nn.Module):
    """Encoder over [B, S, F] windows -> [B, num_classes] rain logits.

    ``per_position``: decoder-style per-position head — [B, S, classes]
    logits, one next-step forecast per position (pair with a CAUSAL
    ``attn_fn`` so position t sees only rows <= t; the causal family in
    the registry wires both). ``horizon`` > 1 widens that head to DIRECT
    multi-horizon forecasting: [B, S, horizon, classes] logits, position
    t predicting steps t+1..t+horizon in one pass."""

    input_dim: int
    seq_len: int
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    num_classes: int = 2
    dropout: float = 0.1
    attn_fn: object = None  # default set in __call__ (dense/blockwise)
    per_position: bool = False
    horizon: int = 1
    remat: bool = False
    compute_dtype: jnp.dtype = jnp.float32
    n_kv_heads: int | None = None
    pos_embed: str = "sincos"
    # The block's form (TransformerBlock's fields of the same names).
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    mlp: str = "gelu"
    use_bias: bool = True
    qk_norm: bool = False
    rope_theta: float = 10000.0
    # Per-layer operator, one entry a layer ("full_attention" |
    # "latent_attention" | "conv"); empty = attention everywhere. ``latent``
    # holds LatentAttention's widths as a tuple of pairs.
    layer_types: tuple = ()
    conv_kernel: int = 3
    latent: tuple = ()
    # Routed experts (MoEFFN's keyword arguments as a tuple of pairs) in
    # every layer from ``num_dense_layers`` on; the leading layers keep
    # the dense MLP of width ``d_ff``.
    moe: tuple | None = None
    num_dense_layers: int = 0

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        from dct_tpu.ops.attention import make_attention_fn

        if self.d_model % 2 or self.d_model % self.n_heads:
            raise ValueError(
                f"d_model={self.d_model} must be even (sinusoidal positions)"
                f" and divisible by n_heads={self.n_heads}"
            )
        if self.layer_types and len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"n_layers={self.n_layers}"
            )
        attn_fn = self.attn_fn or make_attention_fn(None)
        dense = functools.partial(
            TorchStyleDense, dtype=self.compute_dtype, use_bias=self.use_bias
        )
        x = jnp.asarray(x, self.compute_dtype)
        h = dense(self.d_model, name="in_proj")(x)
        if self.pos_embed != "rope":  # rope rotates q/k inside attention
            h = h + jnp.asarray(
                sincos_positions(self.seq_len, self.d_model),
                self.compute_dtype,
            )
        # Activation rematerialization: store only block BOUNDARIES on the
        # forward pass and recompute block internals in backward — the
        # HBM-for-FLOPs trade that unlocks long sequences (activation
        # memory drops from O(layers * seq * d_ff) to O(layers * seq *
        # d_model)). Param tree and math are identical (static_argnums=2
        # is ``train``; self counts as 0 in flax's indexing).
        block_cls = (
            nn.remat(TransformerBlock, static_argnums=(2,))
            if self.remat
            else TransformerBlock
        )
        for i in range(self.n_layers):
            h = block_cls(
                self.d_model,
                self.n_heads,
                self.d_ff,
                self.dropout,
                attn_fn,
                dtype=self.compute_dtype,
                n_kv_heads=self.n_kv_heads,
                rope=self.pos_embed == "rope",
                norm=self.norm,
                norm_eps=self.norm_eps,
                mlp=self.mlp,
                use_bias=self.use_bias,
                qk_norm=self.qk_norm,
                rope_theta=self.rope_theta,
                op=self.layer_types[i] if self.layer_types else "full_attention",
                conv_kernel=self.conv_kernel,
                latent=self.latent,
                moe=self.moe if i >= self.num_dense_layers else None,
                name=f"block_{i}",
            )(h, train)
        h = make_norm(self.norm, self.norm_eps, self.compute_dtype, "ln_out")(h)
        if self.per_position and self.horizon > 1:
            logits = dense(
                self.num_classes * self.horizon, name="head"
            )(h).reshape(*h.shape[:-1], self.horizon, self.num_classes)
        elif self.per_position:
            logits = dense(self.num_classes, name="head")(h)  # [B, S, classes]
        else:
            logits = dense(self.num_classes, name="head")(h.mean(axis=1))
        return jnp.asarray(logits, jnp.float32)

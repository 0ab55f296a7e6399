"""Plain reference for ``sc2_3b_block``: StarCoder2's decoder block stack in
straightforward float32 ``jax.numpy``, no kernels, no cache, no batching
tricks, ``default_matmul_precision("highest")``.

Follows the published block (Starcoder2DecoderLayer: pre-LN LayerNorm,
biased q/k/v/o projections with grouped-query attention, rotary positions
with rotate-half pairing, causal sliding-window softmax attention, biased
c_fc -> gelu(tanh) -> c_proj MLP, residual after each). Departures, the
same ones the configuration file lists:

- no token embedding and no vocabulary head: a biased projection of the
  ``input_dim`` features to ``hidden_size`` goes in, a biased projection to
  ``num_classes`` per position comes out, after the final LayerNorm;
- RoPE base and LayerNorm epsilon are read from the configuration file
  (10,000 and 1e-6 as run; published 999,999.44 and 1e-5);
- q, k and v live in ONE fused projection laid out group-major
  ``(kv_heads, q_per_group + 2, head)``: per group its query heads, then one
  key and one value head. This is storage layout, not mathematics;
- dropout is off (the comparison runs in evaluation mode).

Independent of the code under test: it imports nothing from ``dct_tpu``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _rope(x, theta):
    """x [H, T, Dh]; rotate-half pairing, angle t * theta^(-i/half)."""
    t, half = x.shape[-2], x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(h, p, *, n_heads, n_kv, window, theta):
    """h [T, D] -> [T, D]: causal, position t sees [t - window + 1, t]."""
    t, d = h.shape
    dh = d // n_heads
    per = n_heads // n_kv
    qkv = _dense(h, p["qkv_proj"]).reshape(t, n_kv, per + 2, dh)
    q = qkv[:, :, :per].reshape(t, n_heads, dh).transpose(1, 0, 2)
    k = qkv[:, :, per].transpose(1, 0, 2)  # [n_kv, T, Dh]
    v = qkv[:, :, per + 1].transpose(1, 0, 2)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, per, axis=0)  # query head i uses kv head i // per
    v = jnp.repeat(v, per, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
    pos = jnp.arange(t)
    dist = pos[:, None] - pos[None, :]
    mask = dist >= 0
    if window:
        mask &= dist < window
    s = jnp.where(mask[None], s, -jnp.inf)
    o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v)
    return _dense(o.transpose(1, 0, 2).reshape(t, d), p["o_proj"])


def _forward_one(params, x, *, n_layers, n_heads, n_kv, window, theta, eps):
    h = _dense(x, params["in_proj"])
    for i in range(n_layers):
        p = params[f"block_{i}"]
        h = h + _attention(
            _layer_norm(h, p["ln_attn"], eps), p["attn"], n_heads=n_heads,
            n_kv=n_kv, window=window, theta=theta,
        )
        m = _dense(_layer_norm(h, p["ln_ffn"], eps), p["ffn_in"])
        m = jax.nn.gelu(m, approximate=True)  # gelu_pytorch_tanh
        h = h + _dense(m, p["ffn_out"])
    return _dense(_layer_norm(h, params["ln_out"], eps), params["head"])


def cross_entropy(logits, labels) -> float:
    """Mean negative log-likelihood over every position, float64 numpy."""
    z = np.asarray(logits, np.float64)
    z = z - z.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    lab = np.asarray(labels, np.int64)[..., None]
    return float(-np.take_along_axis(logp, lab, -1).mean())


def forward_and_loss(params, x, y, config: dict):
    """params: the flax tree under ``"params"`` as host arrays; x [N, T, F]
    float32; y [N, T] int. Returns (logits [N, T, C] float32, mean CE)."""
    kw = dict(
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv=int(config["num_key_value_heads"]),
        window=int(config.get("sliding_window") or 0),
        theta=float(config["rope_theta"]),
        eps=float(config["norm_epsilon"]),
    )
    one = jax.jit(lambda p, xi: _forward_one(p, xi, **kw))
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        logits = np.stack([
            np.asarray(one(p32, jnp.asarray(xi, jnp.float32))) for xi in x
        ])
    return logits, cross_entropy(logits, y)

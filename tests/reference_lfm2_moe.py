"""Plain reference for ``lfm2_24b_a2b_ep8``: LFM2-MoE's decoder layers
(``model_type`` ``lfm2_moe``) in straightforward float32 ``jax.numpy``, no
kernels, no cache, no grouped products, ``default_matmul_precision
("highest")``, for exactly the share of the experts the configuration
states.

The published layer (RMS(x) = x / sqrt(mean(x^2) + eps) * w; no bias
anywhere):

- block: ``h = h + Op(RMS(h))`` then ``h = h + FFN(RMS(h))``;
- Op ``conv``: ``B, C, X = split3(u @ W_in)``; ``z = B * X``; ``c[t] =
  sum_j k[:, j] * z[t - (K - 1) + j]`` (depthwise, ``K = conv_L_cache``
  taps, causal, zeros before t = 0); ``Op = (C * c) @ W_out``;
- Op ``full_attention``: grouped-query attention; q and k get an RMS norm
  over each head's dims (own weight each), then rotary positions
  (rotate-half) over the whole head; causal softmax, scale 1/sqrt(head);
- FFN dense (the first ``num_dense_layers`` layers): ``(silu(x @ W1) * (x @
  W3)) @ W2``;
- FFN MoE: ``s = sigmoid(x @ W_r)``; the ``num_experts_per_tok`` experts
  are the top of ``s + b`` (``b`` the expert bias); their weights are ``s``
  at those experts over (their sum + 1e-6), times
  ``routed_scaling_factor``; ``out = sum_i w_i E_i(x)``, each ``E_i`` the
  gated MLP at width ``moe_intermediate_size``.

The share: the router is as wide as its kernel (64) and the top experts are
chosen over all of them; only experts ``first_expert .. first_expert +
num_experts - 1`` are held, and only their terms of the sum are computed.
What the experts held on the other chips would add is left out, here as in
the program, and that partial result goes on to the next layer.

Departures, the ones the configuration file lists: no token embedding and no
vocabulary head (a bias-free projection of ``input_dim`` features in, the
final RMS norm and a bias-free projection to ``num_classes`` per position
out); q, k and v live in ONE fused projection laid out group-major
``(kv_heads, q_per_group + 2, head)``, which is storage and not
mathematics; the expert bias's balancing update is not run.

With ``routing`` given (per MoE layer the chosen experts of every position)
those experts are used in place of the reference's own choice, with the
reference's own scores as their weights: the comparison of logits is then
of everything but the discrete choice, which is compared on its own.

Independent of the code under test: it imports nothing from ``dct_tpu``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Query rows per block of the attention: [heads, 1024, T] f32 scores are
#: 1 GB at 32 heads and 8,192 positions.
Q_BLOCK = 1024


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [H, T, Dh]; rotate-half pairing, angle t * theta^(-i/half)."""
    t, half = x.shape[-2], x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(u, p, *, n_heads, n_kv, theta, eps):
    """u [T, D] -> [T, D]: causal, position t sees positions <= t."""
    t, d = u.shape
    dh = d // n_heads
    per = n_heads // n_kv
    qkv = (u @ p["qkv_proj"]["kernel"]).reshape(t, n_kv, per + 2, dh)
    q = qkv[:, :, :per].reshape(t, n_heads, dh).transpose(1, 0, 2)
    k = qkv[:, :, per].transpose(1, 0, 2)  # [n_kv, T, Dh]
    v = qkv[:, :, per + 1].transpose(1, 0, 2)
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), theta)
    k = jnp.repeat(k, per, axis=0)  # query head i uses kv head i // per
    v = jnp.repeat(v, per, axis=0)
    pos = jnp.arange(t)
    out = []
    for q0 in range(0, t, Q_BLOCK):
        qb = q[:, q0:q0 + Q_BLOCK]
        s = jnp.einsum("hqd,hkd->hqk", qb, k) / jnp.sqrt(jnp.float32(dh))
        mask = pos[q0:q0 + Q_BLOCK, None] >= pos[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(out, axis=1)
    return o.transpose(1, 0, 2).reshape(t, d) @ p["o_proj"]["kernel"]


def _short_conv(u, p):
    """u [T, D] -> [T, D]: the gated short convolution."""
    t = u.shape[0]
    b, c, x = jnp.split(u @ p["in_proj"]["kernel"], 3, axis=-1)
    z = b * x
    taps = p["conv_kernel"]  # [D, K]
    k = taps.shape[1]
    zp = jnp.concatenate([jnp.zeros((k - 1, z.shape[1]), z.dtype), z])
    conv = sum(zp[j:j + t] * taps[:, j] for j in range(k))
    return (c * conv) @ p["out_proj"]["kernel"]


def _gated_mlp(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _moe(x, p, *, top_k, first, scaling, routing):
    """x [T, D] -> (the held experts' part of the layer [T, D], chosen
    experts [T, k], margin [T] between the k-th and the (k+1)-th of the
    selection scores)."""
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    sel = s + p["expert_bias"]
    ranked, own = jax.lax.top_k(sel, top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    topi = own[:, :top_k] if routing is None else routing
    w = jnp.take_along_axis(s, topi, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-6) * scaling
    out = jnp.zeros_like(x)
    for j in range(p["experts_in_kernel"].shape[0]):
        # This expert's weight at every position (0 where it was not chosen).
        wj = jnp.where(topi == first + j, w, 0.0).sum(-1, keepdims=True)
        out = out + wj * _gated_mlp(
            x, p["experts_gate_kernel"][j], p["experts_in_kernel"][j],
            p["experts_out_kernel"][j])
    return out, own[:, :top_k], margin


def forward_one(params, x, routing, *, types, n_dense, n_heads, n_kv,
                theta, eps, top_k, first, scaling):
    """One sequence: params (float32), x [T, F], ``routing`` [M, T, k] or
    None; the keywords are :func:`settings`'. Returns (logits [T, C],
    the reference's own chosen experts [M, T, k], margins [M, T]). Plain
    ``jax.numpy``, so it can be differentiated."""
    h = x @ params["in_proj"]["kernel"]
    chosen, margins = [], []
    for i, kind in enumerate(types):
        p = params[f"block_{i}"]
        u = _rms(h, p["ln_attn"]["scale"], eps)
        if kind == "conv":
            h = h + _short_conv(u, p["conv"])
        else:
            h = h + _attention(
                u, p["attn"], n_heads=n_heads, n_kv=n_kv, theta=theta,
                eps=eps)
        u = _rms(h, p["ln_ffn"]["scale"], eps)
        if i < n_dense:
            h = h + _gated_mlp(
                u, p["ffn_gate"]["kernel"], p["ffn_in"]["kernel"],
                p["ffn_out"]["kernel"])
        else:
            m = len(chosen)
            out, topi, margin = _moe(
                u, p["moe"], top_k=top_k, first=first, scaling=scaling,
                routing=None if routing is None else routing[m])
            h = h + out
            chosen.append(topi)
            margins.append(margin)
    logits = _rms(h, params["ln_out"]["scale"], eps) @ params["head"]["kernel"]
    return logits, jnp.stack(chosen), jnp.stack(margins)


def cross_entropy(logits, labels) -> float:
    """Mean negative log-likelihood over every position, float64 numpy."""
    z = np.asarray(logits, np.float64)
    z = z - z.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    lab = np.asarray(labels, np.int64)[..., None]
    return float(-np.take_along_axis(logp, lab, -1).mean())


def forward(params, x, config: dict, routing=None) -> dict:
    """params: the flax tree under ``"params"`` as host arrays; x [N, T, F]
    float32; ``routing`` [N, M, T, k] int or None (M = layers with
    experts). Returns ``logits`` [N, T, C] float32, ``topk`` [N, M, T, k]
    (the reference's OWN choice, whatever ``routing`` says) and ``margin``
    [N, M, T]."""
    kw = settings(config)
    one = jax.jit(lambda p, xi, r: forward_one(p, xi, r, **kw))
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        held = {
            v["moe"]["experts_in_kernel"].shape[0]
            for v in p32.values() if "moe" in v}
        if held != {int(config["num_experts"])}:
            raise ValueError(
                f"the parameters hold {sorted(held)} experts a layer, the "
                f"configuration states {config['num_experts']}")
        outs = [
            one(p32, jnp.asarray(xi, jnp.float32),
                None if routing is None else jnp.asarray(routing[n]))
            for n, xi in enumerate(x)
        ]
    logits, topk, margin = (
        np.stack([np.asarray(o[i]) for o in outs]) for i in range(3))
    return {"logits": logits, "topk": topk, "margin": margin}


def settings(config: dict) -> dict:
    """The configuration file's keys as :func:`forward_one`'s keywords:
    ``layers_run`` picks the layers that are run out of the published
    ``layer_types``."""
    run = config["layers_run"]
    if len(run) != int(config["num_hidden_layers"]):
        raise ValueError("layers_run does not name num_hidden_layers layers")
    return dict(
        types=tuple(config["layer_types"][i] for i in run),
        n_dense=int(config["num_dense_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv=int(config["num_key_value_heads"]),
        theta=float(config["rope_parameters"]["rope_theta"]),
        eps=float(config["norm_eps"]),
        top_k=int(config["num_experts_per_tok"]),
        first=int(config["first_expert"]),
        scaling=float(config["routed_scaling_factor"]),
    )


def forward_and_loss(params, x, y, config: dict, routing=None):
    """x [N, T, F] float32; y [N, T] int. Returns (logits [N, T, C]
    float32, mean CE)."""
    logits = forward(params, x, config, routing)["logits"]
    return logits, cross_entropy(logits, y)

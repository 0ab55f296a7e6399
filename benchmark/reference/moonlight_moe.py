"""Plain reference for ``moonlight_16b_a3b_ep8``: the decoder layers of
Moonlight-16B-A3B (``model_type`` ``deepseek_v3``) in straightforward
float32 ``jax.numpy``, no kernels, no cache, no grouped products,
``default_matmul_precision("highest")``, for exactly the share of the
routed experts the configuration states.

The published layer (RMS(x) = x / sqrt(mean(x^2) + eps) * w; no bias
anywhere; x [T, hidden]):

- block: ``h = h + Attn(RMS(h))`` then ``h = h + FFN(RMS(h))``;
- Attn, latent attention over ``H`` heads: ``q = x W_q``, per head ``[q_nope
  (qk_nope_head_dim) | q_rope (qk_rope_head_dim)]`` (``q_lora_rank`` is
  null: no query compression); ``x W_kva`` gives ``[c_kv (kv_lora_rank) |
  k_rope (qk_rope_head_dim)]``; ``RMS(c_kv) W_kvb`` gives per head ``[k_nope
  | v (v_head_dim)]``; rotary positions at base ``rope_theta`` on ``q_rope``
  and on the ONE ``k_rope``, which every head shares; ``k = [k_nope |
  k_rope]``; ``o = softmax(q k^T / sqrt(qk_nope + qk_rope) + causal) v``;
  ``Attn = concat_heads(o) W_o``. The rotation pairs dim ``i`` with ``i +
  half`` (rotate-half), as the program does: the interleaved pairing of the
  published code is a fixed permutation of ``W_q``'s and ``W_kva``'s rotated
  columns, and weights are seeded here, not loaded;
- FFN dense (the first ``first_k_dense_replace`` layers): ``(silu(x W1) * (x
  W3)) W2`` at ``intermediate_size``;
- FFN MoE: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok`` experts are
  the top of ``s + b`` (``b`` the selection bias; ``n_group`` =
  ``topk_group`` = 1: no group limit); their weights are ``s`` at those
  experts over (their sum + 1e-20), times ``routed_scaling_factor``; ``out
  = sum_i w_i E_i(x) + S(x)``, ``E_i`` the gated MLP at
  ``moe_intermediate_size`` and ``S`` ONE gated MLP at ``n_shared_experts x
  moe_intermediate_size`` (the shared experts side by side);
- the balancing update (``topk_method`` ``noaux_tc``), once per optimizer
  step and after it: ``b_i += u * sign(mean(c) - c_i)``, ``c_i`` the rows
  routed to expert ``i`` in the step (:func:`bias_step`).

The share: the router is as wide as its kernel (64) and the top experts are
chosen over all of them; only experts ``first_expert .. first_expert +
n_routed_experts - 1`` are held, and only their terms of the sum are
computed; the shared expert is computed whole. What the experts held on the
other chips would add is left out, here as in the program, and that partial
result goes on to the next layer.

Departures, the ones the configuration file lists: no token embedding and no
vocabulary head (a bias-free projection of ``input_dim`` features in, the
final RMS norm and a bias-free projection to ``num_classes`` per position
out); the sequence-wise auxiliary loss (``seq_aux``) is left out.

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
#: 0.5 GB at 16 heads and 8,192 positions.
Q_BLOCK = 1024


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [..., T, D]; rotate-half pairing, angle t * theta^(-i/half)."""
    t, half = x.shape[-2], x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _latent_attention(u, p, *, n_heads, rank, d_nope, d_rope, d_v, theta,
                      eps):
    """u [T, D] -> [T, D]: causal, position t sees positions <= t."""
    t = u.shape[0]
    q = (u @ p["q_proj"]["kernel"]).reshape(t, n_heads, d_nope + d_rope)
    q = q.transpose(1, 0, 2)  # [H, T, d_nope + d_rope]
    latent = u @ p["kv_a_proj"]["kernel"]  # [T, rank + d_rope]
    c_kv = _rms(latent[:, :rank], p["kv_norm"]["scale"], eps)
    kv = (c_kv @ p["kv_b_proj"]["kernel"]).reshape(t, n_heads, d_nope + d_v)
    kv = kv.transpose(1, 0, 2)
    k_nope, v = kv[..., :d_nope], kv[..., d_nope:]
    q_nope, q_rope = q[..., :d_nope], _rope(q[..., d_nope:], theta)
    k_rope = _rope(latent[:, rank:], theta)  # [T, d_rope], every head's
    pos = jnp.arange(t)
    out = []
    for q0 in range(0, t, Q_BLOCK):
        rows = slice(q0, q0 + Q_BLOCK)
        s = jnp.einsum("hqd,hkd->hqk", q_nope[:, rows], k_nope) + jnp.einsum(
            "hqd,kd->hqk", q_rope[:, rows], k_rope)
        s = s / jnp.sqrt(jnp.float32(d_nope + d_rope))
        mask = pos[rows, None] >= pos[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(out, axis=1)  # [H, T, d_v]
    return o.transpose(1, 0, 2).reshape(t, n_heads * d_v) @ p["o_proj"][
        "kernel"]


def _gated_mlp(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _moe(x, p, *, top_k, first, scaling, routing):
    """x [T, D] -> (the held experts' part of the layer plus the shared
    expert [T, D], chosen experts [T, k], margin [T] between the k-th and
    the (k+1)-th of the selection scores)."""
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    sel = s + p["expert_bias"]
    ranked, own = jax.lax.top_k(sel, top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    topi = own[:, :top_k] if routing is None else routing
    w = jnp.take_along_axis(s, topi, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scaling
    out = jnp.zeros_like(x)
    for j in range(p["experts_in_kernel"].shape[0]):
        # This expert's weight at every position (0 where it was not chosen).
        wj = jnp.where(topi == first + j, w, 0.0).sum(-1, keepdims=True)
        out = out + wj * _gated_mlp(
            x, p["experts_gate_kernel"][j], p["experts_in_kernel"][j],
            p["experts_out_kernel"][j])
    if "shared_in" in p:
        out = out + _gated_mlp(
            x, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
            p["shared_out"]["kernel"])
    return out, own[:, :top_k], margin


def bias_step(bias, topk, speed: float):
    """One balancing update of one layer's selection bias: ``bias`` [E],
    ``topk`` [..., k] the experts the step's rows chose (all E counted,
    held here or not). An expert with more rows than the mean goes down by
    ``speed``, one with fewer up, one at the mean stays."""
    load = np.bincount(
        np.asarray(topk).reshape(-1), minlength=bias.shape[0]
    ).astype(np.float64)
    return (np.asarray(bias, np.float64)
            + speed * np.sign(load.mean() - load)).astype(np.float32)


def forward_one(params, x, routing, *, n_layers, n_dense, n_heads, rank,
                d_nope, d_rope, d_v, theta, eps, top_k, first, scaling):
    """One sequence: params (float32), x [T, F], ``routing`` [M, T, k] or
    None; the keywords are :func:`settings`'. Returns (logits [T, C],
    the reference's own chosen experts [M, T, k], margins [M, T]). Plain
    ``jax.numpy``, so it can be differentiated."""
    h = x @ params["in_proj"]["kernel"]
    chosen, margins = [], []
    for i in range(n_layers):
        p = params[f"block_{i}"]
        h = h + _latent_attention(
            _rms(h, p["ln_attn"]["scale"], eps), p["attn"], n_heads=n_heads,
            rank=rank, d_nope=d_nope, d_rope=d_rope, d_v=d_v, theta=theta,
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
        if held != {int(config["n_routed_experts"])}:
            raise ValueError(
                f"the parameters hold {sorted(held)} experts a layer, the "
                f"configuration states {config['n_routed_experts']}")
        outs = [
            one(p32, jnp.asarray(xi, jnp.float32),
                None if routing is None else jnp.asarray(routing[n]))
            for n, xi in enumerate(x)
        ]
    logits, topk, margin = (
        np.stack([np.asarray(o[i]) for o in outs]) for i in range(3))
    return {"logits": logits, "topk": topk, "margin": margin}


def settings(config: dict) -> dict:
    """The configuration file's keys as :func:`forward_one`'s keywords."""
    if config["q_lora_rank"] is not None:
        raise ValueError("a compressed query path is not written here")
    if (config["n_group"], config["topk_group"]) != (1, 1):
        raise ValueError("a group-limited top-k is not written here")
    return dict(
        n_layers=int(config["num_hidden_layers"]),
        n_dense=int(config["first_k_dense_replace"]),
        n_heads=int(config["num_attention_heads"]),
        rank=int(config["kv_lora_rank"]),
        d_nope=int(config["qk_nope_head_dim"]),
        d_rope=int(config["qk_rope_head_dim"]),
        d_v=int(config["v_head_dim"]),
        theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        top_k=int(config["num_experts_per_tok"]),
        first=int(config["first_expert"]),
        scaling=float(config["routed_scaling_factor"]),
    )


def forward_and_loss(params, x, y, config: dict, routing=None):
    """x [N, T, F] float32; y [N, T] int. Returns (logits [N, T, C]
    float32, mean CE)."""
    logits = forward(params, x, config, routing)["logits"]
    return logits, cross_entropy(logits, y)

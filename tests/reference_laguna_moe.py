"""Plain reference for ``laguna_xs2_ep16``: the decoder layers of
Laguna-XS.2 (``model_type`` ``laguna``) in straightforward float32
``jax.numpy``, no kernels, no cache, no grouped products,
``default_matmul_precision("highest")``, for exactly the share of the
routed experts the configuration states.

The published layer (RMS(x) = x / sqrt(mean(x^2) + eps) * w; no bias
anywhere; x [T, hidden]):

- block: ``h = h + Attn(RMS(h))`` then ``h = h + MLP(RMS(h))``;
  ``mlp_layer_types`` names each layer's MLP: ``dense`` is the gated MLP
  ``(silu(x W1) * (x W3)) W2`` at ``intermediate_size``, ``sparse`` the
  routed experts with the shared expert;
- Attn: grouped-query attention, ``num_attention_heads_per_layer[i]``
  query heads over ``num_key_value_heads`` key/value heads of ``head_dim``
  (query head ``j`` reads key/value head ``j // (H / kv)``); scores scaled
  by 1/sqrt(head_dim). ``layer_types`` names each layer's kind:
  ``sliding_attention`` sees the last ``sliding_window`` positions, ``0 <=
  q - k < sliding_window``; ``full_attention`` is full causal, ``q >= k``.
  The kind's ``rope_parameters`` group gives its rotation: the first
  ``partial_rotary_factor x head_dim`` dims of each query and key head
  rotate (rotate-half pairing within them), the rest pass unrotated;
  ``rope_type`` ``default`` is the plain rotation at ``rope_theta``,
  ``yarn`` the YaRN frequencies computed over the rotated dims
  (:func:`yarn_frequencies`: a pair that turns ``beta_fast`` times or more
  over ``original_max_position_embeddings`` keeps its frequency, one that
  turns ``beta_slow`` times or fewer has it divided by ``factor``, a linear
  ramp over the pair index between, the lower index floored and the upper
  ceiled as in the published YaRN code, the ramp clipped to [0, 1]), cos
  and sin multiplied by ``attention_factor``. ``gating``: each head's
  output is multiplied by ``sigmoid(x W_g)`` of its own (``W_g`` [hidden,
  H], from the block's normed input ``x``), then ``concat_h(o_h) W_o``;
- MoE: ``s = sigmoid(x W_r)`` over all the router's experts; the
  ``num_experts_per_tok`` experts are the top of ``s + b`` (``b`` the
  selection bias, which chooses and weighs nothing); their weights are
  ``s`` at those experts over their sum, times
  ``moe_routed_scaling_factor``; ``out = sum_i w_i E_i(x) + S(x)``, ``E_i``
  the gated MLP at ``moe_intermediate_size`` and ``S`` ONE gated MLP at
  ``shared_expert_intermediate_size`` (no gate on it);
- the balancing update, once per optimizer step, after it: ``b += u x
  sign(mean(c) - c)``, ``c_i`` the assignments the step's rows made to
  expert ``i`` (:func:`bias_step`).

The share: the router is as wide as its kernel (256) and the top experts are
chosen over all of them; only experts ``first_expert .. first_expert +
num_experts - 1`` are held, and only their terms of the routed sum are
computed; the shared expert is computed whole. What the experts held on the
other chips would add is left out, here as in the program, and that partial
result goes on to the next layer.

Departures, the ones the configuration file lists: no token embedding and no
vocabulary head (a bias-free projection of ``input_dim`` features in, the
final RMS norm and a bias-free projection to ``num_classes`` per position
out); q, k and v live in ONE fused projection laid out group-major
``(kv_heads, q_per_group + 2, head)``, and the gate's H outputs are the q
heads in that order, which is storage and not mathematics.

With ``routing`` given (per routed layer the chosen experts of every
position) those experts are used in place of the reference's own choice,
with the reference's own scores as their weights: the comparison of logits
is then of everything but the discrete choice, which is compared on its
own. The ``margin`` beside the reference's own choice is the distance
between the k-th and the (k+1)-th selection score ``s + b``: how far a
rounding has to move a score to change the choice.

Independent of the code under test: it imports nothing from ``dct_tpu``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: Query rows per block of the attention: [heads, 512, T] f32 scores are
#: 1 GB at 64 heads and 8,192 positions.
Q_BLOCK = 512


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def plain_frequencies(dim: int, theta: float) -> np.ndarray:
    half = dim // 2
    return theta ** (-np.arange(half, dtype=np.float64) / half)


def find_correction_dim(turns: float, dim: int, theta: float,
                        original: int) -> float:
    """The pair index (a real number) of ``dim`` rotated dims whose pair
    turns ``turns`` times over ``original`` positions."""
    return dim * math.log(original / (turns * 2 * math.pi)) / (
        2 * math.log(theta))


def yarn_frequencies(dim: int, theta: float, *, factor: float,
                     original: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """The YaRN inverse frequencies [dim / 2] over ``dim`` rotated dims,
    float64."""
    half = dim // 2
    low = max(math.floor(
        find_correction_dim(beta_fast, dim, theta, original)), 0)
    high = min(math.ceil(
        find_correction_dim(beta_slow, dim, theta, original)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)  # 0: extrapolated (kept), 1: interpolated
    plain = plain_frequencies(dim, theta)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotated_dims(head_dim: int, rope: dict) -> int:
    """How many of a head's dims one ``rope_parameters`` group rotates."""
    return int(round(float(rope.get("partial_rotary_factor", 1.0))
                     * head_dim))


def rotation(t: int, head_dim: int, rope: dict):
    """(cos, sin) [T, rotated / 2] float32 of one ``rope_parameters``
    group: ``rope_type`` ``default`` or ``yarn``, over the rotated dims."""
    dim = rotated_dims(head_dim, rope)
    theta = float(rope["rope_theta"])
    if rope["rope_type"] == "default":
        inv, scale = plain_frequencies(dim, theta), 1.0
    elif rope["rope_type"] == "yarn":
        inv = yarn_frequencies(
            dim, theta, factor=float(rope["factor"]),
            original=int(rope["original_max_position_embeddings"]),
            beta_fast=float(rope["beta_fast"]),
            beta_slow=float(rope["beta_slow"]))
        scale = float(rope["attention_factor"])
    else:
        raise ValueError(
            f"rope_type {rope['rope_type']!r} is not written here")
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(scale * np.cos(ang), jnp.float32),
            jnp.asarray(scale * np.sin(ang), jnp.float32))


def _rotate(x, cos, sin):
    """x [H, T, Dh]: the first ``2 x cos.shape[-1]`` dims rotate-half
    paired among themselves, the rest unchanged."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], -1)


def _attention(u, p, *, n_heads, n_kv, dh, window, cos, sin, gated):
    """u [T, D] -> [T, D]: position q sees positions k with ``0 <= q - k``
    (``< window`` where the layer has one); each head's output times its
    gate where the model is ``gated``."""
    t = u.shape[0]
    per = n_heads // n_kv
    qkv = (u @ p["qkv_proj"]["kernel"]).reshape(t, n_kv, per + 2, dh)
    q = qkv[:, :, :per].reshape(t, n_heads, dh).transpose(1, 0, 2)
    k = qkv[:, :, per].transpose(1, 0, 2)  # [n_kv, T, Dh]
    v = qkv[:, :, per + 1].transpose(1, 0, 2)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    k = jnp.repeat(k, per, axis=0)  # query head i uses kv head i // per
    v = jnp.repeat(v, per, axis=0)
    pos = jnp.arange(t)
    out = []
    for q0 in range(0, t, Q_BLOCK):
        qb = q[:, q0:q0 + Q_BLOCK]
        s = jnp.einsum("hqd,hkd->hqk", qb, k) / jnp.sqrt(jnp.float32(dh))
        back = pos[q0:q0 + Q_BLOCK, None] - pos[None, :]
        mask = back >= 0
        if window is not None:
            mask &= back < window
        s = jnp.where(mask[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(out, axis=1).transpose(1, 0, 2)  # [T, H, Dh]
    if gated:
        o = o * jax.nn.sigmoid(u @ p["gate_proj"]["kernel"])[..., None]
    return o.reshape(t, n_heads * dh) @ p["o_proj"]["kernel"]


def _gated_mlp(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _moe(x, p, *, top_k, first, scaling, routing):
    """x [T, D] -> (the held experts' part of the layer plus the shared
    expert [T, D], the reference's own chosen experts [T, k], margin [T]
    between the k-th and the (k+1)-th selection score)."""
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    sel = s + p["expert_bias"]
    ranked, own = jax.lax.top_k(sel, top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    topi = own[:, :top_k] if routing is None else routing
    w = jnp.take_along_axis(s, topi, axis=-1)
    w = w / w.sum(-1, keepdims=True) * scaling
    out = jnp.zeros_like(x)
    for j in range(p["experts_in_kernel"].shape[0]):
        # This expert's weight at every position (0 where it was not chosen).
        wj = jnp.where(topi == first + j, w, 0.0).sum(-1, keepdims=True)
        out = out + wj * _gated_mlp(
            x, p["experts_gate_kernel"][j], p["experts_in_kernel"][j],
            p["experts_out_kernel"][j])
    out = out + _gated_mlp(
        x, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
        p["shared_out"]["kernel"])
    return out, own[:, :top_k], margin


def bias_step(bias, topk, speed: float):
    """One balancing update of one layer's selection bias: ``bias`` [E],
    ``topk`` [..., k] the experts the step's rows chose (all E counted,
    held or not). float64 numpy."""
    load = np.bincount(
        np.asarray(topk).reshape(-1), minlength=bias.shape[0]
    ).astype(np.float64)
    return (np.asarray(bias, np.float64)
            + speed * np.sign(load.mean() - load))


def forward_one(params, x, routing, *, types, mlps, heads, n_kv, dh, window,
                ropes, gated, eps, top_k, first, scaling):
    """One sequence: params (float32), x [T, F], ``routing`` [M, T, k] or
    None (M = routed layers); the keywords are :func:`settings`'. Returns
    (logits [T, C], the reference's own chosen experts [M, T, k], margins
    [M, T]). Plain ``jax.numpy``, so it can be differentiated."""
    t = x.shape[0]
    tables = {kind: rotation(t, dh, ropes[kind]) for kind in set(types)}
    h = x @ params["in_proj"]["kernel"]
    chosen, margins = [], []
    for i, kind in enumerate(types):
        p = params[f"block_{i}"]
        cos, sin = tables[kind]
        h = h + _attention(
            _rms(h, p["ln_attn"]["scale"], eps), p["attn"],
            n_heads=heads[i], n_kv=n_kv, dh=dh, cos=cos, sin=sin,
            gated=gated,
            window=window if kind == "sliding_attention" else None)
        u = _rms(h, p["ln_ffn"]["scale"], eps)
        if mlps[i] == "dense":
            h = h + _gated_mlp(u, p["ffn_gate"]["kernel"],
                               p["ffn_in"]["kernel"], p["ffn_out"]["kernel"])
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


def objective(params, x, y, **kw):
    """Mean cross-entropy over every position of x [N, T, F] against y
    [N, T], differentiable (:func:`settings`' keywords)."""
    logits = jnp.stack([forward_one(params, xi, None, **kw)[0] for xi in x])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, jnp.asarray(y)[..., None], -1).mean()


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
    the first ``num_hidden_layers`` entries of the published per-layer
    lists."""
    n_layers = int(config["num_hidden_layers"])
    mlps = tuple(config["mlp_layer_types"][:n_layers])
    if set(mlps) - {"dense", "sparse"}:
        raise ValueError(f"mlp_layer_types {sorted(set(mlps))}")
    if config["gating"] not in (True, False, "per-head", "per_head"):
        raise ValueError(f"gating {config['gating']!r} is not written here")
    return dict(
        types=tuple(config["layer_types"][:n_layers]),
        mlps=mlps,
        heads=tuple(int(h) for h in
                    config["num_attention_heads_per_layer"][:n_layers]),
        n_kv=int(config["num_key_value_heads"]),
        dh=int(config["head_dim"]),
        window=int(config["sliding_window"]),
        ropes=config["rope_parameters"],
        gated=bool(config["gating"]),
        eps=float(config["rms_norm_eps"]),
        top_k=int(config["num_experts_per_tok"]),
        first=int(config["first_expert"]),
        scaling=float(config["moe_routed_scaling_factor"]),
    )


def forward_and_loss(params, x, y, config: dict, routing=None):
    """x [N, T, F] float32; y [N, T] int. Returns (logits [N, T, C]
    float32, mean CE)."""
    out = forward(params, x, config, routing)
    return out["logits"], cross_entropy(out["logits"], y)

"""Operations per token of the pre-LN GQA block stack (``"flops": "block"``
in a configuration file), from shapes. Kept with the benchmark so that no
PR that claims a gain can change the yardstick. Another family brings a
file of its own beside this one, with the same ``train_flops_per_token``.

Counted: what the forward and backward passes require (backward = 2 x
forward), multiply and add as two operations. Not counted: recomputation,
the optimizer, LayerNorm, softmax, GELU, RoPE, masking.
"""

from __future__ import annotations


def block_params(config: dict) -> dict:
    """Parameter counts of the stack as it is run: ``input_dim`` features
    projected in, two classes per position out."""
    input_dim, classes = int(config.get("input_dim", 5)), 2
    d = int(config["hidden_size"])
    ff = int(config["intermediate_size"])
    h = int(config["num_attention_heads"])
    kv = int(config["num_key_value_heads"])
    n = int(config["num_hidden_layers"])
    dh = d // h
    qkv = d * (h + 2 * kv) * dh + (h + 2 * kv) * dh
    o = d * d + d
    mlp = d * ff + ff + ff * d + d
    norms = 2 * 2 * d
    per_layer = qkv + o + mlp + norms
    ends = input_dim * d + d + 2 * d + d * classes + classes
    gemm = n * (d * (h + 2 * kv) * dh + d * d + 2 * d * ff) \
        + input_dim * d + d * classes
    return {"per_layer": per_layer, "total": n * per_layer + ends,
            "gemm_weights": gemm}


def attention_train_flops_per_token(config: dict, seq_len: int) -> float:
    """QK^T and PV are 2 x 2 x T_visible x d_model a token forward, x3 with
    the backward; a causal row sees (T + 1) / 2 keys on average, or the
    window where that is shorter."""
    d = int(config["hidden_size"])
    n = int(config["num_hidden_layers"])
    window = int(config.get("sliding_window") or 0)
    if window and window < seq_len:
        # Row t sees min(t + 1, window) keys.
        visible = (window * (window + 1) / 2
                   + (seq_len - window) * window) / seq_len
    else:
        visible = (seq_len + 1) / 2
    return 3.0 * 4.0 * visible * d * n


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """GEMMs: 6 x weights (2 forward, 4 backward) per token, plus attention."""
    return 6.0 * block_params(config)["gemm_weights"] \
        + attention_train_flops_per_token(config, seq_len)

"""Operations per token of the deepseek_v3 layer stack as it is run
(``"flops": "moonlight_moe"`` in a configuration file), from shapes.

Counted: what the forward and backward passes require (backward = 2 x
forward), multiply and add as two operations: each layer's latent-attention
projections (queries, the compressed key/value path down and up, the
output), QK^T over the whole query/key width (``qk_nope_head_dim +
qk_rope_head_dim``) and PV over ``v_head_dim``, both over the (T + 1) / 2
keys a causal row sees on average; the dense gated MLP's three matrices in
the leading layers; in the others the router, the shared expert (every
token) and the routed experts at the UNIFORM expectation of a token's
share: ``k x held / routed`` experts a token and layer (0.75 with 8 of 64
held and top-6). Not counted: recomputation (the flash backward forms the
scores a second time), zero padding of any operand, the optimizer, norms,
softmax, silu, the rotation, sorting and gathering rows.

``expert_train_flops(config, rows)`` is the yardstick of the grouped expert
products alone, for the rows the run's counters say were routed;
``attention_train_flops(config, seq_len)`` that of the attention kernels
alone, for one sequence.
"""

from __future__ import annotations


def moe_layers(config: dict) -> int:
    return int(config["num_hidden_layers"]) - int(
        config["first_k_dense_replace"])


def expert_weights(config: dict) -> int:
    """One routed expert: three matrices hidden x moe_intermediate."""
    return 3 * int(config["hidden_size"]) * int(
        config["moe_intermediate_size"])


def routed_experts(config: dict) -> int:
    """The router's width: what the configuration held before the cut."""
    return int(config["published"]["n_routed_experts"])


def attention_weights(config: dict) -> int:
    """One latent-attention layer's four projections."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    rank = int(config["kv_lora_rank"])
    nope, rope = int(config["qk_nope_head_dim"]), int(
        config["qk_rope_head_dim"])
    v = int(config["v_head_dim"])
    return (d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + v) + h * v * d)


def gemm_weights_per_token(config: dict) -> float:
    """Weights a token meets in one forward pass (each is one multiply-add)."""
    input_dim, classes = int(config.get("input_dim", 5)), 2
    d = int(config["hidden_size"])
    dense = int(config["first_k_dense_replace"])
    share = int(config["num_experts_per_tok"]) * int(
        config["n_routed_experts"]) / routed_experts(config)
    shared = 3 * d * int(config["n_shared_experts"]) * int(
        config["moe_intermediate_size"])
    total = float(input_dim * d + d * classes)
    total += int(config["num_hidden_layers"]) * attention_weights(config)
    total += dense * 3 * d * int(config["intermediate_size"])
    total += moe_layers(config) * (
        d * routed_experts(config) + share * expert_weights(config) + shared)
    return total


def attention_train_flops(config: dict, seq_len: int) -> float:
    """QK^T and PV of every layer for ONE sequence, forward and backward
    (3 x the forward): a causal row sees (T + 1) / 2 keys on average, a
    score costs 2 x (qk_nope + qk_rope) operations and a value row 2 x
    v_head_dim, a head."""
    widths = int(config["qk_nope_head_dim"]) + int(
        config["qk_rope_head_dim"]) + int(config["v_head_dim"])
    pairs = seq_len * (seq_len + 1) / 2
    return 3.0 * 2.0 * widths * pairs * int(
        config["num_attention_heads"]) * int(config["num_hidden_layers"])


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """GEMMs: 6 x weights (2 forward, 4 backward) per token, plus attention."""
    return 6.0 * gemm_weights_per_token(config) \
        + attention_train_flops(config, seq_len) / seq_len


def expert_train_flops(config: dict, rows: float) -> float:
    """The three grouped products of the routed experts over ``rows``
    routed rows, forward and backward: 3 GEMMs x 3 passes x 2."""
    return 6.0 * expert_weights(config) * rows

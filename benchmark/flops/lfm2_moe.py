"""Operations per token of the lfm2_moe layer stack as it is run
(``"flops": "lfm2_moe"`` in a configuration file), from shapes.

Counted: what the forward and backward passes require (backward = 2 x
forward), multiply and add as two operations: the GEMMs of each layer's
operator (the conv operator's two projections, or attention's fused q/k/v
and output projections with QK^T and PV over the (T + 1) / 2 keys a causal
row sees on average), the convolution's taps, the dense gated MLP's three
matrices in the leading layers, and in the others the router and the
routed experts at the UNIFORM expectation of a token's share: ``k x held /
routed`` experts a token and layer (0.5 with 8 of 64 held and top-4). Not
counted: recomputation, the optimizer, norms, softmax, silu, the rotation,
sorting and gathering rows.

``expert_train_flops(config, rows)`` is the yardstick of the grouped expert
products alone, for the rows the run's counters say were routed.
"""

from __future__ import annotations


def layers(config: dict) -> list[tuple[str, bool]]:
    """(operator, has routed experts) of every layer that is run."""
    types = [config["layer_types"][i] for i in config["layers_run"]]
    dense = int(config["num_dense_layers"])
    return [(t, i >= dense) for i, t in enumerate(types)]


def expert_weights(config: dict) -> int:
    """One routed expert: three matrices hidden x moe_intermediate."""
    return 3 * int(config["hidden_size"]) * int(
        config["moe_intermediate_size"])


def routed_experts(config: dict) -> int:
    """The router's width: what the configuration held before the cut."""
    return int(config["published"]["num_experts"])


def gemm_weights_per_token(config: dict) -> float:
    """Weights a token meets in one forward pass (each is one multiply-add)."""
    input_dim, classes = int(config.get("input_dim", 5)), 2
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    kv = int(config["num_key_value_heads"])
    dh = d // h
    taps = int(config["conv_L_cache"])
    held = int(config["num_experts"])
    routed = routed_experts(config)
    share = int(config["num_experts_per_tok"]) * held / routed
    total = float(input_dim * d + d * classes)
    for op, moe in layers(config):
        if op == "conv":
            total += d * 3 * d + d * d + taps * d
        else:
            total += d * (h + 2 * kv) * dh + d * d
        if moe:
            total += d * routed + share * expert_weights(config)
        else:
            total += 3 * d * int(config["intermediate_size"])
    return total


def attention_train_flops_per_token(config: dict, seq_len: int) -> float:
    """QK^T and PV are 2 x 2 x T_visible x hidden a token forward, x3 with
    the backward; a causal row sees (T + 1) / 2 keys on average."""
    n_attn = sum(1 for op, _ in layers(config) if op == "full_attention")
    return 3.0 * 4.0 * (seq_len + 1) / 2 * int(config["hidden_size"]) * n_attn


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """GEMMs: 6 x weights (2 forward, 4 backward) per token, plus attention."""
    return 6.0 * gemm_weights_per_token(config) \
        + attention_train_flops_per_token(config, seq_len)


def expert_train_flops(config: dict, rows: float) -> float:
    """The three grouped products of the routed experts over ``rows``
    routed rows, forward and backward: 3 GEMMs x 3 passes x 2."""
    return 6.0 * expert_weights(config) * rows

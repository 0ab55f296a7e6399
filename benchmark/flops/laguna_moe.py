"""Operations per token of the laguna layer stack as it is run (``"flops":
"laguna_moe"`` in a configuration file), from shapes.

Counted: what the forward and backward passes require (backward = 2 x
forward), multiply and add as two operations: each layer's attention
projections at its own head count (``num_attention_heads_per_layer``: the
fused query/key/value projection, the heads' gate and the output
projection), QK^T and PV over the keys a row SEES, its kind of layer
deciding: a ``sliding_attention`` row at position t sees ``min(t + 1,
sliding_window)`` keys, a ``full_attention`` row ``t + 1``; the dense
gated MLP of the ``dense`` layers; in the ``sparse`` layers the router,
the shared expert and the routed experts at the UNIFORM expectation of a
token's share: ``k x held / routed`` experts a token and layer (0.5 with 16
of 256 held and top-8). Not counted: recomputation (the flash backward
forms the scores a second time), what a kernel executes outside the band or
above the diagonal because its tiles are wider than the edge, the
optimizer, norms, softmax, sigmoid, silu, the rotation, sorting and
gathering rows.

``expert_train_flops(config, rows)`` is the yardstick of the grouped expert
products alone, for the rows the run's counters say were routed;
``sliding_train_flops(config, seq_len)`` / ``full_train_flops`` that of the
attention kernels of the layers under the window and of the full ones, for
one sequence.
"""

from __future__ import annotations


def layers(config: dict) -> list:
    """(kind, query heads, mlp) of each layer that is run: the head of the
    published per-layer lists."""
    n = int(config["num_hidden_layers"])
    return list(zip(config["layer_types"][:n],
                    config["num_attention_heads_per_layer"][:n],
                    config["mlp_layer_types"][:n]))


def expert_weights(config: dict) -> int:
    """One routed expert: three matrices hidden x moe_intermediate."""
    return 3 * int(config["hidden_size"]) * int(
        config["moe_intermediate_size"])


def routed_experts(config: dict) -> int:
    """The router's width: what the configuration held before the cut."""
    return int(config["published"]["num_experts"])


def attention_weights(config: dict, heads: int) -> int:
    """One layer of ``heads`` query heads: the fused q/k/v projection, the
    heads' gate and the output projection."""
    d, dh = int(config["hidden_size"]), int(config["head_dim"])
    g = int(config["num_key_value_heads"])
    return d * (heads + 2 * g) * dh + d * heads + heads * dh * d


def mlp_weights(config: dict, mlp: str) -> float:
    """Weights a token meets in one layer's MLP: the dense gated MLP, or
    the router, the shared expert and its share of the routed ones."""
    d = int(config["hidden_size"])
    if mlp == "dense":
        return 3.0 * d * int(config["intermediate_size"])
    share = int(config["num_experts_per_tok"]) * int(
        config["num_experts"]) / routed_experts(config)
    return d * routed_experts(config) + 3.0 * d * int(
        config["shared_expert_intermediate_size"]) \
        + share * expert_weights(config)


def gemm_weights_per_token(config: dict) -> float:
    """Weights a token meets in one forward pass (each is one multiply-add)."""
    input_dim, classes = int(config.get("input_dim", 5)), 2
    d = int(config["hidden_size"])
    return float(input_dim * d + d * classes) + sum(
        attention_weights(config, int(heads)) + mlp_weights(config, mlp)
        for _, heads, mlp in layers(config))


def seen_pairs(seq_len: int, window: int | None) -> float:
    """(query, key) pairs of one head over one sequence: row t sees ``t +
    1`` keys, ``min(t + 1, window)`` under a window."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) / 2
    return window * (window + 1) / 2 + (seq_len - window) * window


def _kind_train_flops(config: dict, seq_len: int, kind: str) -> float:
    """QK^T and PV of the layers of one kind for ONE sequence, forward and
    backward (3 x the forward): a score and a value row each cost 2 x
    head_dim operations a head, at each layer's own head count."""
    window = int(config["sliding_window"]) \
        if kind == "sliding_attention" else None
    heads = sum(int(h) for k, h, _ in layers(config) if k == kind)
    return 3.0 * 2.0 * 2 * int(config["head_dim"]) * seen_pairs(
        seq_len, window) * heads


def sliding_train_flops(config: dict, seq_len: int) -> float:
    return _kind_train_flops(config, seq_len, "sliding_attention")


def full_train_flops(config: dict, seq_len: int) -> float:
    return _kind_train_flops(config, seq_len, "full_attention")


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """GEMMs: 6 x weights (2 forward, 4 backward) per token, plus attention."""
    return 6.0 * gemm_weights_per_token(config) + (
        sliding_train_flops(config, seq_len)
        + full_train_flops(config, seq_len)) / seq_len


def expert_train_flops(config: dict, rows: float) -> float:
    """The three grouped products of the routed experts over ``rows``
    routed rows, forward and backward: 3 GEMMs x 3 passes x 2."""
    return 6.0 * expert_weights(config) * rows

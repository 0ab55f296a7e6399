"""flops/block.py and peaks.py against numbers worked out here, by hand,
for the configuration."""

import pytest

from benchmark import peaks
from benchmark.manifest import BENCH_DIR, load_flops, load_json

CFG = load_json(f"{BENCH_DIR}/configs/sc2_3b_block.json")
flops = load_flops(CFG)


def test_operation_counts_are_found_by_the_name_in_the_configuration():
    assert CFG["flops"] == "block"
    assert flops.__file__.endswith("benchmark/flops/block.py")
    assert load_flops({}) is None


def test_block_parameters_by_hand():
    # Per layer: fused qkv 3072 x (24 + 2*2) x 128 (+ bias), o 3072^2 (+ bias),
    # MLP 2 x 3072 x 12288 (+ biases), two LayerNorms (scale and bias).
    qkv = 3072 * 3584 + 3584
    o = 3072 * 3072 + 3072
    mlp = 3072 * 12288 + 12288 + 12288 * 3072 + 3072
    norms = 4 * 3072
    per_layer = qkv + o + mlp + norms
    assert per_layer == 95_979_008
    ends = 5 * 3072 + 3072 + 2 * 3072 + 3072 * 2 + 2
    got = flops.block_params(CFG)
    assert got["per_layer"] == per_layer
    assert got["total"] == CFG["num_hidden_layers"] * per_layer + ends


def test_step_operations_by_hand():
    n = CFG["num_hidden_layers"]
    weights = n * (3072 * 3584 + 3072 * 3072 + 2 * 3072 * 12288) \
        + 5 * 3072 + 3072 * 2
    # seq 4096 under a 4096 window: full causal, (T + 1) / 2 keys a row.
    attn = 3 * 4 * (4097 / 2) * 3072 * n
    per_token = 6 * weights + attn
    assert flops.train_flops_per_token(CFG, 4096) == pytest.approx(per_token)
    # The issue's figure: 2.1e13 operations for 8,192 tokens at depth 4.
    if n == 4:
        assert per_token * 8192 == pytest.approx(2.134e13, rel=1e-3)
    attn512 = 3 * 4 * (513 / 2) * 3072 * n
    assert flops.train_flops_per_token(CFG, 512) == pytest.approx(
        6 * weights + attn512)


def test_window_shorter_than_sequence():
    cfg = {**CFG, "sliding_window": 4}
    # Rows see 1, 2, 3, 4, 4, 4, 4, 4 keys: 26 / 8.
    d, n = 3072, cfg["num_hidden_layers"]
    base = flops.train_flops_per_token({**cfg, "sliding_window": 0}, 8) \
        - 3 * 4 * (9 / 2) * d * n
    assert flops.train_flops_per_token(cfg, 8) == pytest.approx(
        base + 3 * 4 * (26 / 8) * d * n)


def test_peaks_table_refuses_unknown_kind():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks("source")
    # 197e12 operations a second on one chip is 100%.
    assert peaks.mfu(1000.0, 197e9, "TPU v5 lite", 1) == pytest.approx(1.0)
    assert peaks.mfu(1000.0, 197e9, "TPU v5 lite", 4) == pytest.approx(0.25)

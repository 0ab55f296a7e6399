"""The plain reference against the program's model, in float32 on the CPU at
a tiny size: tight enough that a wrong mask, pairing, head grouping or
window would fail by orders of magnitude."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import BENCH_DIR, load_module

REF = load_module(f"{BENCH_DIR}/reference/block.py", "bench_reference_test")


@pytest.mark.parametrize("seq,window", [(64, 64), (64, 16), (48, 0)])
def test_reference_matches_the_program_in_float32(seq, window):
    from dct_tpu.config import ModelConfig
    from dct_tpu.models.registry import get_model

    cfg = ModelConfig(
        name="weather_transformer_causal", d_model=32, n_heads=4,
        n_kv_heads=2, n_layers=2, d_ff=64, seq_len=seq, attn_window=window,
        pos_embed="rope", dropout=0.1,
    )
    model = get_model(cfg, input_dim=5, compute_dtype=jnp.float32)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, seq, 5)).astype(np.float32)
    y = rng.integers(0, 2, (2, seq)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), jnp.asarray(x[:1]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(x), train=False))
    config = {
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "sliding_window": window,
        "rope_theta": 10000.0, "norm_epsilon": 1e-6,
    }
    want, loss = REF.forward_and_loss(
        jax.device_get(params)["params"], x, y, config)
    assert got.shape == want.shape == (2, seq, 2)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    assert np.isfinite(loss)
    # A wrong window must be visible at this tolerance.
    if window and window < seq:
        other, _ = REF.forward_and_loss(
            jax.device_get(params)["params"], x, y,
            {**config, "sliding_window": 0})
        assert np.abs(other - want).max() > 1e-3

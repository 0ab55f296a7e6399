"""The flagship rain-classifier MLP, TPU-native.

Capability parity with the reference's ``WeatherClassifier``
(jobs/train_lightning_ddp.py:51-88): Linear(input_dim, hidden) -> ReLU ->
Dropout(p) -> Linear(hidden, num_classes), trained with cross entropy.

Differences by design:
- a pure ``flax.linen`` module: parameters are an explicit pytree, dropout
  randomness is an explicit rng — no module-held mutable state, so the whole
  train step jits and shards;
- compute dtype is configurable (bf16 on the MXU; params stay f32);
- initialization matches torch ``nn.Linear`` defaults
  (U(-1/sqrt(fan_in), 1/sqrt(fan_in)) for both kernel and bias) so the loss
  trajectory starts in the same band as the reference for parity checks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn


def torch_linear_init(scale_by_fan_in: bool = True):
    """torch nn.Linear default init: kaiming_uniform(a=sqrt(5)) on the kernel
    reduces to U(-1/sqrt(fan_in), 1/sqrt(fan_in)); bias uses the same bound."""

    def init(key, shape, dtype=jnp.float32, fan_in: int | None = None):
        # flax kernel shape is (fan_in, fan_out); bias callers pass fan_in.
        f = fan_in if fan_in is not None else shape[0]
        bound = 1.0 / jnp.sqrt(jnp.asarray(f, jnp.float32))
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class TorchStyleDense(nn.Module):
    """Dense layer with torch nn.Linear's default initialization."""

    features: int
    dtype: jnp.dtype | None = None
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        fan_in = x.shape[-1]
        kernel = self.param(
            "kernel", torch_linear_init(), (fan_in, self.features), jnp.float32
        )
        dtype = self.dtype or x.dtype
        y = jnp.asarray(x, dtype) @ jnp.asarray(kernel, dtype)
        if not self.use_bias:
            return y
        bias = self.param(
            "bias",
            lambda k, s, d=jnp.float32: torch_linear_init()(k, s, d, fan_in=fan_in),
            (self.features,),
            jnp.float32,
        )
        return y + jnp.asarray(bias, dtype)


class WeatherMLP(nn.Module):
    """MLP rain classifier; logits are always returned in float32."""

    input_dim: int
    hidden_dim: int = 64
    num_classes: int = 2
    dropout: float = 0.2
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = jnp.asarray(x, self.compute_dtype)
        x = TorchStyleDense(self.hidden_dim, dtype=self.compute_dtype)(x)
        x = nn.relu(x)
        x = nn.Dropout(rate=self.dropout, deterministic=not train)(x)
        x = TorchStyleDense(self.num_classes, dtype=self.compute_dtype)(x)
        return jnp.asarray(x, jnp.float32)

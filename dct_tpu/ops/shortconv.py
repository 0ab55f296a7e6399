"""Gated short convolution: the LFM2-style operator that stands where
attention stands in a block.

    B, C, X = split3(u @ W_in)          (the caller's projection)
    z = B * X
    c[t] = sum_j k[:, j] * z[t - (K - 1) + j]     depthwise, causal
    out = C * c                          (then the caller's W_out)

The convolution is per channel, ``K`` taps wide (3 in the published
models), causal with zeros before t = 0: ``K`` shifted multiply-adds over
the sequence axis, which XLA fuses into one elementwise pass. No kernel,
no state: the training path sees whole windows.
"""

from __future__ import annotations

import jax.numpy as jnp


def causal_depthwise_conv(z, taps):
    """z [B, T, D], taps [D, K] -> [B, T, D]; output at t reads
    z[t - K + 1 .. t], tap K - 1 on the newest input."""
    k = taps.shape[-1]
    t = z.shape[1]
    padded = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
    out = padded[:, :t] * taps[:, 0]
    for j in range(1, k):
        out = out + padded[:, j:j + t] * taps[:, j]
    return out


def gated_short_conv(bcx, taps):
    """bcx [B, T, 3D] (the input projection's output), taps [D, K] ->
    [B, T, D], before the output projection."""
    b, c, x = jnp.split(bcx, 3, axis=-1)
    return c * causal_depthwise_conv(b * x, taps)

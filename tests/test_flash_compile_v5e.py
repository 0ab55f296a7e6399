"""The three Mosaic flash kernels, compiled for a described v5e at the
benchmark's real shapes on the tiles the shape rule picks.

Interpret mode accepts any tile; Mosaic refuses one that is misaligned or
needs more scoped VMEM than a kernel may use. The TPU compiler is installed
here and compiles for a chip that is described, not attached, so a tile
rule that the chip would refuse fails this file at no chip time. Nothing
runs: a compile that passes is not a chip run (PERF.md section 6 has those).

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports every
test file. Keep these tests in this one file for the same reason.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from dct_tpu.ops import pallas_attention as pa

#: (b, q heads, kv heads, T, d, window[, value width]): BENCHMARK.json's
#: sc2_3b cells at 4,096 and 512 positions, its lfm2 cell (head size 64,
#: 8,192 positions, full causal) and its moonlight cell (latent attention:
#: queries and keys 192 wide, values 128), then a length whose one dividing
#: tile is no power of two (640 = 5 x 128: the rule picks the 640-row tile).
SHAPES = {
    "sc2_3b_seq4096": (2, 24, 2, 4096, 128, 4096),
    "sc2_3b_seq512": (16, 24, 2, 512, 128, 4096),
    "lfm2_24b_seq8192": (1, 32, 8, 8192, 64, None),
    "moonlight_16b_seq8192": (1, 16, 16, 8192, 192, None, 128),
    "odd_seq640": (1, 4, 2, 640, 128, None),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler, no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _operands(shape, sharding):
    b, h, h_kv, t, d, _, *rest = shape
    d_v = rest[0] if rest else d
    bf16 = jnp.bfloat16

    def arg(dims, dtype=bf16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    q, k, v = arg((b, h, t, d)), arg((b, h_kv, t, d)), arg((b, h_kv, t, d_v))
    o = arg((b, h, t, d_v))
    return q, k, v, o, arg((b, h, t), jnp.float32), o  # q k v o lse do


@pytest.mark.parametrize("kernel", ["fwd", "fwd_no_lse", "dkdv", "dq"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_compiles_for_v5e_on_the_rules_tiles(
        one_chip, no_compile_cache, shape, kernel):
    dims = SHAPES[shape]
    t, d, window = dims[3], dims[4], dims[5]
    kw = dict(causal=True, scale=None, interpret=False, window=window)
    q, k, v, o, lse, do = _operands(dims, one_chip)
    if kernel.startswith("fwd"):
        with_lse = kernel == "fwd"  # training forward; validation has none
        fn = lambda q, k, v: pa._flash_fwd(q, k, v, with_lse=with_lse, **kw)
        args = (q, k, v)
    else:
        entry = pa._flash_bwd_dkdv if kernel == "dkdv" else pa._flash_bwd_dq
        fn = lambda *a: entry(*a, **kw)
        args = (q, k, v, o, lse, do)
    tiles = pa.flash_tiles(t, t, d, jnp.bfloat16, *dims[6:])
    assert all(tile % 128 == 0 and t % tile == 0 for tile in tiles)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text

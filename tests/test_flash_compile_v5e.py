"""What only the TPU's compiler can say, at no chip time: the three Mosaic
flash kernels compiled for a described v5e at the benchmark's real shapes
on the tiles the shape rule picks, and (last in the file) where the
compiler puts residual dropout's bit generation in a block's training step.

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
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler, no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

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


def _computations(text):
    """The optimized module's computations, by name: the lines of each."""
    out, name = {}, None
    for line in text.splitlines():
        if name is None and line.endswith("{") and ") -> " in line:
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            out[name] = []
        elif name is not None and line == "}":
            name = None
        elif name is not None:
            out[name].append(line)
    return out


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "data4"])
def test_dropout_bits_are_drawn_outside_the_gemm_fusions(
        topo, no_compile_cache, chips):
    """A block's training step at rate 0.1 (PERF.md section 6, PR 34):
    no fused computation holds a threefry round (its ``xor``s) beside a
    convolution, so no GEMM generates bits again for every tile pass (with
    ``nn.Dropout`` in the block's place two of this step's do); on
    ``data=4`` each chip draws its own shard of a mask and no collective
    carries one."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dct_tpu.models.transformer import TransformerBlock
    from dct_tpu.ops.attention import make_attention_fn

    mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
    batch, replicated = (
        NamedSharding(mesh, P("data")), NamedSharding(mesh, P()))
    b, t, d = 8, 256, 256
    block = TransformerBlock(
        d, 4, 4 * d, 0.1, make_attention_fn(None), dtype=jnp.bfloat16)

    def loss(params, x, key):
        out = block.apply(params, x, True, rngs={"dropout": key})
        return out.astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((b, t, d), jnp.bfloat16, sharding=batch)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
        jax.eval_shape(
            lambda: block.init(jax.random.PRNGKey(0), jnp.zeros((1, t, d)))))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated)
    text = jax.jit(jax.value_and_grad(loss)).lower(
        params, x, key).compile().as_text()
    fused = {
        name: lines for name, lines in _computations(text).items()
        if "fused_computation" in name}
    drawing = {
        name for name, lines in fused.items()
        if any(" xor(" in line for line in lines)}
    assert drawing, "no threefry round in any fusion: the probe is blind"
    assert not {
        name for name in drawing
        if any(" convolution(" in line for line in fused[name])}
    # The masks the step holds are one chip's rows of them.
    masks = {
        line.split(" = ")[1].split("{")[0] for lines in fused.values()
        for line in lines if " = pred[" in line and line.count(",") >= 2}
    assert f"pred[{b // chips},{t},{d}]" in masks
    assert (f"pred[{b},{t},{d}]" in masks) == (chips == 1)
    collectives = [
        line for line in text.splitlines()
        if any(f" {op}(" in line or f" {op}-start(" in line for op in (
            "all-reduce", "all-gather", "all-to-all", "collective-permute",
            "reduce-scatter"))]
    assert bool(collectives) == (chips > 1)
    assert not [
        line for line in collectives
        if "pred[" in line or f"u32[{b // chips},{t}" in line]

"""Attention-path selection policy + ring-with-flash numerics.

VERDICT r1 item 2: the Pallas flash kernel must be the PRODUCT's attention
path, not a demo — ``make_attention_fn`` selects it for long single-shard
sequences on the TPU backend (interpret mode when a CPU rig opts in via
``DCT_FLASH=interpret``), and ring attention's per-shard block compute can
run through it. These tests pin the selection table and the flash-in-ring
numerics against the dense oracle on the 8-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dct_tpu.ops.attention import (
    dense_attention,
    make_attention_fn,
    ring_attention,
    select_attention_path,
)
from dct_tpu.parallel.mesh import make_mesh
from dct_tpu.config import MeshConfig


def test_selection_default_cpu(monkeypatch):
    """On a CPU backend with no opt-in, flash never selects (interpret mode
    is far slower than XLA blockwise); long sequences go blockwise."""
    monkeypatch.delenv("DCT_FLASH", raising=False)
    assert select_attention_path(64) == "dense"
    assert select_attention_path(1024) == "blockwise"
    assert select_attention_path(512) == "dense"  # not > block_size


def test_selection_interpret_opt_in(monkeypatch):
    monkeypatch.setenv("DCT_FLASH", "interpret")
    assert select_attention_path(256) == "flash"
    assert select_attention_path(1024) == "flash"
    assert select_attention_path(64) == "dense"  # below flash_min_len
    assert select_attention_path(320) == "dense"  # not 128-aligned


def test_selection_tpu_backend(monkeypatch):
    """On a TPU backend the Mosaic kernel selects by default ('auto')."""
    monkeypatch.delenv("DCT_FLASH", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert select_attention_path(1024) == "flash"
    monkeypatch.setenv("DCT_FLASH", "off")
    assert select_attention_path(1024) == "blockwise"


def test_selection_ring_wins(monkeypatch):
    monkeypatch.setenv("DCT_FLASH", "interpret")
    mesh = make_mesh(MeshConfig(data=2, seq=4))
    assert select_attention_path(1024, mesh=mesh) == "ring"


def test_make_attention_fn_flash_matches_dense(monkeypatch, rng):
    monkeypatch.setenv("DCT_FLASH", "interpret")
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 2, 256, 16)), jnp.float32)
        for _ in range(3)
    )
    attn = make_attention_fn(None)
    out = attn(q, k, v)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_dense(monkeypatch, rng, causal):
    """Ring attention with the flash per-shard block (2-device seq ring,
    128-aligned local shards) equals the dense oracle."""
    monkeypatch.setenv("DCT_FLASH", "interpret")
    mesh = make_mesh(MeshConfig(data=2, model=2, seq=2))
    b, h, t, d = 2, 2, 256, 16
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
        for _ in range(3)
    )
    out = ring_attention(q, k, v, mesh=mesh, causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ring_flash_grad_matches_dense(monkeypatch, rng):
    monkeypatch.setenv("DCT_FLASH", "interpret")
    mesh = make_mesh(MeshConfig(data=2, model=2, seq=2))
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 2, 256, 8)), jnp.float32)
        for _ in range(3)
    )

    def loss_ring(q, k, v):
        return ring_attention(q, k, v, mesh=mesh, causal=True).sum()

    def loss_dense(q, k, v):
        return dense_attention(q, k, v, causal=True).sum()

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd), atol=1e-3)


def test_ring_use_flash_false_disables(monkeypatch, rng):
    """use_flash=False must mean 'no flash' — the JAX ring body runs even
    when the policy would select flash (and would crash Mosaic-on-CPU)."""
    monkeypatch.setenv("DCT_FLASH", "interpret")
    mesh = make_mesh(MeshConfig(data=2, model=2, seq=2))
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 2, 256, 8)), jnp.float32)
        for _ in range(3)
    )
    out = ring_attention(q, k, v, mesh=mesh, causal=True, use_flash=False)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_use_flash_true_forces_interpret_on_cpu(monkeypatch, rng):
    """use_flash=True on a CPU backend resolves to interpret mode instead
    of crashing on an unsupported Mosaic compile."""
    monkeypatch.setenv("DCT_FLASH", "off")
    mesh = make_mesh(MeshConfig(data=2, model=2, seq=2))
    # Batch must tile the data axis: eager undersized batches now raise
    # rather than silently densifying (ADVICE r3), so this exercises the
    # real ring path.
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 2, 256, 8)), jnp.float32)
        for _ in range(3)
    )
    out = ring_attention(q, k, v, mesh=mesh, use_flash=True)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ring_unaligned_falls_back(monkeypatch, rng):
    """A local shard not 128-aligned silently uses the JAX-level ring body
    — same numerics, no crash."""
    monkeypatch.setenv("DCT_FLASH", "interpret")
    mesh = make_mesh(MeshConfig(data=2, model=2, seq=2))
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 2, 64, 8)), jnp.float32)
        for _ in range(3)
    )
    out = ring_attention(q, k, v, mesh=mesh, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("t", [384, 640])
def test_every_aligned_length_takes_the_kernel(monkeypatch, rng, t):
    """A length whose only 128-multiple divisors are small (384 = 3 x 128,
    640 = 5 x 128) takes the kernel on a tile that divides it — it neither
    falls to blockwise nor crashes inside the kernel."""
    monkeypatch.setenv("DCT_FLASH", "interpret")
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 2, t, 8)), jnp.float32)
        for _ in range(3)
    )
    attn = make_attention_fn(None, causal=True)
    assert "pallas_call" in str(jax.make_jaxpr(attn)(q, k, v))
    out = attn(q, k, v)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("t", [256, 384, 512, 640, 1024, 4096, 16384])
def test_flash_tile_rule_table(monkeypatch, t):
    """The shape rule, pinned: the tiles are multiples of 128 that divide
    T, the largest such under the cap, and the policy still sends T to the
    kernel. Wider operand rows (f32, or a head of 256) halve the cap and
    never drop under 128."""
    from dct_tpu.ops.pallas_attention import _TILE_CAP, flash_tiles

    monkeypatch.setenv("DCT_FLASH", "interpret")
    assert select_attention_path(t) == "flash"
    tiles = flash_tiles(t, t, 128, jnp.bfloat16)
    for tile in tiles:
        assert tile % 128 == 0 and t % tile == 0 and tile <= _TILE_CAP
        assert not any(
            t % c == 0 for c in range(tile + 128, min(t, _TILE_CAP) + 1, 128)
        )
    wide = flash_tiles(t, t, 128, jnp.float32)
    assert wide == flash_tiles(t, t, 256, jnp.bfloat16)
    for tile, narrow in zip(wide, tiles):
        assert tile % 128 == 0 and t % tile == 0
        assert 128 <= tile <= _TILE_CAP // 2 and tile <= narrow
    assert min(flash_tiles(t, t, 1024, jnp.float32)) >= 128
    # A smaller head than the measured one never raises the cap.
    assert flash_tiles(t, t, 64, jnp.bfloat16) == tiles


def test_flash_tile_rule_benchmark_shapes_and_short_extents():
    """The tiles the benchmark's two shapes run (PERF.md section 6, PR 26),
    rectangular extents, and extents under or off the 128 grid: a short
    sequence is one tile, a long unaligned one keeps 128 and is refused by
    the kernel's divisibility check."""
    from dct_tpu.ops.pallas_attention import flash_attention, flash_tiles

    bf16 = jnp.bfloat16
    assert flash_tiles(4096, 4096, 128, bf16) == (1024, 1024)
    assert flash_tiles(512, 512, 128, bf16) == (512, 512)
    assert flash_tiles(384, 384, 128, bf16) == (384, 384)
    assert flash_tiles(640, 640, 128, bf16) == (640, 640)
    assert flash_tiles(256, 2048, 128, bf16) == (256, 1024)
    assert flash_tiles(4096, 4096, 192, bf16) == (512, 512)
    assert flash_tiles(64, 48, 16, bf16) == (64, 48)
    assert flash_tiles(320, 320, 16, bf16) == (128, 128)
    q = jnp.zeros((1, 1, 320, 8), jnp.float32)
    with pytest.raises(ValueError, match="pad upstream"):
        flash_attention(q, q, q, interpret=True)


def test_flash_under_a_mesh_runs_per_shard(monkeypatch, rng):
    """GSPMD cannot partition a Mosaic kernel (the four-chip smoke died
    on exactly that), so under a multi-device mesh the single-shard
    flash path runs inside a full-manual shard_map: batch over data,
    heads over model — same numbers, forward and backward, and the
    traced program holds the shard_map."""
    monkeypatch.setenv("DCT_FLASH", "interpret")
    mesh = make_mesh(MeshConfig(data=4, model=2))
    q, k, v = (
        jnp.asarray(rng.standard_normal((4, 2, 256, 32)), jnp.float32)
        for _ in range(3)
    )
    meshed = make_attention_fn(mesh, causal=True)
    single = make_attention_fn(None, causal=True)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    jaxpr = str(jax.make_jaxpr(meshed)(q, k, v))
    assert "shard_map" in jaxpr and "pallas_call" in jaxpr
    got = jax.jit(jax.value_and_grad(loss(meshed), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(loss(single), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
    # A batch that does not tile the data axis is a sizing bug, said so.
    with pytest.raises(ValueError, match="do not tile mesh axes"):
        meshed(q[:3], k[:3], v[:3])
    # The batch-1 init trace takes a JAX-level path instead.
    one = jax.jit(meshed)(q[:1], k[:1], v[:1])
    np.testing.assert_allclose(
        one, dense_attention(q[:1], k[:1], v[:1], causal=True),
        rtol=2e-5, atol=2e-5,
    )

"""The grouped matmul kernels (``ops/grouped_matmul.py``) in interpret mode
against XLA's ``ragged_dot``, their tile rule, the grouped MoE engine on
them against the engine on ``ragged_dot``, and the ``moe_rows_tiled``
counter against a count made by hand."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dct_tpu.models import moe
from dct_tpu.models.moe import MoEFFN, _chunked_moe
from dct_tpu.ops import grouped_matmul as gm

#: Group sizes over m = 384 rows (three row tiles of 128): an empty group
#: first and in the middle, a group that crosses two tile boundaries, one
#: that starts and ends inside a tile, the rows past the groups left over.
SIZES = {
    "tail_left": [0, 100, 0, 170, 40, 31],          # 341 of 384
    "every_row": [128, 0, 200, 56, 0, 0],           # 384: empty groups last
    "one_group": [0, 0, 0, 384, 0, 0],
    "nothing": [0, 0, 0, 0, 0, 0],
}
M, K, N = 384, 256, 128


def _operands(dtype, sizes, seed=0):
    rng = np.random.default_rng(seed)
    sizes = jnp.asarray(sizes, jnp.int32)
    routed = int(sizes.sum())
    valid = (np.arange(M) < routed)[:, None]
    # Rows past the groups hold NaN: nothing may read them.
    lhs = np.where(valid, rng.standard_normal((M, K)), np.nan)
    d = np.where(valid, rng.standard_normal((M, N)), np.nan)
    w = rng.standard_normal((len(sizes), K, N))
    return (jnp.asarray(lhs, dtype), jnp.asarray(d, dtype),
            jnp.asarray(w, dtype), sizes, valid)


def _tol(dtype):
    # bf16 operands: both sides round the f32 sum once, to 8 bits.
    return 1e-4 if dtype == jnp.float32 else 2e-2


def _close(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(1.0, float(np.abs(want).max()))
    np.testing.assert_allclose(got, want, rtol=0, atol=_tol(dtype) * scale)


@pytest.mark.parametrize("tiles", [None, (128, 128, 128), (128, 256, 128)])
@pytest.mark.parametrize("case", list(SIZES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_equals_ragged_dot_on_the_groups(dtype, case, tiles):
    lhs, _, w, sizes, valid = _operands(dtype, SIZES[case])
    clean = jnp.where(valid, lhs, 0)
    with jax.default_matmul_precision("highest"):
        want = lax.ragged_dot(clean, w, sizes)
    got = gm.gmm(lhs, w, sizes, tiles=tiles, interpret=True)
    assert got.dtype == want.dtype == dtype
    # Past the groups the output is the buffer's: compared on the groups.
    _close(jnp.where(valid, got, 0), jnp.where(valid, want, 0), dtype)


@pytest.mark.parametrize("case", list(SIZES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_t_reads_the_stack_transposed(dtype, case):
    lhs, _, w, sizes, valid = _operands(dtype, SIZES[case], seed=1)
    w_t = jnp.swapaxes(w, 1, 2)  # [G, N, K]: contract on its last axis
    with jax.default_matmul_precision("highest"):
        want = lax.ragged_dot(jnp.where(valid, lhs, 0), w, sizes)
        xla = gm._ragged(jnp.where(valid, lhs, 0), w_t, sizes, True)
    got = gm.gmm(lhs, w_t, sizes, transpose_rhs=True, interpret=True)
    _close(jnp.where(valid, got, 0), jnp.where(valid, want, 0), dtype)
    _close(jnp.where(valid, xla, 0), jnp.where(valid, want, 0), dtype)


@pytest.mark.parametrize("tiles", [None, (128, 128, 128), (128, 256, 128)])
@pytest.mark.parametrize("case", list(SIZES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tgmm_is_the_transpose_of_ragged_dot(dtype, case, tiles):
    lhs, d, w, sizes, valid = _operands(dtype, SIZES[case], seed=2)
    clean_lhs, clean_d = jnp.where(valid, lhs, 0), jnp.where(valid, d, 0)
    with jax.default_matmul_precision("highest"):
        want = jax.linear_transpose(
            lambda w: lax.ragged_dot(clean_lhs, w, sizes), w)(clean_d)[0]
    got = gm.tgmm(lhs, d, sizes, tiles=tiles, interpret=True)
    assert got.shape == w.shape and got.dtype == dtype
    _close(got, want, dtype)
    # Every group gets its block, an empty one zeros: the output holds
    # all G groups, none left to a caller.
    empty = np.asarray(sizes) == 0
    assert not np.asarray(got, np.float32)[empty].any()


def test_the_products_differentiate_through_the_kernels(monkeypatch):
    """``grouped_product`` on the kernel path carries its own gradient
    (``gmm`` / ``gmm_t`` for the rows, ``tgmm`` for the stack), equal to
    autodiff through ``ragged_dot``."""
    lhs, _, w, sizes, valid = _operands(jnp.float32, SIZES["tail_left"], 3)
    lhs = jnp.where(valid, lhs, 0)
    cot = jnp.asarray(np.random.default_rng(4).standard_normal((M, N)))
    w_t = jnp.swapaxes(w, 1, 2)

    def loss(lhs, w, transpose):
        out = gm.grouped_product(lhs, w, sizes, transpose_rhs=transpose)
        return (jnp.where(valid, out, 0) * cot).sum()

    grads = {}
    for mode in (None, True):
        monkeypatch.setattr(gm, "grouped_interpret_mode", lambda m=mode: m)
        with jax.default_matmul_precision("highest"):
            grads[mode] = [jax.grad(loss, (0, 1))(lhs, stack, transpose)
                           for stack, transpose in ((w, False), (w_t, True))]
    for want, got in zip(grads[None], grads[True]):
        for a, b in zip(got, want):
            _close(a, b, jnp.float32)


#: The grouped products of the four routed cells' layers: (chunk rows,
#: rows of every chunk that can come, d, f).
CELL_SHAPES = {
    "lfm2": (5120, 35840, 2048, 1536),
    "moonlight": (7680, 53760, 2048, 1408),
    "mellum2": (20480, 81920, 2304, 896),
    "laguna": (5120, 66560, 2048, 512),
}


def _products(chunk, top, d, f):
    """(m, k, n, form) of the twelve products a layer runs."""
    return [(chunk, d, f, "gmm"), (chunk, f, d, "gmm"),
            (chunk, d, f, "gmm_t"), (chunk, f, d, "gmm_t"),
            (top, d, f, "tgmm"), (top, f, d, "tgmm")]


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_the_rules_tiles_divide_and_fit(cell):
    for m, k, n, form in _products(*CELL_SHAPES[cell]):
        tiles = gm.grouped_tiles(m, k, n, jnp.bfloat16, form)
        tm, tk, tn = tiles
        assert m % tm == 0 and k % tk == 0 and n % tn == 0, (form, tiles)
        assert tm % 16 == 0 and tk % 128 == 0 and tn % 128 == 0
        assert gm.grouped_vmem_bytes(
            tiles, jnp.bfloat16, form) <= gm._VMEM_BUDGET, (form, tiles)
        # Pure: the same shape, the same tiles.
        assert gm.grouped_tiles(m, k, n, jnp.bfloat16, form) == tiles


@pytest.mark.parametrize("m, k, n", [
    (40, 32, 24), (256, 96, 640), (512, 8192, 8192), (384, 128, 128)])
@pytest.mark.parametrize("form", gm.FORMS)
def test_the_rule_on_awkward_extents(m, k, n, form):
    """An extent that is no multiple of 128 is one whole tile; one too
    wide for the budget is cut by a dividing multiple of 128."""
    tm, tk, tn = gm.grouped_tiles(m, k, n, jnp.float32, form)
    assert m % tm == 0 and k % tk == 0 and n % tn == 0
    assert tk == k or tk % 128 == 0
    assert tn == n or tn % 128 == 0
    assert gm.grouped_vmem_bytes(
        (tm, tk, tn), jnp.float32, form) <= gm._VMEM_BUDGET
    with pytest.raises(ValueError):
        gm.grouped_tiles(m, k, n, jnp.float32, "bmm")


def test_tiled_rows_counts_a_shared_tile_for_each_group():
    assert int(gm.tiled_rows(jnp.asarray([128, 256, 0], jnp.int32), 128)) \
        == 384
    # 100 | 170 | 40 | 31 over tiles of 128: tiles 0; 0-2; 2; 2.
    sizes = jnp.asarray([0, 100, 0, 170, 40, 31], jnp.int32)
    assert int(gm.tiled_rows(sizes, 128)) == (1 + 3 + 1 + 1) * 128
    meta, steps = gm._metadata(sizes, 384, 128, visit_empty=False)
    assert int(steps) * 128 == int(gm.tiled_rows(sizes, 128))
    _, group, m_tile = meta
    assert np.asarray(group)[:6].tolist() == [1, 3, 3, 3, 4, 5]
    assert np.asarray(m_tile)[:6].tolist() == [0, 0, 1, 2, 2, 2]


def _engine(n_tokens, held, e, k, d, f, seed):
    """Tokens, routing, gates and the held stacks of a tiny layer."""
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.standard_normal((n_tokens, d)), jnp.float32)
    topi = jnp.asarray(np.stack([
        rng.choice(e, k, replace=False) for _ in range(n_tokens)]))
    gates = jnp.asarray(rng.random((n_tokens, k)), jnp.float32)
    stacks = [jnp.asarray(0.2 * rng.standard_normal(s), jnp.float32)
              for s in ((held, d, f), (held, d, f), (held, f, d))]
    return tokens, topi, gates, stacks


@pytest.mark.parametrize("chunk, chunks", [(640, 1), (256, 3)])
def test_the_engine_on_the_kernels_equals_it_on_ragged_dot(
        monkeypatch, chunk, chunks):
    """``_chunked_moe`` forward and backward (tokens, gates, three
    stacks), the kernels in interpret mode against ``ragged_dot``, with
    the routed rows in one chunk and in three."""
    # Six of eight experts held: 576 routed rows, about.
    tokens, topi, gates, stacks = _engine(192, 6, 8, 4, 32, 24, seed=5)
    cot = jnp.asarray(np.random.default_rng(6).standard_normal((192, 32)))

    def loss(tokens, gates, *stacks):
        out, rows, bound, overflow, _ = _chunked_moe(
            tokens, topi, gates, *stacks, first_expert=1, chunk=chunk)
        return (out * cot).sum(), (rows.sum(), bound, overflow)

    found = {}
    for mode in (None, True):
        monkeypatch.setattr(gm, "grouped_interpret_mode", lambda m=mode: m)
        with jax.default_matmul_precision("highest"):
            found[mode] = jax.value_and_grad(
                loss, range(5), has_aux=True)(tokens, gates, *stacks)
    (want, (routed, bound, overflow)), g_want = found[None]
    (got, counted), g_got = found[True]
    assert [int(c) for c in counted] == [int(routed), int(bound), 0]
    assert int(bound) == chunks * chunk and int(overflow) == 0
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))
    for name, a, b in zip(("tokens", "gates", "gate", "in", "out"),
                          g_got, g_want):
        assert np.abs(np.asarray(b)).max() > 1e-3, name
        _close(a, b, jnp.float32)


def test_the_chunks_tail_is_zero_on_the_kernels(monkeypatch):
    """What a chunk's product gives past the routed rows, on the kernel
    path: zero, whatever the tail of the input held."""
    monkeypatch.setattr(gm, "grouped_interpret_mode", lambda: True)
    rng = np.random.default_rng(7)
    rows = jnp.asarray([30, 0, 100, 70], jnp.int32)  # 200 of 256
    tokens = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    order = jnp.asarray(rng.permutation(256) % 256)
    _, _, zeroed, product = moe._chunk_rows(
        tokens, order, rows, k=4, chunk=256, index=0)
    a = jnp.where(jnp.arange(256)[:, None] < 200,
                  jnp.asarray(rng.standard_normal((256, 16)),
                              jnp.float32), jnp.nan)
    w = jnp.asarray(rng.standard_normal((4, 16, 8)), jnp.float32)
    for out in (product(a, w), product(a, jnp.swapaxes(w, 1, 2), True)):
        out = np.asarray(out)
        assert not out[200:].any() and np.isfinite(out[:200]).all()


def test_the_tiled_counter_against_a_count_by_hand():
    """``moe_rows_tiled``: the rows the forward product's row tiles cover,
    summed over the chunks the loop ran; every chunk's groups counted by
    hand from the routed rows."""
    n, e, k, held, d, f = 256, 8, 4, 4, 32, 24
    layer = MoEFFN(d_model=d, d_ff=f, n_experts=e, aux_weight=0.0,
                   dispatch="grouped", top_k=k, experts_held=held)
    x = jnp.asarray(np.random.default_rng(8).standard_normal((1, n, d)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(9), x)["params"]
    _, sown = layer.apply({"params": params}, x, mutable=["counters"])
    counters = sown["counters"]
    rows = np.asarray(counters["moe_rows"][0])
    chunk = -(-5 * n * k * held // (4 * e))  # 640: five tiles of 128
    tm = gm.grouped_tiles(chunk, d, f, jnp.float32, "gmm")[0]
    assert tm == 128
    by_hand, start = 0, 0
    bound = int(counters["moe_rows_bound"][0])
    for first in range(0, bound, chunk):
        for size in rows:
            lo, hi = max(start, first), min(start + size, first + chunk)
            if hi > lo:  # the tiles from lo's to (hi - 1)'s, chunk-local
                by_hand += ((hi - 1 - first) // tm
                            - (lo - first) // tm + 1) * tm
            start += size
        start = 0
    assert int(counters["moe_rows_tiled"][0]) == by_hand
    assert rows.sum() <= by_hand < rows.sum() + held * bound // chunk * tm

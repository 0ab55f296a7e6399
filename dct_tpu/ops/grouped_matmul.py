"""Pallas TPU grouped matrix products: the routed experts' GEMMs.

The grouped MoE engine (:mod:`dct_tpu.models.moe`) sorts its rows by
expert and multiplies each expert's run of rows by that expert's matrix.
Three forms cover the forward and the backward:

- ``gmm``:   ``out[r] = lhs[r] @ rhs[g(r)]``, lhs [m, k], rhs [G, k, n];
- ``gmm_t``: ``out[r] = lhs[r] @ rhs[g(r)]^T``, rhs [G, n, k]: the
  backward's products against the stacks, without a transposed copy;
- ``tgmm``:  ``out[g] = lhs[rows of g]^T @ rhs[rows of g]``, lhs [m, k],
  rhs [m, n], out [G, k, n]: the stacks' gradients.

``sizes`` [G] int32 gives each group's run of rows, in order from row 0;
rows past ``sum(sizes)`` belong to no group. ``gmm`` leaves them as the
output buffer held (the caller zeroes them, as after ``lax.ragged_dot``);
``tgmm`` never reads them, and an empty group's gradient is zero.

The kernels are adapted from JAX's megablox grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox``): the grid's row axis runs
over the row tiles the groups cover, a tile that two groups share visited
once for each, so the work follows ``sum(sizes)`` and not ``m``
(:func:`tiled_rows` counts it). Trimmed to what the engine needs (no
sharded group offset, no output to accumulate into, dimensions the tiles
divide) and changed where the engine's shapes want it: the row mask is
applied only on a tile that a group boundary cuts, the tiles come from a
rule of the shape (:func:`grouped_tiles`), and each call asks for the
scoped VMEM its tiles take (:func:`grouped_vmem_bytes`). Every product
accumulates in float32 and writes the operands' dtype, as the
``ragged_dot`` it replaces does.

Off the TPU, :func:`grouped_interpret_mode` keeps XLA's ``ragged_dot``;
tests run these kernels with ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FORMS = ("gmm", "gmm_t", "tgmm")

#: Scoped VMEM a kernel's tiles may take, double-buffered operands and the
#: f32 accumulator together. v5e's core has 128 MiB of VMEM; the compiler
#: scopes 16 MiB to a kernel unless told otherwise.
_VMEM_BUDGET = 48 * 1024 * 1024
#: Row tile: one pass of the MXU's 128 rows. A tile that two groups share
#: runs once for each, so every group boundary costs up to ``tm`` rows.
_ROW_TILE = 128


def grouped_interpret_mode() -> bool | None:
    """How the grouped products run here: False (the Mosaic kernels) on
    the TPU backend, None (XLA's ``lax.ragged_dot``) elsewhere."""
    return False if jax.default_backend() == "tpu" else None


def _divisor_tiles(n: int) -> list[int]:
    """Tiles of an extent ``n``, widest first: the multiples of 128 that
    divide it, or ``n`` whole where it is no multiple of 128 (a block as
    wide as the array is always allowed)."""
    if n % 128:
        return [n]
    return [c for c in range(n, 0, -128) if n % c == 0]


def grouped_vmem_bytes(tiles: tuple[int, int, int], dtype, form: str) -> int:
    """Scoped VMEM of one kernel on ``tiles`` (tm, tk, tn): both buffers
    of every streamed block and the f32 accumulator."""
    tm, tk, tn = tiles
    size = jnp.dtype(dtype).itemsize
    if form == "tgmm":
        blocks = tm * tk + tm * tn + tk * tn
        acc = tk * tn
    else:
        blocks = tm * tk + tk * tn + tm * tn
        acc = tm * tn
    return 2 * blocks * size + 4 * acc


def grouped_tiles(m: int, k: int, n: int, dtype,
                  form: str) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` for a grouped product of ``m`` rows, contracting
    ``k`` into ``n`` (``tgmm``: the rows are contracted, k x n is one
    group's output). Pure, static per shape, no knob.

    The row tile is one MXU pass (128 rows, or ``m`` where it is less or
    128 does not divide it). The other two cover their extents whole
    while the tiles fit the VMEM budget, so each expert's matrix is read
    once per group and each row once per product; where they do not fit,
    ``tn`` steps down through the multiples of 128 that divide ``n``,
    then ``tk`` through those of ``k``."""
    if form not in FORMS:
        raise ValueError(f"form {form!r} must be one of {FORMS}")
    tm = _ROW_TILE if m % _ROW_TILE == 0 else m
    for tk in _divisor_tiles(k):
        for tn in _divisor_tiles(n):
            if grouped_vmem_bytes((tm, tk, tn), dtype, form) <= _VMEM_BUDGET:
                return tm, tk, tn
    return tm, _divisor_tiles(k)[-1], _divisor_tiles(n)[-1]


def _tile_counts(sizes, tm: int, *, visit_empty: bool):
    """Row tiles each group's rows cover: from its first row's tile to its
    last row's, so a tile that two groups share counts for both. An empty
    group covers none, or one where ``visit_empty`` (``tgmm`` writes its
    zero gradient from it)."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tiles = (ends + tm - 1) // tm - starts // tm
    return jnp.where(sizes == 0, int(visit_empty), tiles).astype(jnp.int32)


def tiled_rows(sizes, tm: int):
    """The rows a ``gmm`` / ``gmm_t`` call on row tile ``tm`` computes
    for ``sizes``: its grid's row steps times ``tm``. ``sum(sizes)`` where
    every group starts and ends on a tile boundary; each boundary inside a
    tile adds up to ``tm``."""
    return _tile_counts(sizes, tm, visit_empty=False).sum() * tm


def _metadata(sizes, m: int, tm: int, *, visit_empty: bool):
    """The grid's scalars: (offsets [G + 1], the group and the row tile of
    each row step, both [m // tm + G - 1]), and the steps to run. Step ``i``
    computes row tile ``m_tile[i]`` for group ``group[i]``; the steps run
    group by group, so the visits of one row tile are consecutive."""
    g = sizes.shape[0]
    n_tiles = m // tm
    length = n_tiles + g - 1
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    counts = _tile_counts(sizes, tm, visit_empty=visit_empty)
    group = jnp.repeat(jnp.arange(g, dtype=jnp.int32), counts,
                       total_repeat_length=length)
    # Each row tile is visited once by the group its first row belongs to,
    # and once more by every group that starts inside it (and, visiting
    # empty groups, by every empty group placed at it).
    again = (starts % tm != 0) & (sizes > 0)
    if visit_empty:
        again = again | (sizes == 0)
    visits = jnp.ones(n_tiles, jnp.int32).at[
        jnp.where(again, starts // tm, n_tiles)].add(1, mode="drop")
    m_tile = jnp.repeat(jnp.arange(n_tiles, dtype=jnp.int32), visits,
                        total_repeat_length=length)
    return (offsets, group, m_tile), counts.sum()


def _rows_of_group(offsets, group, m_tile, i, tm: int):
    """Step ``i``'s group's rows [start, end) and its tile's first row,
    and whether the tile lies inside the group (no row to mask)."""
    g = group[i]
    start, end = offsets[g], offsets[g + 1]
    first = m_tile[i] * tm
    return start, end, first, (start <= first) & (first + tm <= end)


def _row_mask(shape, start, end, first):
    rows = first + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= start) & (rows < end)


def _gmm_kernel(offsets, group, m_tile, lhs_ref, rhs_ref, out_ref, *scratch,
                tm: int, tiles_k: int, transpose_rhs: bool):
    i, k_i = pl.program_id(1), pl.program_id(2)
    contract = (((1,), (1,)) if transpose_rhs else ((1,), (0,)), ((), ()))
    part = lax.dot_general(lhs_ref[...], rhs_ref[...], contract,
                           preferred_element_type=jnp.float32)

    def store(acc):
        start, end, first, inside = _rows_of_group(
            offsets, group, m_tile, i, tm)

        @pl.when(inside)
        def _whole():
            out_ref[...] = acc.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(inside))
        def _cut():
            # Rows of other groups keep what their own steps wrote.
            keep = _row_mask(acc.shape, start, end, first)
            out_ref[...] = jnp.where(
                keep, acc, out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    if tiles_k == 1:
        store(part)
        return
    acc_ref, = scratch

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += part

    @pl.when(k_i == tiles_k - 1)
    def _last():
        store(acc_ref[...])


def _tgmm_kernel(offsets, group, m_tile, lhs_ref, rhs_ref, out_ref, acc_ref,
                 *, tm: int):
    i = pl.program_id(2)
    last = pl.num_programs(2) - 1
    g = group[i]
    first_visit = (i == 0) | (group[jnp.maximum(i - 1, 0)] != g)
    last_visit = (i == last) | (group[jnp.minimum(i + 1, last)] != g)
    start, end, first, inside = _rows_of_group(offsets, group, m_tile, i, tm)

    @pl.when(first_visit)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(lhs, rhs):
        acc_ref[...] += lax.dot(lhs.swapaxes(0, 1), rhs,
                                preferred_element_type=jnp.float32)

    @pl.when(inside)
    def _whole():
        accumulate(lhs_ref[...], rhs_ref[...])

    @pl.when(jnp.logical_not(inside) & (end > start))
    def _cut():
        # Both operands masked: a row of another group, or past the last
        # one, reaches no gradient, whatever it holds.
        def masked(ref):
            keep = _row_mask(ref.shape, start, end, first)
            return jnp.where(keep, ref[...].astype(jnp.float32), 0.0)

        lhs, rhs = masked(lhs_ref), masked(rhs_ref)
        accumulate(lhs.astype(lhs_ref.dtype), rhs.astype(rhs_ref.dtype))

    @pl.when(last_visit)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _check_tiles(m: int, k: int, n: int, tiles):
    tm, tk, tn = tiles
    if m % tm or k % tk or n % tn:
        raise ValueError(
            f"grouped product ({m}, {k}, {n}) must be a multiple of its "
            f"tiles {tiles}")


def _compiler_params(tiles, dtype, form: str):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=max(
            16 << 20, grouped_vmem_bytes(tiles, dtype, form) + (4 << 20)),
    )


@functools.partial(
    jax.jit, static_argnames=("transpose_rhs", "tiles", "interpret"))
def gmm(lhs, rhs, sizes, *, transpose_rhs: bool = False,
        tiles: tuple[int, int, int] | None = None, interpret: bool = False):
    """``lhs[r] @ rhs[g]`` (``rhs[g]^T`` with ``transpose_rhs``) for each
    row r of group g: [m, k] x [G, k, n] (or [G, n, k]) -> [m, n] in
    ``lhs``'s dtype, accumulated in f32. Rows past ``sum(sizes)`` hold
    whatever the buffer held."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    form = "gmm_t" if transpose_rhs else "gmm"
    tiles = tiles or grouped_tiles(m, k, n, lhs.dtype, form)
    _check_tiles(m, k, n, tiles)
    tm, tk, tn = tiles
    tiles_k = k // tk
    meta, steps = _metadata(sizes.astype(jnp.int32), m, tm,
                            visit_empty=False)

    def lhs_index(n_i, i, k_i, offsets, group, m_tile):
        return m_tile[i], k_i

    def rhs_index(n_i, i, k_i, offsets, group, m_tile):
        return (group[i], n_i, k_i) if transpose_rhs else (group[i], k_i, n_i)

    def out_index(n_i, i, k_i, offsets, group, m_tile):
        return m_tile[i], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, steps, tiles_k),
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec(rhs_block, rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            scratch_shapes=(
                [] if tiles_k == 1 else [pltpu.VMEM((tm, tn), jnp.float32)]),
        ),
        compiler_params=_compiler_params(tiles, lhs.dtype, form),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * (n // tn) + rhs.size + m * n)
            * lhs.dtype.itemsize),
    )(*meta, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def tgmm(lhs, rhs, sizes, *, tiles: tuple[int, int, int] | None = None,
         interpret: bool = False):
    """``lhs[rows of g]^T @ rhs[rows of g]`` for each group g: [m, k] and
    [m, n] -> [G, k, n] in ``lhs``'s dtype, accumulated in f32. Rows past
    ``sum(sizes)`` are never read; an empty group's block is zero."""
    m, k = lhs.shape
    n = rhs.shape[1]
    g = sizes.shape[0]
    tiles = tiles or grouped_tiles(m, k, n, lhs.dtype, "tgmm")
    _check_tiles(m, k, n, tiles)
    tm, tk, tn = tiles
    meta, steps = _metadata(sizes.astype(jnp.int32), m, tm,
                            visit_empty=True)

    def lhs_index(n_i, k_i, i, offsets, group, m_tile):
        return m_tile[i], k_i

    def rhs_index(n_i, k_i, i, offsets, group, m_tile):
        return m_tile[i], n_i

    def out_index(n_i, k_i, i, offsets, group, m_tile):
        return group[i], k_i, n_i

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((g, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, steps),
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((tm, tn), rhs_index)],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=_compiler_params(tiles, lhs.dtype, "tgmm"),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * (n // tn) + m * n * (k // tk)
                            + g * k * n) * lhs.dtype.itemsize),
    )(*meta, lhs, rhs)


def _ragged(lhs, rhs, sizes, transpose_rhs: bool):
    """XLA's grouped product, ``rhs`` contracted on its last axis with
    ``transpose_rhs``."""
    if not transpose_rhs:
        return lax.ragged_dot(lhs, rhs, sizes)
    return lax.ragged_dot_general(
        lhs, rhs, sizes, lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(([1], [2]), ([], [])),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[0]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel_product(lhs, rhs, sizes, transpose_rhs, interpret):
    return gmm(lhs, rhs, sizes, transpose_rhs=transpose_rhs,
               interpret=interpret)


def _kernel_product_fwd(lhs, rhs, sizes, transpose_rhs, interpret):
    return (_kernel_product(lhs, rhs, sizes, transpose_rhs, interpret),
            (lhs, rhs, sizes))


def _kernel_product_bwd(transpose_rhs, interpret, saved, ct):
    lhs, rhs, sizes = saved
    ct = ct.astype(lhs.dtype)
    d_lhs = gmm(ct, rhs, sizes, transpose_rhs=not transpose_rhs,
                interpret=interpret)
    # A row past the groups meets no stack: its gradient is zero, not
    # what the buffer held.
    past = jnp.arange(lhs.shape[0])[:, None] >= jnp.sum(sizes)
    d_lhs = jnp.where(past, 0, d_lhs)
    d_rhs = (tgmm(ct, lhs, sizes, interpret=interpret) if transpose_rhs
             else tgmm(lhs, ct, sizes, interpret=interpret))
    return d_lhs, d_rhs.astype(rhs.dtype), None


_kernel_product.defvjp(_kernel_product_fwd, _kernel_product_bwd)


def grouped_product(lhs, rhs, sizes, *, transpose_rhs: bool = False):
    """``lhs[r] @ rhs[g]`` (``rhs[g]^T`` with ``transpose_rhs``) for the
    rows r of each group g, as :func:`gmm`: the Mosaic kernel on the TPU,
    XLA's ``ragged_dot`` elsewhere (:func:`grouped_interpret_mode`).
    Differentiable on either path."""
    interpret = grouped_interpret_mode()
    if interpret is None:
        return _ragged(lhs, rhs, sizes, transpose_rhs)
    return _kernel_product(lhs, rhs, sizes, transpose_rhs, interpret)


def grouped_weight_grad(lhs, rhs, sizes):
    """``lhs[rows of g]^T @ rhs[rows of g]`` for each group g, as
    :func:`tgmm`: the gradient of a ``grouped_product``'s stack, [G, k,
    n] in ``lhs``'s dtype. XLA's form off the TPU is the transpose of
    ``ragged_dot``."""
    interpret = grouped_interpret_mode()
    if interpret is None:
        stack = jax.ShapeDtypeStruct(
            (sizes.shape[0], lhs.shape[1], rhs.shape[1]), lhs.dtype)
        return jax.linear_transpose(
            lambda w: lax.ragged_dot(lhs, w, sizes), stack)(rhs)[0]
    return tgmm(lhs, rhs, sizes, interpret=interpret)

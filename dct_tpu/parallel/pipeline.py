"""Pipeline parallelism: GPipe-style microbatch streaming over ``pipe``.

The reference has no pipeline parallelism (SURVEY §2.3 lists PP as an
extension point); this module supplies it TPU-natively, completing the
mesh's DP x TP x SP x EP x PP matrix:

- stages are a STACKED pytree (leading dim = stage) sharded
  ``P("pipe", ...)`` — each pipeline device holds one stage's params;
- the batch is split into microbatches that stream through the stages
  inside one ``shard_map``: every tick, each stage applies its params to
  its current activation and ``lax.ppermute``s the result to the next
  stage (a neighbor hop over ICI), while stage 0 ingests the next
  microbatch and the last stage banks its finished one;
- the schedule is the classic GPipe fill/drain: ``M + P - 1`` ticks for
  ``M`` microbatches over ``P`` stages, bubble fraction ``(P-1)/(M+P-1)``;
- the BACKWARD schedule is not hand-written: ``jax.grad`` through the
  scan+ppermute forward yields the reverse pipeline automatically
  (ppermute transposes to the reverse permutation), so the same jitted
  train step machinery works unchanged.

Stages must share one param structure (e.g. equal groups of identical
blocks) — that is what makes the stacked-pytree layout expressible as a
single sharded array per leaf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_stage_params(stage_params: list):
    """[per-stage pytrees with identical structure] -> stacked pytree
    (leading dim = n_stages), ready to shard ``P('pipe', ...)``."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *stage_params)


def stage_params_sharding(stacked, mesh: Mesh, axis: str = "pipe"):
    """NamedSharding tree placing the stage dim on the ``pipe`` axis."""
    def one(leaf):
        spec = P(axis, *([None] * (leaf.ndim - 1)))
        return NamedSharding(mesh, spec)

    return jax.tree.map(one, stacked)


def _pipeline_body(params, xs, *, stage_fn, axis: str, n_stages: int):
    """Runs inside shard_map: params [1, ...] local stage slice; xs
    [M, mb, ...] microbatches (replicated). Returns [M, mb, ...] outputs
    (replicated via a final psum broadcast from the last stage)."""
    stage = lax.axis_index(axis)
    local = jax.tree.map(lambda a: a[0], params)
    m = xs.shape[0]
    ticks = m + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    # The carry becomes device-varying over the pipe axis from the first
    # tick (stage-dependent compute); type the initial carry that way so
    # the scan carry type is fixed (same recipe as ring attention).
    act0 = lax.pcast(jnp.zeros_like(xs[0]), (axis,), to="varying")
    ys0 = lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")

    def tick(carry, t):
        act, ys = carry
        # Stage 0 ingests microbatch t (index clamps past the end during
        # the drain ticks; the result is never banked then).
        mb = lax.dynamic_index_in_dim(
            xs, jnp.clip(t, 0, m - 1), axis=0, keepdims=False
        )
        inp = jnp.where(stage == 0, mb, act)
        out = stage_fn(local, inp)
        # The last stage finished microbatch t-(P-1) this tick.
        done_idx = t - (n_stages - 1)
        banked = lax.dynamic_update_index_in_dim(
            ys, out, jnp.clip(done_idx, 0, m - 1), axis=0
        )
        take = jnp.logical_and(stage == n_stages - 1, done_idx >= 0)
        ys = jnp.where(take, banked, ys)
        # Rotate activations one stage forward (ICI neighbor hop).
        act = lax.ppermute(out, axis, perm)
        return (act, ys), None

    (_, ys), _ = lax.scan(tick, (act0, ys0), jnp.arange(ticks))
    # Replicate the last stage's banked outputs to every pipe device.
    ys = lax.psum(jnp.where(stage == n_stages - 1, ys, jnp.zeros_like(ys)), axis)
    return ys


def gpipe_tick_apply(
    stage_fn,
    stacked_params,
    x,
    *,
    n_microbatches: int | None = None,
):
    """GPipe microbatch streaming WITHOUT shard_map: the tick loop as a
    plain vmapped scan under GSPMD.

    Semantically identical to :func:`pipeline_apply` (same ``M + P - 1``
    tick schedule, same bubble ``(P-1)/(M+P-1)``), but the stage axis is
    an ordinary array dimension: every tick vmaps ``stage_fn`` over the
    stacked stage dim and rotates activations with ``jnp.roll`` — when
    the stacked params/activations are sharded ``P('pipe', ...)`` the
    partitioner turns the vmap into per-shard stage compute and the roll
    into the neighbor collective-permute, with no shard_map involved.
    The SPMD-GPipe comparator for the MPMD runner (tests/test_mpmd.py);
    the tick structure — and therefore the bubble — is the same either
    way.

    Differentiable: ``jax.grad`` through the scan+roll yields the
    reverse tick schedule, exactly as with ppermute.
    """
    first = jax.tree.leaves(stacked_params)[0]
    n_stages = first.shape[0]
    b = x.shape[0]
    m = n_microbatches or n_stages
    if b % m:
        raise ValueError(f"batch {b} not divisible by n_microbatches {m}")
    xs = x.reshape(m, b // m, *x.shape[1:])
    ticks = m + n_stages - 1

    def tick(carry, t):
        act, ys = carry
        mb = lax.dynamic_index_in_dim(
            xs, jnp.clip(t, 0, m - 1), axis=0, keepdims=False
        )
        # Stage 0 ingests microbatch t; other stages keep their carry.
        inp = act.at[0].set(mb)
        out = jax.vmap(stage_fn)(stacked_params, inp)
        done_idx = t - (n_stages - 1)
        banked = lax.dynamic_update_index_in_dim(
            ys, out[n_stages - 1], jnp.clip(done_idx, 0, m - 1), axis=0
        )
        ys = jnp.where(done_idx >= 0, banked, ys)
        act = jnp.roll(out, 1, axis=0)
        return (act, ys), None

    act0 = jnp.zeros((n_stages, b // m, *x.shape[1:]), x.dtype)
    ys0 = jnp.zeros_like(xs)
    (_, ys), _ = lax.scan(tick, (act0, ys0), jnp.arange(ticks))
    return ys.reshape(b, *x.shape[1:])


def pipeline_apply(
    stage_fn,
    stacked_params,
    x,
    *,
    mesh: Mesh,
    axis: str = "pipe",
    n_microbatches: int | None = None,
    data_axis: str | None = None,
):
    """Apply ``n_stages`` chained stages to ``x`` [B, ...] with GPipe
    microbatch streaming over ``mesh[axis]``.

    ``stage_fn(params_one_stage, activation) -> activation`` must preserve
    the activation shape (stages are homogeneous). ``n_microbatches``
    defaults to the pipeline depth (bubble fraction ~1/2; raise it to
    amortize the bubble). Differentiable: jax.grad produces the reverse
    pipeline schedule.

    ``data_axis``: compose DP x PP — the within-microbatch batch dim
    shards over that mesh axis (each data-parallel group runs its own
    pipeline over its rows); None replicates the batch over the mesh.
    """
    n_stages = mesh.shape[axis]
    first = jax.tree.leaves(stacked_params)[0]
    if first.shape[0] != n_stages:
        raise ValueError(
            f"stacked params have {first.shape[0]} stages but mesh axis "
            f"'{axis}' has {n_stages} devices"
        )
    b = x.shape[0]
    m = n_microbatches or n_stages
    if b % m:
        raise ValueError(f"batch {b} not divisible by n_microbatches {m}")
    if data_axis is not None and (b // m) % mesh.shape[data_axis]:
        raise ValueError(
            f"microbatch {b // m} not divisible by mesh axis "
            f"'{data_axis}' ({mesh.shape[data_axis]})"
        )
    xs = x.reshape(m, b // m, *x.shape[1:])

    body = functools.partial(
        _pipeline_body, stage_fn=stage_fn, axis=axis, n_stages=n_stages
    )
    param_specs = jax.tree.map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), stacked_params
    )
    xs_spec = P(None, data_axis, *([None] * (x.ndim - 1)))
    # PARTIAL-manual shard_map: only the pipe (and data) axes are manual;
    # every other mesh axis (model/seq) stays AUTO, so tensor-parallel
    # shardings on the stage params' inner dims survive into the body and
    # the compiler inserts the TP collectives inside each stage — PP x TP
    # compose without hand-written stage communication.
    manual = {axis} | ({data_axis} if data_axis is not None else set())
    ys = shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, xs_spec),
        out_specs=xs_spec,
        axis_names=frozenset(manual),
    )(stacked_params, xs)
    return ys.reshape(b, *x.shape[1:])

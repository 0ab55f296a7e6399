"""Pure-functional train/eval steps, compiled once, sharded over the mesh.

This replaces the reference's per-batch Python call stack
(LightningModule.training_step -> backward -> gloo all-reduce -> Adam.step,
jobs/train_lightning_ddp.py:66-71,88) with a single jitted function:

    loss_fn -> jax.value_and_grad -> optax update  (one XLA program)

Distribution is declarative, not imperative: the batch arrives sharded over
the mesh's ``data`` axis and params arrive replicated, so XLA inserts the
gradient all-reduce (the gloo/NCCL analog) over ICI automatically. Metrics
come back as (weighted_sum, count) pairs — already globally reduced — which
is the exact analog of Lightning's ``sync_dist=True`` logging
(jobs/train_lightning_ddp.py:70,83-84) without a separate collective.

Two compilation granularities over the SAME step bodies (shared helpers
``_train_body``/``_eval_body`` make the equivalence structural, not just
tested): per-batch jit, and whole-epoch ``lax.scan`` — one host dispatch
per epoch, the throughput path at the reference's tiny parity batch size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from dct_tpu.ops.losses import (
    masked_accuracy,
    masked_binary_counts,
    masked_cross_entropy,
)
from dct_tpu.parallel.sharding_rules import cast_params_by_rules
from dct_tpu.train.state import TrainState

# Mixed-precision dispatch (docs/PARALLELISM.md §dtype rules): every
# loss/eval body below applies ``cast_params_by_rules`` to the f32
# MASTER params as the first traced op. With DCT_DTYPE_RULES unset the
# call is the identity (bits unchanged — the contract every resume/
# parity test pins); with rules set, matching param leaves enter the
# forward in bf16 while value_and_grad differentiates w.r.t. the
# UNCAST masters — the cast's vjp widens cotangents back to f32, so
# gradient accumulation and optimizer state stay full-width. The env
# is read at TRACE time: the trainer joins dtype_rules_digest() into
# the AOT program identity so a precision change recompiles loudly.


def _position_weight(logits, y, weight):
    """Per-position supervision support: [B, S, C] logits with [B, S]
    labels (or [B, S, H, C] with [B, S, H] — the multi-horizon causal
    head) broadcast the [B] row weight over the label positions (padded
    rows mask every position; the mean stays per-position)."""
    if logits.ndim == y.ndim + 1 and y.ndim >= 2 and weight.ndim == 1:
        return jnp.broadcast_to(
            weight.reshape(-1, *([1] * (y.ndim - 1))), y.shape
        )
    return weight


#: What a train step lets the model write: objective terms, what it
#: counts while it runs, and steps of its own parameters that are no
#: gradient steps (:func:`_stepped`).
_SOWN = ["aux_loss", "counters", "param_steps"]


def _objective(updates, loss, aux_share: int = 1):
    """The CE plus every sown ``aux_loss`` leaf (over ``aux_share``, the
    microbatches an accumulated step averages them over), and what else
    the step sowed: its ``counters`` (what the model counts while it runs:
    the MoE layers' routed rows; empty for a model that counts nothing)
    and its ``param_steps``."""
    for leaf in jax.tree.leaves(updates.get("aux_loss", {})):
        loss = loss + (leaf / aux_share if aux_share > 1 else leaf)
    return loss, (
        updates.get("counters", {}), updates.get("param_steps", {}))


def _sown_name(path) -> str:
    """The name a leaf of a sown collection was sown under."""
    return [k.key for k in path if hasattr(k, "key")][-1]


def _reduce_counters(counters, axis: int = 0):
    """Counters stacked along ``axis`` (an epoch's steps, a step's
    microbatches) as one value each: the sum, and for a counter whose name
    ends in ``_max`` the largest."""
    def reduce(path, c):
        return c.max(axis) if _sown_name(path).endswith("_max") \
            else c.sum(axis)

    return jax.tree_util.tree_map_with_path(reduce, counters)


def _stepped(state: TrainState, steps) -> TrainState:
    """``state`` after the steps the model sowed for its own parameters
    into ``param_steps``, each under its module's path and the parameter's
    name: added to that parameter as they are, after the optimizer's
    update (whose gradient for such a parameter is zero). The selection
    bias of the routed experts moves so
    (:meth:`dct_tpu.models.moe.MoEFFN._grouped`). Nothing sown: the same
    state."""
    if not steps:
        return state

    def add(params, steps):
        out = dict(params)
        for k, step in steps.items():
            out[k] = (add(params[k], step) if isinstance(step, dict)
                      else params[k] + step)
        return out

    return state.replace(
        params={**state.params, "params": add(state.params["params"], steps)})


def counter_metrics(counters) -> dict:
    """An epoch's counters (host arrays, summed over its steps) as flat
    metrics, by the name each was sown under, summed over the modules that
    sowed it: a scalar as ``name``; a vector as ``name`` (its total) and
    ``name_<j>``; and where a vector ``name`` comes with a scalar
    ``name_uniform`` (what each entry would hold under an even spread), the
    worst entry of any module over that mean as ``name_max_over_mean``. A
    counter sown under a name that ends in ``_max`` is the largest over
    steps and modules, not their sum."""
    import numpy as np

    by_name: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(counters):
        by_name.setdefault(_sown_name(path), []).append(
            np.asarray(leaf, np.float64))
    out: dict = {}
    for name, leaves in by_name.items():
        if name.endswith("_max"):  # the largest, not the sum
            out[name] = float(np.max(leaves))
            continue
        total = np.sum(leaves, axis=0)
        out[name] = float(total.sum())
        if total.ndim == 1:
            out.update({f"{name}_{j}": float(v) for j, v in enumerate(total)})
            uniform = by_name.get(name + "_uniform")
            if uniform is not None:
                out[name + "_max_over_mean"] = float(max(
                    v.max() / u for v, u in zip(leaves, uniform)))
    return out


def _train_body(state: TrainState, x, y, weight):
    """One optimization step: (state, batch) -> (new_state, loss, grad
    norm)."""
    return _train_body_counted(state, x, y, weight)[:3]


def _train_body_counted(state: TrainState, x, y, weight):
    """:func:`_train_body` plus the step's sown counters.

    Computes the global weighted-mean CE (the reference's ``train_loss``,
    jobs/train_lightning_ddp.py:70), its grads, and the Adam update.
    Models may sow extra objective terms into the ``aux_loss`` collection
    (e.g. the MoE family's pre-weighted load-balance loss); every sown
    leaf is added to the objective. For models that sow nothing the
    collection is empty and this is a no-op.
    """
    step_rng = jax.random.fold_in(state.rng, state.step)

    def loss_fn(params):
        logits, updates = state.apply_fn(
            cast_params_by_rules(params), x, train=True,
            rngs={"dropout": step_rng}, mutable=_SOWN,
        )
        w = _position_weight(logits, y, weight)
        loss_sum, count = masked_cross_entropy(logits, y, w)
        return _objective(updates, loss_sum / jnp.maximum(count, 1.0))

    (loss, (counters, steps)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(state.params)
    # Gradient global norm: the health monitor's drift signal. One fused
    # reduction over leaves XLA already has resident — and dead-code
    # eliminated entirely by factories that do not emit it.
    return (
        _stepped(state.apply_gradients(grads), steps), loss,
        optax.global_norm(grads), counters,
    )


def _eval_body(state: TrainState, x, y, weight):
    """One eval step -> (loss_sum, acc_sum, count, tp, fp, fn) running
    sums (the reference's ``val_loss``/``val_acc``,
    jobs/train_lightning_ddp.py:73-85, plus the positive-class counts
    behind precision/recall/F1 — a metric surface the reference's rain
    classifier lacks). Sown aux losses are training regularizers only;
    val_loss stays pure CE."""
    logits, _ = state.apply_fn(
        cast_params_by_rules(state.params), x, train=False,
        mutable=["aux_loss"],
    )
    w = _position_weight(logits, y, weight)
    loss_sum, count = masked_cross_entropy(logits, y, w)
    acc_sum, _ = masked_accuracy(logits, y, w)
    tp, fp, fn = masked_binary_counts(logits, y, w)
    return loss_sum, acc_sum, count, tp, fp, fn


def _train_accum_body(state: TrainState, x, y, weight, accum_steps: int):
    """One optimizer step over ``accum_steps`` microbatches: grads are
    accumulated in a ``lax.scan`` (one resident microbatch of activations
    at a time — effective batch grows without growing live HBM) and
    applied once. Exactly equal to one big-batch step for the CE term
    (the weighted-sum/total decomposition is linear; ``total`` is
    param-independent); sown aux losses average over microbatches."""
    b = x.shape[0]
    step_rng = jax.random.fold_in(state.rng, state.step)
    xs = x.reshape(accum_steps, b // accum_steps, *x.shape[1:])
    ys = y.reshape(accum_steps, b // accum_steps, *y.shape[1:])
    ws = weight.reshape(accum_steps, b // accum_steps)
    # Per-position supervision ([B, S] or [B, S, H] labels) counts every
    # supervised position.
    positions = 1
    for d in y.shape[1:]:
        positions *= d
    total = jnp.maximum(weight.sum() * positions, 1.0)

    def chunk_loss(params, cx, cy, cw, rng):
        logits, updates = state.apply_fn(
            cast_params_by_rules(params), cx, train=True,
            rngs={"dropout": rng}, mutable=_SOWN,
        )
        loss_sum, _ = masked_cross_entropy(
            logits, cy, _position_weight(logits, cy, cw)
        )
        return _objective(updates, loss_sum / total, accum_steps)

    grad_fn = jax.value_and_grad(chunk_loss, has_aux=True)

    def body(carry, chunk):
        gacc, lacc, i = carry
        cx, cy, cw = chunk
        (loss_i, sown), g = grad_fn(
            state.params, cx, cy, cw, jax.random.fold_in(step_rng, i)
        )
        return (jax.tree.map(jnp.add, gacc, g), lacc + loss_i, i + 1), sown

    zeros = jax.tree.map(jnp.zeros_like, state.params)
    (grads, loss, _), (counters, steps) = jax.lax.scan(
        body, (zeros, jnp.zeros(()), jnp.zeros((), jnp.int32)), (xs, ys, ws)
    )
    # Norm of the ACCUMULATED gradient — the update the optimizer sees.
    # Sown parameter steps average over the microbatches, as aux losses do.
    return (
        _stepped(
            state.apply_gradients(grads),
            jax.tree.map(lambda c: c.mean(axis=0), steps)),
        loss, optax.global_norm(grads), _reduce_counters(counters),
    )


def make_train_step(donate: bool = True, accum_steps: int = 1,
                    with_grad_norm: bool = False):
    """Per-batch jitted step: (state, x, y, weight) -> (state, metrics).
    ``accum_steps`` > 1 splits the batch into that many microbatches and
    accumulates gradients before the single optimizer update.
    ``with_grad_norm=True`` adds ``metrics["grad_norm"]`` (the health
    monitor's signal); the default keeps the historical metrics dict so
    bench/step-time consumers measure the exact prior program."""

    def train_step(state: TrainState, x, y, weight):
        if accum_steps > 1:
            new_state, loss, gnorm, _ = _train_accum_body(
                state, x, y, weight, accum_steps
            )
        else:
            new_state, loss, gnorm = _train_body(state, x, y, weight)
        metrics = {"train_loss": loss}
        if with_grad_norm:
            metrics["grad_norm"] = gnorm
        return new_state, metrics

    return jax.jit(train_step, donate_argnums=(0,) if donate else ())


def _epoch_train_scan(state: TrainState, xs, ys, ws, accum_steps: int):
    """Shared whole-epoch train scan body (see make_epoch_train_step):
    -> (state, losses[S'], grad_norms[S'], counters) with S' = optimizer
    updates and the counters summed over them. The stacked grad norms and
    the counters are free for callers that drop them (XLA DCEs unused
    scan outputs at lowering)."""
    if accum_steps > 1:
        s, b = xs.shape[0], xs.shape[1]
        xs = xs.reshape(s // accum_steps, accum_steps * b, *xs.shape[2:])
        # Trailing label dims survive (per-position [S, B, seq] labels
        # of the causal family).
        ys = ys.reshape(s // accum_steps, accum_steps * b, *ys.shape[2:])
        ws = ws.reshape(s // accum_steps, accum_steps * b)

        def body(st, batch):
            st, *out = _train_accum_body(st, *batch, accum_steps)
            return st, tuple(out)
    else:
        def body(st, batch):
            st, *out = _train_body_counted(st, *batch)
            return st, tuple(out)

    state, (losses, gnorms, counters) = jax.lax.scan(
        body, state, (xs, ys, ws)
    )
    return state, losses, gnorms, _reduce_counters(counters)


def _epoch_eval_scan(state: TrainState, xs, ys, ws):
    """Shared whole-valset eval scan body -> the 6 global metric sums
    (loss_sum, acc_sum, count, tp, fp, fn)."""

    def body(carry, batch):
        sums = _eval_body(state, *batch)
        return tuple(a + b for a, b in zip(carry, sums)), None

    zeros = tuple(jnp.zeros(()) for _ in range(6))
    sums, _ = jax.lax.scan(body, zeros, (xs, ys, ws))
    return sums


def make_epoch_train_step(donate: bool = True, accum_steps: int = 1,
                          with_grad_norms: bool = False):
    """Whole-epoch training as one XLA program: ``lax.scan`` of
    ``_train_body`` over the stacked batches [S, B, ...].

    Semantically identical to S calls of the per-batch step (same rng
    folding, same order, same updates) but with ONE host dispatch per epoch
    instead of S — at the reference's parity batch size (4/rank,
    jobs/train_lightning_ddp.py:122) per-step dispatch latency dominates a
    TPU step, so this is where the throughput win over the eager loop
    comes from. Returns (state, losses[S]) so per-step logging cadence
    (log_every_n_steps, :139) is preserved from the host side.

    ``accum_steps`` > 1 groups every ``accum_steps`` consecutive stacked
    batches into ONE optimizer update (gradient accumulation); S must be
    divisible (the Trainer truncates the remainder).

    ``with_grad_norms=True`` appends the per-update gradient global
    norms ``[S']`` to the outputs (the health monitor's drift signal);
    the default keeps the historical (state, losses) signature, and the
    unemitted norms are DCE'd at lowering.
    """

    def epoch_train(state: TrainState, xs, ys, ws):
        state, losses, gnorms, _ = _epoch_train_scan(
            state, xs, ys, ws, accum_steps
        )
        if with_grad_norms:
            return state, losses, gnorms
        return state, losses

    return jax.jit(epoch_train, donate_argnums=(0,) if donate else ())


def _epoch_donate(donate: bool, donate_stacks: bool) -> tuple:
    """Donation sets for the fused train+eval programs: argnum 0 is the
    state; 1-3 are the single-use epoch/span stacks (donating them frees
    a full span of HBM before activations peak). The validation stacks
    (4-6) are NEVER donated — they are reused every span. Callers that
    re-dispatch the same stacks must keep donate_stacks=False or their
    second call reads donated buffers."""
    nums = (0,) if donate else ()
    if donate_stacks:
        nums = nums + (1, 2, 3)
    return nums


#: The fused epoch program's key in the AOT store, the goodput ledger's
#: dispatch accounting and the ``trainer.dispatch`` spans. The benchmark
#: reads ``aot_store.executables[EPOCH_PROGRAM_KEY]`` by this literal and
#: finds the program on the device's timeline as ``jit_epoch_fused``.
EPOCH_PROGRAM_KEY = "scan_k1"


def make_epoch_train_eval_step(donate: bool = True, accum_steps: int = 1,
                               donate_stacks: bool = False,
                               with_grad_norms: bool = False):
    """Train epoch + full validation pass as ONE XLA program — one host
    dispatch per epoch where train-then-eval would cost two. The
    numerics are identical to make_epoch_train_step followed by
    make_epoch_eval_step (eval runs on the post-epoch state).

    Returns (state, losses[S], the 6 eval sums (val_loss_sum,
    val_acc_sum, val_count, tp, fp, fn), then with ``with_grad_norms=True``
    the per-update grad global norms [S], and last the model's sown
    counters summed over the epoch's updates (an empty tree for a model
    that counts nothing)). The validation stacks are NOT donated — they
    are reused every epoch.
    """

    def epoch_fused(state: TrainState, xs, ys, ws, vxs, vys, vws):
        state, losses, gnorms, counters = _epoch_train_scan(
            state, xs, ys, ws, accum_steps
        )
        out = (state, losses, _epoch_eval_scan(state, vxs, vys, vws))
        if with_grad_norms:
            out += (gnorms,)
        return out + (counters,)

    donate_argnums = _epoch_donate(donate, donate_stacks)
    return jax.jit(epoch_fused, donate_argnums=donate_argnums)


def make_eval_step():
    """Per-batch jitted eval step returning running-sum metrics."""
    return jax.jit(_eval_body)


def make_epoch_eval_step():
    """Whole-valset evaluation as one scan of ``_eval_body``; returns
    the 6 global sums (loss_sum, acc_sum, count, tp, fp, fn)."""
    return jax.jit(_epoch_eval_scan)

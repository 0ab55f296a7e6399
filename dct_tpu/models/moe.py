"""Mixture-of-Experts family: switch-routed FFN with expert parallelism.

The reference has exactly one dense MLP and one parallelism axis (2-rank
DDP, SURVEY §2.3); this family completes the mesh's parallelism matrix —
experts shard over the ``model`` axis (expert parallelism), composing with
batch DP and attention TP/SP in the same jitted step.

TPU-first routing: no data-dependent shapes. Two dispatch engines share
one softmax router and one capacity policy that drops what does not fit;
a third is the dropless layer of the hybrid family
(``weather_hybrid_moe_causal``):

- ``einsum`` — dense one-hot dispatch/combine einsums with a STATIC
  per-expert capacity:

      dispatch [N_tokens, E, C]  (one-hot: token -> (expert, slot))
      expert_in = einsum('nec,nd->ecd', dispatch, tokens)
      expert_out = per-expert FFN batched over E      <- MXU batched GEMMs
      out = einsum('nec,ecd->nd', dispatch, expert_out) * gate

  Exact arrival-order capacity semantics, but the dispatch tensors are
  O(N·E·C) — it stops scaling once E·C outgrows a few hundred.

- ``sorted`` — segment-based dispatch with the same static shapes and
  O(N log N + N·D) cost: stable-sort tokens by expert, rank them within
  their expert (bincount prefix sums), scatter the first ``capacity``
  of each into a [E, C, D] expert buffer, run the batched GEMMs, gather
  back and unsort. Under expert parallelism the buffer is exchanged with
  an EXPLICIT ``lax.all_to_all`` over the ``model`` axis inside a
  shard_map: each model-rank routes its 1/ep slice of the local tokens
  (so expert compute is sharded, not replicated), sends per-destination
  slots, computes its own experts, reverses the exchange, and
  all-gathers the combined outputs — the canonical MoE a2a pipeline,
  visible as ``all-to-all`` in the compiled HLO (asserted by tests).

- ``grouped`` — top-k of sigmoid scores plus a per-expert selection
  bias (or, ``scoring="softmax"``, of the softmax probabilities, with
  the balance loss below and no bias), weights normalised over the
  chosen experts,
  bias-free SwiGLU experts, and NO dropping: the routed rows are sorted by
  expert and go through three grouped products
  (:mod:`dct_tpu.ops.grouped_matmul`: a Pallas grouped matmul on the TPU,
  ``jax.lax.ragged_dot`` elsewhere), a static chunk of rows at a time, as
  many chunks as hold the rows that were routed this step
  (``_chunked_moe``). The layer is
  told which experts it holds (``experts_held``, ``first_expert``): the
  router stays ``n_experts`` wide, the top-k is over all of them, and the
  layer computes its own experts' part of the sum, one chip's share of an
  expert-parallel layer without the exchange (:meth:`MoEFFN._grouped`).
  It sows what it routed into the ``counters`` collection, which the
  train step hands to the trainer's epoch metrics.

In the first two, tokens over capacity are dropped (their dispatch row is
zero); the block's
residual connection passes them through unchanged — standard switch
behavior. Expert weights are [E, D, F] tensors named ``experts_in`` /
``experts_out``; the sharding rules place them ``P("model", None, None)``.

A load-balance auxiliary loss (Switch Transformer's f·P dot) is returned
via ``self.sow("aux_loss", ...)`` by the two capacity engines and by the
grouped layer under a softmax router; the train step folds every sown
``aux_loss`` into the objective, weighted by ``router_aux_weight``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P
from flax import linen as nn

from dct_tpu.models.mlp import TorchStyleDense, torch_linear_init
from dct_tpu.models.transformer import MultiHeadAttention, sincos_positions
from dct_tpu.ops import grouped_matmul


def _expert_ffn(batch, w_in, b_in, w_out, b_out):
    """Batched per-expert GEMMs: [..., E, C, D] x [E, D, F] — the MXU hot
    path shared by both dispatch engines."""
    h = jnp.einsum("...ecd,edf->...ecf", batch, w_in)
    h = nn.gelu(h + b_in[:, None, :])
    out = jnp.einsum("...ecf,efd->...ecd", h, w_out)
    return out + b_out[:, None, :]


def _sorted_moe(tokens, expert_idx, gate, w_in, b_in, w_out, b_out, *,
                e_total: int, capacity: int, ep_axis: str | None = None):
    """Segment-based switch dispatch on LOCAL arrays.

    tokens [N, D] (compute dtype), expert_idx [N] int32, gate [N]
    (compute dtype); expert weights are the LOCAL shard [E_local, ...]
    (E_local == e_total when not expert-parallel). With ``ep_axis`` the
    [e_total, C, D] buffer is reshaped [ep, E_local, C, D] and exchanged
    with ``lax.all_to_all`` so each rank computes only its own experts.
    """
    n, d = tokens.shape
    e_local = w_in.shape[0]
    ep = e_total // e_local

    order = jnp.argsort(expert_idx)  # stable: preserves arrival order
    sorted_e = expert_idx[order]
    counts = jnp.bincount(expert_idx, length=e_total)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n) - starts[sorted_e]  # rank within expert
    keep = pos < capacity
    # Row e*C+c of the buffer is (expert e, slot c); dropped tokens all
    # target the sentinel row, which is sliced off before compute.
    dst = jnp.where(keep, sorted_e * capacity + pos, e_total * capacity)
    buf = jnp.zeros((e_total * capacity + 1, d), tokens.dtype)
    buf = buf.at[dst].set(tokens[order])
    expert_in = buf[:-1].reshape(e_total, capacity, d)

    if ep_axis is not None and ep > 1:
        z = expert_in.reshape(ep, e_local, capacity, d)
        # tiled=False all_to_all REMOVES the split axis and INSERTS the
        # source axis at concat_axis: [dst, le, C, d] -> [src, le, C, d]
        # (each rank keeps only its own experts' slots, one per source).
        z = lax.all_to_all(z, ep_axis, split_axis=0, concat_axis=0)
        out_e = _expert_ffn(z, w_in, b_in, w_out, b_out)
        # Same exchange returns results to their source rank; the [owner,
        # le] leading dims then flatten to global-expert order.
        out_e = lax.all_to_all(out_e, ep_axis, split_axis=0, concat_axis=0)
        out_e = out_e.reshape(e_total, capacity, d)
    else:
        out_e = _expert_ffn(expert_in, w_in, b_in, w_out, b_out)

    out_flat = jnp.concatenate(
        [out_e.reshape(e_total * capacity, d), jnp.zeros((1, d), out_e.dtype)]
    )
    out_sorted = out_flat[dst] * keep[:, None].astype(out_e.dtype)
    out = out_sorted[jnp.argsort(order)]  # unsort
    return out * gate[:, None]


def _held_rows(topi, *, held: int, first_expert: int):
    """The N * k routed rows by held expert: (``order``, the stable sort
    of the rows by held expert with the rows of experts held elsewhere
    last; ``rows`` [held] int32 routed to each held expert). Integer work
    on the router's choice only."""
    local = topi.astype(jnp.int32) - first_expert
    flat = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    rows = jnp.bincount(flat, length=held + 1)[:held].astype(jnp.int32)
    return jnp.argsort(flat, stable=True), rows


def _chunk_sizes(rows, *, chunk: int, index):
    """Each held expert's rows among sorted rows ``index * chunk .. (index
    + 1) * chunk - 1`` ([held]; with ``index`` of shape [c, 1], [c, held])."""
    first = index * chunk
    ends = jnp.cumsum(rows)
    return (jnp.clip(ends, first, first + chunk)
            - jnp.clip(ends - rows, first, first + chunk))


def _chunk_rows(tokens, order, rows, *, k: int, chunk: int, index):
    """Sorted rows ``index * chunk .. (index + 1) * chunk - 1``: (x
    [chunk, D], each row's token; row [chunk], its place among the N * k;
    ``zeroed``, which zeroes a [chunk, ...] past the routed rows, as x is;
    ``product``, the grouped product of such rows with a held stack, or
    with ``transpose=True`` with its transpose, zeroed). ``order`` must
    reach the chunk's end.

    A grouped product (:func:`grouped_matmul.grouped_product`) visits only
    the row tiles the group sizes cover. Past the routed rows its output
    is whatever the buffer held, NaN included, and so is the cotangent its
    transpose hands back: the rows are zeroed going in and after EVERY
    product, so nothing the tail holds reaches the result, a gradient, or
    (as 0 x NaN) the next product's backward."""
    first = index * chunk
    sizes = _chunk_sizes(rows, chunk=chunk, index=index)
    valid = (first + jnp.arange(chunk) < jnp.sum(rows))[:, None]
    row = lax.dynamic_slice_in_dim(order, first, chunk)

    def zeroed(a):
        return jnp.where(valid, a, 0)

    def product(a, w, transpose=False):
        return zeroed(grouped_matmul.grouped_product(
            a, w, sizes, transpose_rhs=transpose))

    return zeroed(tokens[row // k]), row, zeroed, product


def _grouped_chunk(tokens, gates, order, rows, w_gate, w_in, w_out, *,
                   chunk: int, index):
    """One chunk of sorted rows through the held experts: (y [chunk, D]
    f32, each row its expert's output times its gate, zero past the
    routed rows; token_of [chunk], the token each row belongs to)."""
    k = gates.shape[1]
    with jax.named_scope("moe.dispatch"):
        x, row, _, product = _chunk_rows(
            tokens, order, rows, k=k, chunk=chunk, index=index)
    with jax.named_scope("moe.experts"):
        h = nn.silu(product(x, w_gate)) * product(x, w_in)
        y = product(h, w_out)
    with jax.named_scope("moe.combine"):
        y = y.astype(jnp.float32) * gates.reshape(-1)[row][:, None]
    return y, row // k


def _grouped_chunk_transposed(ct, tokens, gates, order, rows, stacks, *,
                              chunk: int, index):
    """What ``ct`` [N, D] f32, the cotangent of the layer's output, hands
    back through one chunk: (dx [chunk, D] for its rows' tokens, d_gate
    [chunk] f32 for their gates, row [chunk], and (x, h, dg, du, dy), the
    operands of the three stacks' gradients ``x^T dg``, ``x^T du`` and
    ``h^T dy``, which are left to the caller), all zero past the routed
    rows. :func:`_grouped_chunk` computed again and transposed step for
    step, as autodiff would, less those three products; the products
    against the transposed stacks read the stacks as they are."""
    k = gates.shape[1]
    w_gate, w_in, w_out = stacks
    with jax.named_scope("moe.dispatch"):
        x, row, zeroed, product = _chunk_rows(
            tokens, order, rows, k=k, chunk=chunk, index=index)
    with jax.named_scope("moe.experts"):
        h, act = jax.vjp(
            lambda g, u: nn.silu(g) * u, product(x, w_gate),
            product(x, w_in))
        y = product(h, w_out)
    with jax.named_scope("moe.combine"):
        d_y = ct[row // k]
        d_gate = (d_y * y.astype(jnp.float32)).sum(-1)
        dy = zeroed((d_y * gates.reshape(-1)[row][:, None]).astype(y.dtype))
    with jax.named_scope("moe.experts"):
        dg, du = act(product(dy, w_out, transpose=True))
        dx = (product(dg, w_gate, transpose=True)
              + product(du, w_in, transpose=True))
    return dx, d_gate, row, (x, h, dg, du, dy)


def _grouped_moe(tokens, topi, gates, w_gate, w_in, w_out, *,
                 first_expert: int, row_bound: int):
    """The held experts' part of a top-k layer under ONE static row
    bound, no row dropped up to it:
    ``sum_{i in topk, i held} gate_i * E_i(x)``.

    tokens [N, D] (compute dtype), topi / gates [N, k] (global expert ids,
    f32 weights); the expert weights are the HELD stack [held, ...] of
    bias-free SwiGLU experts. The N * k routed rows are sorted by held
    expert, the rows of experts held elsewhere last, and the first
    ``row_bound`` are one :func:`_grouped_chunk`. The products skip the
    tail past the routed rows; the gather, the masks, the silu and the
    weighted scatter-add run on ``row_bound`` rows whatever was routed,
    which is why the layer runs :func:`_chunked_moe` instead.
    Returns (out [N, D] f32, rows [held] int32 routed to each held expert,
    overflow int32 = routed rows past ``row_bound``, which are dropped: 0
    or the bound is wrong)."""
    with jax.named_scope("moe.dispatch"):
        order, rows = _held_rows(
            topi, held=w_in.shape[0], first_expert=first_expert)
    y, token_of = _grouped_chunk(
        tokens, gates, order, rows, w_gate, w_in, w_out, chunk=row_bound,
        index=0)
    with jax.named_scope("moe.combine"):
        out = jnp.zeros(tokens.shape, jnp.float32).at[token_of].add(y)
    return out, rows, jnp.maximum(rows.sum() - row_bound, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunk_loop(chunk, tokens, gates, order, rows, w_gate, w_in, w_out):
    """:func:`_grouped_chunk` after :func:`_grouped_chunk` until the
    chunks hold every routed row: (out [N, D] f32, chunks int32, how many
    ran). ``order`` comes cut to whole chunks.

    A loop of traced length has no reverse-mode rule, so the backward is
    written out. The forward saves its inputs only. The backward is the
    same loop over :func:`_grouped_chunk_transposed`, which computes each
    chunk again; the operands of the stacks' gradients go into buffers of
    every row that can come, and each stack's gradient is ONE grouped
    product over the routed rows after the loop, as under one bound: a
    further chunk costs its rows, not a pass over the stacks."""
    routed = rows.sum()

    def body(state):
        index, out = state
        y, token_of = _grouped_chunk(
            tokens, gates, order, rows, w_gate, w_in, w_out, chunk=chunk,
            index=index)
        with jax.named_scope("moe.combine"):
            return index + 1, out.at[token_of].add(y)

    chunks, out = lax.while_loop(
        lambda state: state[0] * chunk < routed, body,
        (jnp.int32(0), jnp.zeros(tokens.shape, jnp.float32)))
    return out, chunks


def _chunk_loop_fwd(chunk, *operands):
    out, chunks = _chunk_loop(chunk, *operands)
    return (out, chunks), (operands, chunks)


def _chunk_loop_bwd(chunk, saved, cts):
    (tokens, gates, order, rows, *stacks), chunks = saved
    ct, _ = cts
    k = gates.shape[1]
    w_gate, w_in, w_out = stacks

    def body(index, carry):
        d_tokens, d_gates, kept = carry
        dx, d_gate, row, operands = _grouped_chunk_transposed(
            ct, tokens, gates, order, rows, stacks, chunk=chunk,
            index=index)
        with jax.named_scope("moe.combine"):
            d_tokens = d_tokens.at[row // k].add(dx)
            d_gates = d_gates.at[row].add(d_gate)
        with jax.named_scope("moe.experts"):
            kept = tuple(
                lax.dynamic_update_slice_in_dim(b, a, index * chunk, 0)
                for b, a in zip(kept, operands))
        return d_tokens, d_gates, kept

    with jax.named_scope("moe.experts"):
        d, f = w_out.shape[2], w_out.shape[1]
        kept = tuple(
            jnp.zeros((order.shape[0], width), tokens.dtype)
            for width in (d, f, f, f, d))
    d_tokens, d_gates, (x, h, dg, du, dy) = lax.fori_loop(
        0, chunks, body,
        (jnp.zeros_like(tokens), jnp.zeros(gates.size, gates.dtype), kept))
    with jax.named_scope("moe.experts"):
        d_stacks = tuple(
            grouped_matmul.grouped_weight_grad(a, b, rows)
            for a, b in ((x, dg), (x, du), (h, dy)))
        # Only the optimizer waits for the stacks' gradients, so the
        # scheduler would hold every layer's buffers until it runs: tied
        # to the tokens' gradient they are computed here, and the buffers
        # are free for the layer before.
        d_tokens, d_stacks = lax.optimization_barrier((d_tokens, d_stacks))
    # The sort and the counts are integers: no cotangent.
    return (d_tokens, d_gates.reshape(gates.shape), None, None, *d_stacks)


_chunk_loop.defvjp(_chunk_loop_fwd, _chunk_loop_bwd)


def _tiled_rows(rows, *, chunk: int, top: int, tm: int):
    """The rows the row tiles of one forward product cover (tile ``tm``,
    :func:`grouped_matmul.tiled_rows`), summed over the chunks of the
    ``top`` sorted rows: the chunks the loop ran hold every routed row and
    the others none. ``rows.sum()`` where no group boundary cuts a
    tile."""
    index = jnp.arange(top // chunk)[:, None]
    sizes = _chunk_sizes(rows, chunk=chunk, index=index)
    return jax.vmap(lambda s: grouped_matmul.tiled_rows(s, tm))(sizes).sum()


def _chunked_moe(tokens, topi, gates, w_gate, w_in, w_out, *,
                 first_expert: int, chunk: int):
    """:func:`_grouped_moe` on the rows that were routed: as many chunks
    of ``chunk`` sorted rows as hold them (:func:`_chunk_loop`), so what
    the layer gathers, masks and scatters follows the router's load and no
    row can be left out. Returns (out [N, D] f32, rows [held] int32, bound
    int32 = the rows of the chunks that ran, overflow int32 = routed rows
    past them: 0 or the loop stopped early, tiled int32 = the rows the
    grouped kernel's row tiles cover in one forward product of those
    chunks)."""
    n, k = topi.shape
    held, d, f = w_in.shape
    with jax.named_scope("moe.dispatch"):
        order, rows = _held_rows(topi, held=held, first_expert=first_expert)
        # Whole chunks that hold every row that can come.
        top = -(-n * min(k, held) // chunk) * chunk
        order = jnp.pad(order, (0, max(top - n * k, 0)))[:top]
    out, chunks = _chunk_loop(
        chunk, tokens, gates, order, rows, w_gate, w_in, w_out)
    bound = chunks * chunk
    tm, _, _ = grouped_matmul.grouped_tiles(chunk, d, f, w_in.dtype, "gmm")
    tiled = _tiled_rows(rows, chunk=chunk, top=top, tm=tm)
    return out, rows, bound, jnp.maximum(rows.sum() - bound, 0), tiled


class MoEFFN(nn.Module):
    """Switch (top-1) mixture of expert FFNs over flattened tokens.

    ``dispatch``: 'einsum' | 'sorted' | 'auto' (module docstring); 'auto'
    picks sorted once the one-hot dispatch tensors would dominate.
    ``mesh`` routes the sorted engine through its shard_map/all_to_all
    path when the ``model`` (expert) axis — or any token axis — is
    populated; without a mesh the engine runs single-shard.

    ``top_k``: 1 = switch routing (raw top prob as gate); k > 1 =
    GShard-style top-k — each token goes to its k best experts with
    gates normalized over the k choices, expressed as k*N dispatch
    entries ordered choice-major so first choices win capacity slots
    before any second choice. Capacity scales with k
    (``cf * k * N / E``).
    """

    d_model: int
    d_ff: int
    n_experts: int
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    dtype: jnp.dtype = jnp.float32
    dispatch: str = "auto"
    mesh: object = None
    top_k: int = 1
    auto_threshold: int = 1 << 21
    # The 'grouped' layer (:meth:`_grouped`): the share of the experts
    # this layer holds (0 = all of them); what the routed weights are
    # multiplied by and the term beside their sum; the width of the shared
    # expert every token passes beside its routed ones (0 = none); the size
    # of the selection bias's balancing step (0 = the bias stays put);
    # the router's scores, "sigmoid" | "softmax".
    experts_held: int = 0
    first_expert: int = 0
    routed_scale: float = 1.0
    gate_eps: float = 1e-6
    shared_d_ff: int = 0
    bias_update_speed: float = 0.0
    scoring: str = "sigmoid"

    @nn.compact
    def __call__(self, x):  # [B, S, D] -> [B, S, D]
        b, s, d = x.shape
        n = b * s
        e = self.n_experts
        k = self.top_k
        if not 1 <= k <= e:
            raise ValueError(f"top_k={k} must be in [1, n_experts={e}]")
        if self.dispatch not in ("auto", "sorted", "einsum", "grouped"):
            raise ValueError(
                f"moe_dispatch={self.dispatch!r} must be "
                "'auto' | 'sorted' | 'einsum' | 'grouped'"
            )
        if self.dispatch == "grouped":
            return self._grouped(x)
        if self.experts_held:
            raise ValueError(
                "experts_held belongs to moe_dispatch='grouped'"
            )
        capacity = max(1, int(self.capacity_factor * k * n / e))
        tokens = x.reshape(n, d)

        logits = TorchStyleDense(e, dtype=jnp.float32, name="router")(
            jnp.asarray(tokens, jnp.float32)
        )  # [N, E] — routing in f32: tiny matmul, decides everything
        probs = jax.nn.softmax(logits, axis=-1)
        if k == 1:
            expert_choice = jnp.argmax(probs, axis=-1)[None, :]  # [1, N]
            gate_choice = jnp.max(probs, axis=-1)[None, :]
        else:
            topv, topi = jax.lax.top_k(probs, k)  # [N, k]
            gates = topv / jnp.maximum(
                topv.sum(axis=-1, keepdims=True), 1e-9
            )
            expert_choice = topi.T  # [k, N], choice-major
            gate_choice = gates.T
        expert_idx = expert_choice[0]  # first choice: aux loss + einsum path
        gate = gate_choice[0]

        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # [N, E]

        # Switch load-balance loss: E * sum_e(frac_tokens_e * mean_prob_e),
        # sown pre-weighted — the train step adds every aux_loss leaf as-is.
        frac = onehot.mean(axis=0)
        mean_prob = probs.mean(axis=0)
        self.sow(
            "aux_loss",
            "load_balance",
            self.aux_weight * e * jnp.sum(frac * mean_prob),
        )

        w_in = self.param(
            "experts_in_kernel",
            lambda k, sh, dt=jnp.float32: torch_linear_init()(k, sh, dt, fan_in=d),
            (e, d, self.d_ff),
            jnp.float32,
        )
        b_in = self.param(
            "experts_in_bias",
            lambda k, sh, dt=jnp.float32: torch_linear_init()(k, sh, dt, fan_in=d),
            (e, self.d_ff),
            jnp.float32,
        )
        w_out = self.param(
            "experts_out_kernel",
            lambda k, sh, dt=jnp.float32: torch_linear_init()(
                k, sh, dt, fan_in=self.d_ff
            ),
            (e, self.d_ff, d),
            jnp.float32,
        )
        b_out = self.param(
            "experts_out_bias",
            lambda k, sh, dt=jnp.float32: torch_linear_init()(
                k, sh, dt, fan_in=self.d_ff
            ),
            (e, d),
            jnp.float32,
        )

        ct = self.dtype
        wi, bi = jnp.asarray(w_in, ct), jnp.asarray(b_in, ct)
        wo, bo = jnp.asarray(w_out, ct), jnp.asarray(b_out, ct)

        # Flat dispatch entries, choice-major ([all 1st choices; all 2nd
        # choices; ...]): a stable sort / cumsum over this order gives
        # first choices capacity priority, the GShard convention.
        flat_idx = expert_choice.reshape(k * n).astype(jnp.int32)
        flat_gate = jnp.asarray(gate_choice.reshape(k * n), ct)

        engine = self.dispatch
        if engine == "auto":
            # One-hot dispatch materializes [kN, E, C] twice; past
            # ``auto_threshold`` (elements of that tensor) the sort-based
            # engine wins on both memory and time. Default ~2^21; set
            # DCT_MOE_AUTO_THRESHOLD (-> ModelConfig.moe_auto_threshold)
            # once measured on the target chip (no cell has, ROADMAP D6).
            engine = (
                "sorted"
                if k * n * e * capacity >= self.auto_threshold
                else "einsum"
            )
        mesh = self.mesh
        if engine == "sorted" and mesh is not None:
            dp = mesh.shape.get("data", 1)
            sp = mesh.shape.get("seq", 1)
            ep = mesh.shape.get("model", 1)
            sharded = dp > 1 or sp > 1 or ep > 1
            ok = (
                b % dp == 0 and s % sp == 0 and e % ep == 0
                and ((b // dp) * (s // sp)) % ep == 0
            )
            if sharded and not ok:
                if b < dp:
                    # The batch-1 flax init trace cannot tile the data
                    # axis (same escape as ring_attention's dense path);
                    # the einsum engine creates identical params.
                    engine = "einsum"
                elif self.dispatch == "sorted":
                    raise ValueError(
                        f"sorted MoE dispatch cannot tile tokens [B={b}, "
                        f"S={s}] experts E={e} over mesh data={dp}, "
                        f"seq={sp}, model={ep}"
                    )
                else:
                    engine = "einsum"  # auto: fall back rather than fail
            elif sharded:
                out = self._sorted_sharded(
                    jnp.asarray(x, ct),
                    expert_choice.reshape(k, b, s),
                    jnp.asarray(gate_choice, ct).reshape(k, b, s),
                    wi, bi, wo, bo, mesh=mesh, dp=dp, sp=sp, ep=ep,
                )
                return out

        toks_ct = jnp.asarray(tokens, ct)
        if engine == "sorted":
            flat_tokens = jnp.tile(toks_ct, (k, 1)) if k > 1 else toks_ct
            out2 = _sorted_moe(
                flat_tokens, flat_idx, flat_gate, wi, bi, wo, bo,
                e_total=e, capacity=capacity,
            )
            out = out2.reshape(k, n, d).sum(axis=0) if k > 1 else out2
            return out.reshape(b, s, d)

        # Slot of each entry within its expert (arrival order over the
        # choice-major flat entries).
        onehot_f = jax.nn.one_hot(flat_idx, e, dtype=jnp.float32)
        position = jnp.cumsum(onehot_f, axis=0) - onehot_f  # [kN, E]
        keep = (position < capacity).astype(jnp.float32) * onehot_f
        slot = jax.nn.one_hot(
            jnp.sum(position * onehot_f, axis=-1).astype(jnp.int32),
            capacity,
            dtype=jnp.float32,
        )  # [kN, C]
        dispatch = keep[:, :, None] * slot[:, None, :]  # [kN, E, C]

        disp = jnp.asarray(dispatch, ct)
        toks = jnp.tile(toks_ct, (k, 1)) if k > 1 else toks_ct
        expert_in = jnp.einsum("nec,nd->ecd", disp, toks)  # [E, C, D]
        h = jnp.einsum("ecd,edf->ecf", expert_in, wi)
        h = nn.gelu(h + bi[:, None, :])
        out_e = jnp.einsum("ecf,efd->ecd", h, wo)
        out_e = out_e + bo[:, None, :]
        out2 = jnp.einsum("nec,ecd->nd", disp, out_e)
        out2 = out2 * flat_gate[:, None]
        out = out2.reshape(k, n, d).sum(axis=0) if k > 1 else out2
        return out.reshape(b, s, d)

    def _grouped(self, x):
        """The dropless layer of bias-free SwiGLU experts, as one chip's
        share of an expert-parallel layer: the router is ``n_experts``
        wide and the top ``top_k`` are chosen over all of them; this layer
        holds experts ``first_expert .. first_expert + experts_held - 1``
        and computes their part of the result
        (:func:`_grouped_moe`). What the experts held elsewhere would add
        is left out; no exchange, nothing stands in for the other chips.

        Scores, ``scoring="softmax"``: the softmax of the logits over all
        ``n_experts``; the top ``top_k`` probabilities choose, and the
        weights are the chosen ones over their sum (times
        ``routed_scale``). No selection bias is in the tree and nothing
        is sown into ``param_steps``. What keeps the load even is the
        balance loss of the Switch / Mixtral family, sown into
        ``aux_loss``: ``aux_weight x E x sum_i f_i P_i`` over ALL experts
        from this layer's tokens, ``f_i`` the share of the N x k
        assignments that chose expert ``i`` (no gradient) and ``P_i`` its
        mean probability. ``counters`` get the term (``moe_aux_loss``)
        and every expert's assignments (``moe_rows_all``, with the
        uniform expectation beside it).

        Scores, ``scoring="sigmoid"``: a sigmoid an expert, with a
        per-expert bias added for
        the SELECTION only (a parameter under ``stop_gradient``: the
        optimizer sees a zero gradient and leaves it where it is). The
        weights are the chosen experts' scores, without the bias, over
        their sum (+ ``gate_eps``), times ``routed_scale``. With
        ``bias_update_speed`` u > 0 the layer sows the bias's balancing
        step into ``param_steps`` under the parameter's name, and the
        train step adds it after the optimizer's: ``u x sign(mean(c) -
        c)``, ``c`` the rows this batch routed to each of the
        ``n_experts`` (the auxiliary-loss-free rule of DeepSeek-V3: an
        overloaded expert's bias falls, an underloaded one's rises),
        and ``moe_bias_abs_max`` into ``counters``. With ``shared_d_ff``
        every token also passes ONE bias-free SwiGLU of that width (the
        model's shared experts side by side), computed whole on every
        share: summing shares counts it once a share. The grouped
        engine runs as many chunks of sorted rows as hold what the router
        sent here this step (:func:`_chunked_moe`; a chunk is 1.25 x the
        even share ``N * top_k * held / n_experts``), so no row is ever left
        out (``capacity_factor`` belongs to the engines that drop). Sows
        ``counters`` (rows per held expert, the rows of the chunks the
        step ran, routed rows past them, the rows the grouped kernel's row
        tiles cover in one forward product of those chunks, the uniform
        expectation per expert) and, for a caller that asks,
        ``intermediates`` (the chosen experts)."""
        b, s, d = x.shape
        n, e, k = b * s, self.n_experts, self.top_k
        held = self.experts_held or e
        if not 0 <= self.first_expert <= e - held:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + held - 1}"
                f" are not among n_experts={e}"
            )
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(
                f"router scoring {self.scoring!r} must be 'sigmoid' or "
                "'softmax'"
            )
        softmax = self.scoring == "softmax"
        if softmax and self.bias_update_speed:
            raise ValueError(
                "bias_update_speed belongs to the sigmoid router's "
                "selection bias; a softmax router has none"
            )
        tokens = x.reshape(n, d)
        with jax.named_scope("moe.route"):
            # f32 at full precision: a [N, D] x [D, E] product decides
            # which experts run.
            with jax.default_matmul_precision("highest"):
                logits = TorchStyleDense(
                    e, dtype=jnp.float32, use_bias=False, name="router"
                )(jnp.asarray(tokens, jnp.float32))
            if softmax:
                probs = jax.nn.softmax(logits, axis=-1)
                chosen, topi = lax.top_k(probs, k)
                gates = chosen / chosen.sum(axis=-1, keepdims=True)
                load = jnp.bincount(topi.reshape(-1), length=e)
                balance = self.aux_weight * e * jnp.sum(
                    load.astype(jnp.float32) / (n * k) * probs.mean(axis=0))
                if self.aux_weight:
                    self.sow("aux_loss", "load_balance", balance)
                self.sow("counters", "moe_aux_loss", balance)
                self.sow("counters", "moe_rows_all", load)
                self.sow(
                    "counters", "moe_rows_all_uniform", jnp.float32(n * k / e))
            else:
                scores = jax.nn.sigmoid(logits)
                bias = self.param(
                    "expert_bias", nn.initializers.zeros, (e,), jnp.float32
                )
                _, topi = lax.top_k(scores + lax.stop_gradient(bias), k)
                chosen = jnp.take_along_axis(scores, topi, axis=-1)
                gates = chosen / (
                    chosen.sum(axis=-1, keepdims=True) + self.gate_eps)
            if self.routed_scale != 1.0:
                gates = gates * self.routed_scale
            if self.bias_update_speed:
                load = jnp.bincount(topi.reshape(-1), length=e).astype(
                    jnp.float32)
                self.sow(
                    "param_steps", "expert_bias",
                    self.bias_update_speed * jnp.sign(load.mean() - load),
                    reduce_fn=lambda _, step: step, init_fn=lambda: None,
                )
                self.sow(
                    "counters", "moe_bias_abs_max", jnp.abs(bias).max())
        self.sow("intermediates", "topk", topi)

        def stack(name, fan_in, shape):
            w = self.param(
                name,
                lambda key, sh, dt=jnp.float32: torch_linear_init()(
                    key, sh, dt, fan_in=fan_in
                ),
                (held, *shape),
                jnp.float32,
            )
            return jnp.asarray(w, self.dtype)

        w_gate = stack("experts_gate_kernel", d, (d, self.d_ff))
        w_in = stack("experts_in_kernel", d, (d, self.d_ff))
        w_out = stack("experts_out_kernel", self.d_ff, (self.d_ff, d))
        out, rows, bound, overflow, tiled = _chunked_moe(
            jnp.asarray(tokens, self.dtype), topi, gates, w_gate, w_in,
            w_out, first_expert=self.first_expert,
            # A quarter over what an even router sends the held experts:
            # the balancing (the bias's update, the balance loss) holds a
            # share within a few percent of the even one, so a step takes
            # one chunk, and every row of a chunk past the routed ones is
            # gathered, masked and scattered all the same. A heavier
            # share takes a further chunk.
            chunk=-(-5 * n * k * held // (4 * e)),
        )
        self.sow("counters", "moe_rows", rows)
        self.sow("counters", "moe_rows_bound", bound)
        self.sow("counters", "moe_rows_overflowed", overflow)
        self.sow("counters", "moe_rows_tiled", tiled)
        self.sow("counters", "moe_rows_uniform", jnp.float32(n * k / e))
        out = jnp.asarray(out, self.dtype)
        if self.shared_d_ff:
            with jax.named_scope("moe.shared"):
                dense = functools.partial(
                    TorchStyleDense, dtype=self.dtype, use_bias=False)
                t = jnp.asarray(tokens, self.dtype)
                hidden = nn.silu(
                    dense(self.shared_d_ff, name="shared_gate")(t)
                ) * dense(self.shared_d_ff, name="shared_in")(t)
                out = out + dense(d, name="shared_out")(hidden)
        return out.reshape(b, s, d)

    def _sorted_sharded(self, x, expert_choice, gate_choice, wi, bi, wo,
                        bo, *, mesh, dp: int, sp: int, ep: int):
        """Sorted dispatch under the mesh: shard_map over (data, seq,
        model). Each model-rank routes its 1/ep slice of the local tokens
        (expert compute is SHARDED, not replicated), exchanges expert
        buffers with lax.all_to_all, and all-gathers the combined outputs
        back to replicated-over-model activations. ``expert_choice`` /
        ``gate_choice`` are [k, B, S] (k routing choices per token)."""
        b, s, d = x.shape
        e = self.n_experts
        k = expert_choice.shape[0]
        n_local = (b // dp) * (s // sp)
        chunk = n_local // ep
        cap = max(1, int(self.capacity_factor * k * chunk / e))

        def body(xb, ei, gt, wi, bi, wo, bo):
            toks = xb.reshape(-1, d)
            ei = ei.reshape(k, -1).astype(jnp.int32)
            gt = gt.reshape(k, -1)
            r = lax.axis_index("model")
            tok_my = lax.dynamic_slice_in_dim(toks, r * chunk, chunk, 0)
            ei_my = lax.dynamic_slice_in_dim(ei, r * chunk, chunk, 1)
            gt_my = lax.dynamic_slice_in_dim(gt, r * chunk, chunk, 1)
            flat_tokens = (
                jnp.tile(tok_my, (k, 1)) if k > 1 else tok_my
            )
            out2 = _sorted_moe(
                flat_tokens, ei_my.reshape(k * chunk),
                gt_my.reshape(k * chunk), wi, bi, wo, bo,
                e_total=e, capacity=cap, ep_axis="model",
            )
            out_my = (
                out2.reshape(k, chunk, d).sum(axis=0) if k > 1 else out2
            )
            out = lax.all_gather(out_my, "model", axis=0, tiled=True)
            return out.reshape(xb.shape)

        # check_vma=False: the closing all_gather makes the output
        # replicated over ``model``, but the vma type system cannot prove
        # value-equality after a collective; numerics are pinned against
        # the single-shard engine by tests.
        return shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P("data", "seq", None),
                P(None, "data", "seq"), P(None, "data", "seq"),
                P("model", None, None), P("model", None),
                P("model", None, None), P("model", None),
            ),
            out_specs=P("data", "seq", None),
            check_vma=False,
        )(x, expert_choice, gate_choice, wi, bi, wo, bo)


class MoEBlock(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int
    n_experts: int
    capacity_factor: float
    dropout: float
    attn_fn: object
    aux_weight: float = 0.01
    dtype: jnp.dtype = jnp.float32
    dispatch: str = "auto"
    mesh: object = None
    top_k: int = 1
    auto_threshold: int = 1 << 21
    n_kv_heads: int | None = None
    rope: bool = False

    @nn.compact
    def __call__(self, x, *, train: bool):
        h = nn.LayerNorm(dtype=self.dtype, name="ln_attn")(x)
        h = MultiHeadAttention(
            self.d_model, self.n_heads, self.attn_fn, dtype=self.dtype,
            n_kv_heads=self.n_kv_heads, rope=self.rope, name="attn",
        )(h)
        h = nn.Dropout(rate=self.dropout, deterministic=not train)(h)
        x = x + h
        h = nn.LayerNorm(dtype=self.dtype, name="ln_ffn")(x)
        h = MoEFFN(
            self.d_model, self.d_ff, self.n_experts, self.capacity_factor,
            aux_weight=self.aux_weight, dtype=self.dtype,
            dispatch=self.dispatch, mesh=self.mesh, top_k=self.top_k,
            auto_threshold=self.auto_threshold,
            name="moe",
        )(h)
        h = nn.Dropout(rate=self.dropout, deterministic=not train)(h)
        return x + h


class WeatherMoE(nn.Module):
    """MoE encoder over [B, S, F] windows -> [B, num_classes] rain logits."""

    input_dim: int
    seq_len: int
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 128
    n_experts: int = 4
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    num_classes: int = 2
    dropout: float = 0.1
    attn_fn: object = None
    compute_dtype: jnp.dtype = jnp.float32
    dispatch: str = "auto"
    mesh: object = None
    top_k: int = 1
    auto_threshold: int = 1 << 21
    n_kv_heads: int | None = None
    pos_embed: str = "sincos"

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        from dct_tpu.ops.attention import make_attention_fn

        attn_fn = self.attn_fn or make_attention_fn(None)
        x = jnp.asarray(x, self.compute_dtype)
        h = TorchStyleDense(self.d_model, dtype=self.compute_dtype, name="in_proj")(x)
        if self.pos_embed != "rope":  # rope rotates q/k inside attention
            h = h + jnp.asarray(
                sincos_positions(self.seq_len, self.d_model),
                self.compute_dtype,
            )
        for i in range(self.n_layers):
            h = MoEBlock(
                self.d_model,
                self.n_heads,
                self.d_ff,
                self.n_experts,
                self.capacity_factor,
                self.dropout,
                attn_fn,
                aux_weight=self.router_aux_weight,
                dtype=self.compute_dtype,
                dispatch=self.dispatch,
                mesh=self.mesh,
                top_k=self.top_k,
                auto_threshold=self.auto_threshold,
                n_kv_heads=self.n_kv_heads,
                rope=self.pos_embed == "rope",
                name=f"block_{i}",
            )(h, train=train)
        h = nn.LayerNorm(dtype=self.compute_dtype, name="ln_out")(h)
        pooled = h.mean(axis=1)
        logits = TorchStyleDense(
            self.num_classes, dtype=self.compute_dtype, name="head"
        )(pooled)
        return jnp.asarray(logits, jnp.float32)

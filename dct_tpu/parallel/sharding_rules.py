"""Declarative partition rules: regex path patterns -> PartitionSpecs.

The scaling-book recipe, made first-class for the CONTINUOUS-training
path (ROADMAP item 1): modules carry load-bearing NAMES (``qkv_proj``/
``ffn_in`` = column-parallel, ``o_proj``/``ffn_out`` = row-parallel), a
per-family RULE TABLE maps ``/``-joined parameter paths to
``PartitionSpec``s over the ``data``/``model``/``seq``/``pipe`` mesh
axes, and ``jit`` inserts the collectives. No imperative communication
anywhere — the analog of the reference's gloo all-reduce is a compiler
decision.

Applied to the WHOLE TrainState: Adam's ``mu``/``nu`` mirror the param
tree, so the same path-pattern match shards optimizer state identically
— giving tensor-parallel training a fully sharded optimizer for free;
``shard_opt``/``shard_params`` additionally split the unmatched leaves'
leading dim over ``data`` (ZeRO-1 / FSDP, per "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training").

The rule surface (docs/PARALLELISM.md §partition rules):

- :data:`FAMILY_RULES` — the per-family default tables (regex, spec);
- ``DCT_SHARD_RULES`` — operator overrides prepended to the family
  table: ``pattern=axes[;pattern=axes...]`` where ``axes`` is a
  comma-separated per-dimension axis list (``data``/``model``/``seq``/
  ``pipe``; ``-`` = replicated dim; the empty string = fully
  replicated leaf). First match wins.
- :func:`match_partition_rules` / :func:`make_shard_and_gather_fns` —
  the snippet-style primitives: a spec tree from the rules, and paired
  place/gather callables per leaf (gather is what the publish path —
  checkpoint deploy tier, package export — runs so serving artifacts
  stay dense).
"""

from __future__ import annotations

import hashlib
import os
import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_NAMES = ("data", "model", "seq", "pipe")

# The transformer-family name rules (column-parallel shards the OUTPUT
# dim, row-parallel the INPUT dim; a row-parallel bias stays replicated
# — it is added after the row all-reduce), plus expert parallelism:
# MoE expert weights are [E, ...] stacks whose leading expert dim
# shards over ``model`` (each shard owns whole experts; the dispatch
# einsum's token exchange compiles to an all-to-all over the same
# axis). The router stays replicated (no rule matches it). Patterns
# are regexes over the ``/``-joined path (params AND their opt_state
# moment mirrors — the moments embed the same path tail).
_TENSOR_PARALLEL_RULES = (
    (r"(^|/)experts_in_kernel$", P("model", None, None)),
    (r"(^|/)experts_in_bias$", P("model", None)),
    (r"(^|/)experts_out_kernel$", P("model", None, None)),
    (r"(^|/)experts_out_bias$", P("model", None)),
    (r"(qkv_proj|ffn_in).*/kernel$", P(None, "model")),
    (r"(qkv_proj|ffn_in).*/bias$", P("model")),
    (r"(o_proj|ffn_out).*/kernel$", P("model", None)),
    (r"(o_proj|ffn_out).*/bias$", P()),
)

#: Per-family default rule tables. Families without an entry use
#: ``None``'s table (the tensor-parallel name rules — a family whose
#: params match no pattern, like the MLP, replicates everywhere, which
#: is exactly pure DP). Override or extend via ``DCT_SHARD_RULES``.
FAMILY_RULES: dict = {
    None: _TENSOR_PARALLEL_RULES,
    "weather_mlp": _TENSOR_PARALLEL_RULES,
    "weather_gru": _TENSOR_PARALLEL_RULES,
    "weather_transformer": _TENSOR_PARALLEL_RULES,
    "weather_transformer_causal": _TENSOR_PARALLEL_RULES,
    "weather_transformer_pp": _TENSOR_PARALLEL_RULES,
    "weather_moe": _TENSOR_PARALLEL_RULES,
    # The gated MLP's second column-parallel matrix and the gated
    # experts' third stack.
    "weather_hybrid_moe_causal": (
        (r"(^|/)experts_gate_kernel$", P("model", None, None)),
        (r"ffn_gate.*/kernel$", P(None, "model")),
    ) + _TENSOR_PARALLEL_RULES,
}


def parse_rules(text: str):
    """``DCT_SHARD_RULES`` grammar -> tuple of (regex, PartitionSpec).

    ``pattern=axes[;pattern=axes...]``: ``pattern`` is a regex matched
    (``re.search``) against the leaf's ``/``-joined path; ``axes`` is a
    comma-separated per-dimension list of mesh axis names (``-`` for a
    replicated dimension, the empty string for a fully replicated
    leaf). Examples::

        .*dense.*/kernel$=-,model      # shard the output dim
        head/kernel$=                  # force-replicate
    Malformed specs raise ``ValueError`` naming the offending clause —
    a typo'd layout must never silently train replicated.
    """
    rules = []
    for clause in (text or "").split(";"):
        clause = clause.strip()
        if not clause:
            continue
        if "=" not in clause:
            raise ValueError(
                f"DCT_SHARD_RULES clause {clause!r} has no '=': expected "
                "pattern=axis,axis,..."
            )
        pattern, _, axes = clause.rpartition("=")
        pattern = pattern.strip()
        try:
            re.compile(pattern)
        except re.error as e:
            raise ValueError(
                f"DCT_SHARD_RULES pattern {pattern!r} is not a valid "
                f"regex: {e}"
            ) from e
        dims = []
        if axes.strip():
            for tok in axes.split(","):
                tok = tok.strip()
                if tok in ("-", "", "none", "None"):
                    dims.append(None)
                elif tok in AXIS_NAMES:
                    dims.append(tok)
                else:
                    raise ValueError(
                        f"DCT_SHARD_RULES clause {clause!r}: unknown mesh "
                        f"axis {tok!r} (valid: {', '.join(AXIS_NAMES)}, "
                        "'-' for a replicated dim)"
                    )
        rules.append((pattern, P(*dims)))
    return tuple(rules)


#: parse_rules memo keyed by the raw env string: rule resolution runs
#: once per TREE LEAF (spec_for_path inside the sharding tree-map), and
#: re-validating every regex clause per leaf is pure waste — the env
#: string is invariant within a placement pass.
_PARSE_CACHE: dict[str, tuple] = {}


def rules_for_family(family: str | None = None):
    """The ACTIVE rule table for ``family``: any ``DCT_SHARD_RULES``
    overrides first (first match wins), then the family's defaults."""
    base = FAMILY_RULES.get(family, FAMILY_RULES[None])
    env = os.environ.get("DCT_SHARD_RULES")
    if not env:
        return tuple(base)
    cached = _PARSE_CACHE.get(env)
    if cached is None:
        cached = parse_rules(env)
        if len(_PARSE_CACHE) > 8:  # bound: env strings are few
            _PARSE_CACHE.clear()
        _PARSE_CACHE[env] = cached
    return cached + tuple(base)


def rules_digest(family: str | None = None) -> str:
    """Content digest of the active rule table — part of the AOT
    executable identity (a layout change recompiles; the same layout
    warm-relaunches) and the checkpoint layout manifest."""
    blob = "|".join(
        f"{pat}={','.join(str(a) for a in spec)}"
        for pat, spec in rules_for_family(family)
    )
    return hashlib.sha1(blob.encode()).hexdigest()[:10]


def path_str(path) -> str:
    """A tree path -> the ``/``-joined string the rule regexes match."""
    return "/".join(
        str(getattr(k, "key", getattr(k, "name", k))) for k in path
    )


# ----------------------------------------------------------------------
# Dtype rules: the precision analog of the partition rules. The SAME
# regex-over-param-path grammar as DCT_SHARD_RULES selects which param
# leaves run the forward/backward in low precision
# (``DCT_DTYPE_RULES='.*=bf16'`` = bf16 compute everywhere), while the
# MASTER params, gradients-as-accumulated, and optimizer state stay
# f32: the cast happens INSIDE the traced loss body (train/steps.py),
# so autodiff's cast-vjp routes the bf16 gradients back into f32
# accumulation and nothing below the loss ever sees the low-precision
# copy. Rules off (the default) is the bitwise status quo.

#: Accepted dtype tokens (right-hand side of a clause) -> canonical
#: jax dtype name.
DTYPE_ALIASES = {
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "f16": "float16", "float16": "float16",
    "f32": "float32", "float32": "float32",
}


def parse_dtype_rules(text: str):
    """``DCT_DTYPE_RULES`` grammar -> tuple of (regex, dtype name).

    ``pattern=dtype[;pattern=dtype...]`` — the clause grammar of
    :func:`parse_rules` with a dtype token (bf16/bfloat16, f16/float16,
    f32/float32) where the axis list would be. Malformed specs raise
    ``ValueError`` naming the offending clause — a typo'd precision
    must never silently train full-width."""
    rules = []
    for clause in (text or "").split(";"):
        clause = clause.strip()
        if not clause:
            continue
        if "=" not in clause:
            raise ValueError(
                f"DCT_DTYPE_RULES clause {clause!r} has no '=': expected "
                "pattern=dtype"
            )
        pattern, _, dname = clause.rpartition("=")
        pattern = pattern.strip()
        try:
            re.compile(pattern)
        except re.error as e:
            raise ValueError(
                f"DCT_DTYPE_RULES pattern {pattern!r} is not a valid "
                f"regex: {e}"
            ) from e
        canonical = DTYPE_ALIASES.get(dname.strip().lower())
        if canonical is None:
            raise ValueError(
                f"DCT_DTYPE_RULES clause {clause!r}: unknown dtype "
                f"{dname.strip()!r} (valid: "
                f"{', '.join(sorted(set(DTYPE_ALIASES)))})"
            )
        rules.append((pattern, canonical))
    return tuple(rules)


_DTYPE_PARSE_CACHE: dict[str, tuple] = {}


def dtype_rules():
    """The active ``DCT_DTYPE_RULES`` table (empty tuple when unset) —
    memoized per env string like the partition-rule cache."""
    env = os.environ.get("DCT_DTYPE_RULES")
    if not env:
        return ()
    cached = _DTYPE_PARSE_CACHE.get(env)
    if cached is None:
        cached = parse_dtype_rules(env)
        if len(_DTYPE_PARSE_CACHE) > 8:
            _DTYPE_PARSE_CACHE.clear()
        _DTYPE_PARSE_CACHE[env] = cached
    return cached


def dtype_rules_digest() -> str:
    """Content digest of the active dtype rules, joined into the AOT
    program identity (trainer) and the checkpoint layout manifest: a
    precision change is a LOUD cache miss, never a stale executable.
    ``"off"`` when no rules are set, so every pre-rules artifact and
    manifest keys identically."""
    rules = dtype_rules()
    if not rules:
        return "off"
    blob = "|".join(f"{pat}={dname}" for pat, dname in rules)
    return hashlib.sha1(blob.encode()).hexdigest()[:10]


def cast_params_by_rules(params):
    """Cast float param leaves whose ``/``-joined path matches a dtype
    rule (first match wins; unmatched and non-float leaves untouched).

    Called INSIDE the jitted loss/eval bodies on the f32 master params:
    under ``jax.value_and_grad`` the cast's vjp widens the incoming
    bf16 cotangents back to f32, so gradient ACCUMULATION and the
    optimizer update run full-width — the mixed-precision
    master-weight contract (docs/PARALLELISM.md §dtype rules)."""
    rules = dtype_rules()
    if not rules:
        return params
    import jax.numpy as jnp

    def one(path, leaf):
        dt = getattr(leaf, "dtype", None)
        if dt is None or not jnp.issubdtype(dt, jnp.floating):
            return leaf
        name = path_str(path)
        for pattern, dname in rules:
            if re.search(pattern, name):
                return leaf.astype(getattr(jnp, dname))
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)


def match_partition_rules(rules, tree):
    """Spec tree for ``tree`` under ``rules`` (the snippet-style
    primitive): scalars and unmatched leaves replicate (``P()`` — the
    pure-DP MLP matches nothing and fully replicates), first matching
    rule wins. Works over params alone or a whole TrainState tree
    (optimizer-state moment mirrors embed the same path tails)."""

    def one(path, leaf):
        if getattr(leaf, "ndim", 0) == 0:
            return P()
        name = path_str(path)
        for pattern, spec in rules:
            if re.search(pattern, name):
                return spec
        return P()

    return jax.tree_util.tree_map_with_path(one, tree)


def spec_for_path(path, ndim: int | None = None, family: str | None = None) -> P:
    names = [str(getattr(k, "key", k)) for k in path]
    leaf = names[-1] if names else ""
    if "pp_stages" in names:
        # Pipeline stages: stacked [n_stages, ...] leaves, stage dim on
        # ``pipe`` — one stage per pipeline device. The INNER dims keep
        # their tensor-parallel rule placement (PP x TP compose:
        # pipeline_apply's shard_map is manual only over pipe/data, so
        # the model-axis sharding survives into the stage compute).
        # Structural, not regex: the pad depends on the leaf's ndim.
        inner_names = names[names.index("pp_stages") + 1:]
        inner_path = "/".join(inner_names)
        inner = P()
        for pattern, spec in rules_for_family(family):
            if re.search(pattern, inner_path):
                inner = spec
                break
        n = ndim if ndim is not None else 2
        pad = n - 1 - len(inner)
        return P("pipe", *inner, *([None] * max(pad, 0)))
    name = "/".join(names)
    for pattern, spec in rules_for_family(family):
        if re.search(pattern, name):
            return spec
    return P()


def _data_shard_spec(leaf, mesh: Mesh) -> P | None:
    """Data-axis leading-dim sharding for a leaf that divides evenly.

    Applied to optimizer-state leaves this is ZeRO-1 weight-update
    sharding (XLA reduce-scatters gradients into the sharded Adam
    moments and all-gathers the updates back); applied to param leaves
    too it is FSDP/ZeRO-3 — each data rank stores 1/N of every weight,
    and XLA inserts the all-gather-on-use in forward/backward. Both are
    pure layout annotations: no imperative communication."""
    shape = getattr(leaf, "shape", ())
    data = mesh.shape["data"]
    if data > 1 and len(shape) >= 1 and shape[0] % data == 0 and shape[0] >= data:
        return P("data", *([None] * (len(shape) - 1)))
    return None


def state_shardings(
    state, mesh: Mesh, *, shard_opt: bool = False, shard_params: bool = False,
    family: str | None = None,
):
    """NamedSharding tree for a TrainState under the family rule table.
    Scalars/rngs/unmatched params replicate; matched params (and their
    mirrored Adam moments) shard over ``model``. With ``shard_opt``,
    otherwise-replicated optimizer-state leaves additionally shard their
    leading dim over ``data`` (ZeRO-1); with ``shard_params``, the params
    themselves (and their moment mirrors) do too — FSDP/ZeRO-3, where
    params, gradients, and optimizer state all live 1/N-sharded and XLA
    all-gathers weights on use (see :func:`_data_shard_spec`).
    Tensor-parallel matches keep their ``model``-axis placement — TP and
    FSDP compose axis-wise, the scaling-book combined recipe."""

    def one(path, leaf):
        if getattr(leaf, "ndim", 0) == 0:
            return NamedSharding(mesh, P())
        spec = spec_for_path(
            path, ndim=getattr(leaf, "ndim", None), family=family
        )
        if spec == P():
            names = {
                str(getattr(k, "key", getattr(k, "name", k))) for k in path
            }
            eligible = (
                (shard_opt and "opt_state" in names)
                or (shard_params and ("opt_state" in names or "params" in names))
            )
            if eligible:
                data_spec = _data_shard_spec(leaf, mesh)
                if data_spec is not None:
                    spec = data_spec
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, state)


def shard_state_with_rules(
    state, mesh: Mesh, *, shard_opt: bool = False, shard_params: bool = False,
    family: str | None = None,
):
    """Place a TrainState: tensor-parallel where rules match, replicated
    elsewhere (the pure-DP MLP matches nothing and fully replicates,
    keeping :func:`dct_tpu.parallel.mesh.shard_state` semantics).
    ``shard_opt`` opts optimizer state into data-axis weight-update
    sharding (ZeRO-1); ``shard_params`` additionally shards the params
    (FSDP/ZeRO-3)."""
    return jax.device_put(
        state,
        state_shardings(
            state, mesh, shard_opt=shard_opt, shard_params=shard_params,
            family=family,
        ),
    )


# ----------------------------------------------------------------------
# Shard/gather fns: the paired place/publish callables (snippet [1]/[2]
# idiom). ``gather`` is the publish contract: every path that exports
# TrainState params out of the mesh (checkpoint deploy tier, package
# export, serving) must produce DENSE host arrays — a sharded jax.Array
# leaking into a package would serve one shard's weights as the model.
# dct-lint rule ``gather-on-publish`` enforces the call sites.


def gather_leaf(leaf) -> np.ndarray:
    """One leaf -> a dense host ndarray, whatever its placement.

    Arrays sharded across processes (TP/SP spanning hosts) are not
    fully addressable and cannot be ``device_get``; they are assembled
    with a cross-process allgather instead. NB: the allgather is a
    COLLECTIVE — when any leaf is non-addressable, every process must
    run the gather (the Trainer does: it gathers on all ranks, then
    gates the file write on the coordinator)."""
    if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(leaf, tiled=True))
    return np.asarray(jax.device_get(leaf))


def gather_tree(tree):
    """Device tree -> dense host numpy tree via :func:`gather_leaf`
    (the gather half of :func:`make_shard_and_gather_fns`, applied
    uniformly — what ``checkpoint.manager.to_host`` delegates to)."""
    return jax.tree.map(gather_leaf, tree)


def _as_dtype(spec) -> np.dtype:
    """A dtype-like (np/jnp dtype, scalar type, or alias string like
    ``'bf16'``) -> concrete ``np.dtype`` (bfloat16 resolves through
    jax's extended-dtype registry)."""
    if isinstance(spec, str):
        import jax.numpy as jnp

        name = DTYPE_ALIASES.get(spec.strip().lower(), spec)
        return np.dtype(getattr(jnp, name, name))
    return np.dtype(spec)


def _is_dtype_like(x) -> bool:
    """True for anything ``_as_dtype`` accepts as ONE dtype (a string,
    dtype, or scalar type) — i.e. NOT a per-leaf pytree of specs."""
    if isinstance(x, str):
        return True
    if isinstance(x, (dict, list, tuple)):
        # Containers are per-leaf spec trees (np.dtype would try to
        # parse a dict as a STRUCTURED dtype and raise ValueError).
        return False
    try:
        np.dtype(x)
        return True
    except (TypeError, ValueError):
        return False


def make_shard_and_gather_fns(shardings, dtype_specs=None):
    """(shard_fns, gather_fns) trees from a tree of NamedShardings.

    ``shard_fn(host_array)`` places a leaf under its declared sharding
    (``jax.device_put`` — XLA splits/replicates as the spec says);
    ``gather_fn(device_array)`` brings it back as a dense host ndarray
    (cross-process allgather where the layout spans hosts). The pair is
    the checkpoint/publish contract: save/restore and package export go
    through these, never through raw per-leaf copies.

    ``dtype_specs`` optionally casts float leaves on the way through:
    either ONE dtype-like applied tree-wide, or a pytree shaped like
    ``shardings`` carrying a per-leaf dtype (``None`` = leave alone).
    The upstream snippet's ``dtype_specs in float_dtypes`` membership
    test only ever worked for the scalar case (a pytree on the left of
    ``in`` compares elementwise and crashes); per-leaf specs are
    first-class here. Non-float leaves (step counters, int stats) are
    never cast."""
    is_sharding = lambda x: isinstance(x, NamedSharding)  # noqa: E731
    if dtype_specs is None:
        spec_tree = jax.tree.map(lambda _s: None, shardings,
                                 is_leaf=is_sharding)
    elif _is_dtype_like(dtype_specs):
        dt = _as_dtype(dtype_specs)
        spec_tree = jax.tree.map(lambda _s: dt, shardings,
                                 is_leaf=is_sharding)
    else:
        spec_tree = jax.tree.map(
            lambda d: None if d is None else _as_dtype(d), dtype_specs,
            is_leaf=lambda x: x is None or _is_dtype_like(x),
        )

    def _cast(x, dt):
        if dt is None:
            return x
        src = getattr(x, "dtype", None)
        if src is None or not jnp_issubdtype_floating(src):
            return x
        return x.astype(dt) if hasattr(x, "astype") else np.asarray(x, dt)

    def make_shard_fn(s, dt):
        return lambda x: jax.device_put(_cast(x, dt), s)

    def make_gather_fn(_s, dt):
        return lambda x: _cast(gather_leaf(x), dt)

    shard_fns = jax.tree.map(
        make_shard_fn, shardings, spec_tree, is_leaf=is_sharding,
    )
    gather_fns = jax.tree.map(
        make_gather_fn, shardings, spec_tree, is_leaf=is_sharding,
    )
    return shard_fns, gather_fns


def jnp_issubdtype_floating(dt) -> bool:
    """Float check that also covers jax extended dtypes (bfloat16 is
    not an ``np.floating`` subtype under plain numpy)."""
    import jax.numpy as jnp

    return bool(jnp.issubdtype(dt, jnp.floating))


# ----------------------------------------------------------------------
# Layout introspection: the declared-vs-actual reconciliation surface
# (trainer fit start) and the checkpoint layout manifest.


def spec_to_json(spec) -> list:
    """PartitionSpec -> JSON-able per-dim axis list (nested tuples —
    multiple axes on one dim — become lists)."""
    out = []
    for entry in tuple(spec):
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            out.append([str(a) for a in entry])
        else:
            out.append(str(entry))
    return out


def leaf_spec(leaf):
    """The PartitionSpec a jax.Array leaf actually carries (None for
    host arrays / non-named shardings)."""
    sharding = getattr(leaf, "sharding", None)
    if isinstance(sharding, NamedSharding):
        return sharding.spec
    return None


def layout_mismatches(state, declared) -> list[dict]:
    """Where the live state's layout drifted from the DECLARED rule
    layout: [{path, actual, declared}] per mismatched leaf. The jitted
    step's OUTPUT shardings can legitimately drift (under ZeRO-1 XLA
    keeps the weight update — and therefore the output params — sharded
    over ``data`` instead of all-gathering); the trainer reconciles by
    re-pinning to the declared layout before checkpointing, and emits
    ``shard.layout_mismatch`` so the drift is on the record instead of
    silently checkpointed."""
    out: list[dict] = []

    def one(path, leaf, want):
        actual = leaf_spec(leaf)
        if actual is None:
            return
        want_spec = want.spec if isinstance(want, NamedSharding) else want
        # Compare normalized: trailing Nones are layout-equivalent.
        def norm(s):
            dims = list(tuple(s))
            while dims and dims[-1] is None:
                dims.pop()
            return tuple(dims)

        if norm(actual) != norm(want_spec):
            out.append({
                "path": path_str(path),
                "actual": spec_to_json(actual),
                "declared": spec_to_json(want_spec),
            })

    jax.tree_util.tree_map_with_path(
        lambda p, a, b: one(p, a, b), state, declared
    )
    return out

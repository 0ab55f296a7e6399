"""AOT executable store: serialized XLA executables, fingerprinted.

The hot programs a relaunch or endpoint spin-up re-pays — the fused
epoch/train-step programs, the jitted batched scorer — are compiled
once via ``jax.jit(...).lower(*args).compile()`` and the **compiled
executable itself** is serialized to disk (the ``jax.export``-style
path: ``jax.experimental.serialize_executable``). A warm process
deserializes instead of compiling: same machine code, bit-identical
results, milliseconds instead of seconds.

Artifact format (one file per (program, signature), published
tmp+``os.replace`` so a reader can never see a torn artifact)::

    DCTAOT1\\n
    {header JSON: fingerprints + identity + payload sha256}\\n
    <raw serialized-executable payload>

The header is the **load-or-miss contract**: every fingerprint
(jax/jaxlib version, backend, device kind/count, process count, CPU
arch) and every identity field (program, family, config_hash, mesh,
code, extra) must match the loading process exactly, and the payload must
hash to the header's sha256 — anything else is a LOUD miss
(``compile.cache_miss`` event naming the reason) that falls back to a
normal jit compile. A stale, foreign, or corrupted artifact can cost a
compile; it can never produce a wrong execution.

Pytree treedefs are deliberately NOT serialized: a ``TrainState``
treedef carries live closures (the optax transformation, the bound
``apply_fn``) that neither pickle nor belong on disk. Both trees are
rebuilt at load time from the live function and the first call's
arguments — ``tree_flatten((args, {{}}))`` for the input tree,
``jax.eval_shape`` (a trace, no compile) for the output tree — so the
loaded executable is called with metadata that matches the calling
process by construction.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import sys
import threading

_MAGIC = b"DCTAOT1\n"

#: Artifact-header format version; bump on any layout change (a
#: version mismatch is a loud miss like every other fingerprint).
ARTIFACT_VERSION = 1


def runtime_fingerprint() -> dict:
    """The facts that make a serialized executable loadable HERE and
    nowhere else. Exact-match on load; any drift is a loud miss."""
    import platform as _platform

    import jax
    import jaxlib

    return {
        "version": ARTIFACT_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "process_count": jax.process_count(),
        "machine": _platform.machine(),
    }


@functools.lru_cache(maxsize=1)
def source_digest() -> str:
    """Content digest of the program's own source: every ``.py`` file of
    the ``dct_tpu`` package, by relative path and bytes. The configuration
    says WHICH program was asked for; the code says what that program
    does — a changed kernel or step under an unchanged configuration
    compiles a different executable, so the code joins the identity
    (:func:`store_from_env`) and an upgrade misses instead of running the
    previous version's machine code. Milliseconds, once per process."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for dirpath, dirnames, files in os.walk(root):
        dirnames.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()[:16]


def signature_of(args) -> str:
    """Stable digest of the call's abstract signature (leaf shapes,
    dtypes, weak_type flags). Deliberately leaf-only: treedef reprs can
    embed object addresses, which would make the signature unstable
    across processes — the semantic identity (program name, family,
    config_hash, mesh, extra) lives in the store's key instead."""
    import jax

    leaves = jax.tree_util.tree_leaves(args)
    parts = [
        f"{tuple(getattr(a, 'shape', ()))}:"
        f"{getattr(a, 'dtype', type(a).__name__)}:"
        f"{int(bool(getattr(a, 'weak_type', False)))}"
        for a in leaves
    ]
    blob = f"n{len(leaves)}|" + "|".join(parts)
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


def _execution_device_ids(compiled) -> list[int]:
    """Ids of the devices ``compiled`` runs on, in device-assignment
    order (read off the same unloaded executable ``serialize`` pickles)."""
    unloaded = compiled._executable._unloaded_executable
    return [int(d.id) for d in unloaded.device_list]


_cache_hits = threading.local()


@functools.cache
def _count_persistent_cache_hits() -> None:
    """Install (once) the ``jax.monitoring`` listener that counts, per
    thread, the compiles JAX's persistent compilation cache served."""
    import jax.monitoring

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _cache_hits.n = getattr(_cache_hits, "n", 0) + 1

    jax.monitoring.register_event_listener(on_event)


def _persistent_cache_hits() -> int:
    """How many compiles of THIS thread the persistent compilation
    cache has served so far. An executable that came out of that cache
    must not be published as an AOT artifact: XLA:CPU re-serializes
    such an executable without its object code, and the artifact then
    dies at its first call in the next process (``NOT_FOUND: Function
    ... not found``). The persistent cache already holds it — nothing
    is lost."""
    _count_persistent_cache_hits()
    return getattr(_cache_hits, "n", 0)


def _safe_name(s: str) -> str:
    return "".join(
        c if c.isalnum() or c in "._-" else "_" for c in str(s)
    ) or "program"


def weights_digest(weights: dict) -> str:
    """Content digest of a serving weights dict (sorted keys, shapes,
    dtypes, raw bytes). The jitted scorer CLOSES OVER the weights, so
    they are baked into the serialized executable as constants — an
    identity without this digest would let a meta-identical artifact
    built from different weights load cleanly and serve the stale
    model. One pass at scorer build time (~ms per MB), never on the
    request path."""
    import numpy as np

    h = hashlib.sha256()
    for k in sorted(weights):
        a = np.ascontiguousarray(weights[k])
        h.update(str(k).encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


class ExecutableStore:
    """Load-or-miss store of serialized executables under one root.

    ``identity`` carries the compile-accounting key the artifacts are
    minted under — ``family`` / ``config_hash`` / ``mesh`` (the same
    labels ``compile.window`` events use) plus an optional ``extra``
    dict for program-shaping knobs the model config alone does not
    capture (the trainer hashes its optimizer/precision/donation facts
    in; constants like the learning rate are baked into the executable,
    so they MUST be part of the key). ``states`` records, per program
    key, how its executables resolved: ``hit`` (all loaded from disk),
    ``miss`` (at least one fresh compile), or ``disabled``.
    """

    def __init__(
        self,
        root: str | None,
        *,
        identity: dict | None = None,
        enabled: bool = True,
        emit=None,
    ):
        self.root = root
        self.enabled = bool(enabled and root)
        self.identity = dict(identity or {})
        self._emit = emit
        self.states: dict[str, str] = {}
        # Roofline accounting (observability.roofline): per program key,
        # the normalized cost_analysis/memory_analysis record captured
        # at compile (or load) time. Populated regardless of `enabled`
        # — a disabled store is still every CachedProgram's cost book.
        self.costs: dict[str, dict] = {}
        # Per program key, the executable its first dispatch resolved
        # (loaded or freshly compiled): what ran, for whoever wants its
        # HLO text beside a device trace (``.as_text()``).
        self.executables: dict[str, object] = {}
        self._lock = threading.Lock()

    # -- bookkeeping ---------------------------------------------------
    def _note(self, program: str, state: str) -> None:
        with self._lock:
            prev = self.states.get(program)
            # A miss outranks a hit: one fresh compile under a program
            # key means the key was not fully served from disk.
            if prev == "miss" and state == "hit":
                return
            self.states[program] = state

    def note_cost(self, program: str, cost: dict | None) -> None:
        """Record a program's analytic cost; first capture per program
        goes on the event log as ``roofline.program`` so efficiency
        accounting has the same audit trail as compile accounting."""
        if not cost:
            return
        with self._lock:
            fresh = program not in self.costs
            self.costs[program] = cost
        if fresh:
            self._event(
                "roofline.program", program, component="roofline",
                **cost,
            )

    def _event(self, event: str, program: str,
               component: str = "compile", **fields) -> None:
        if self._emit is None:
            return
        try:
            self._emit(component, event, program=program, **fields)
        except Exception:  # noqa: BLE001 — telemetry never fails a load
            pass

    def _identity_key(self) -> str:
        blob = json.dumps(self.identity, sort_keys=True, default=str)
        return hashlib.sha1(blob.encode()).hexdigest()[:10]

    def _path(self, program: str, signature: str) -> str:
        name = (
            f"{_safe_name(program)}-{self._identity_key()}-"
            f"{signature}.aotx"
        )
        return os.path.join(self.root, name)

    # -- save ----------------------------------------------------------
    def save(self, program: str, signature: str, compiled, cost: dict | None = None) -> bool:  # dct: noqa[rank0-io] — per-rank BY DESIGN: in a multi-process world store_from_env stamps proc=<rank> into the identity, so every rank writes DISTINCT artifact names (a rank-0 gate would lose all nonzero ranks' executables); the pid-suffixed tmp + os.replace publish also makes concurrent single-host writers (serving workers) tear-proof
        """Serialize ``compiled`` under (program, signature); atomic
        publish. ``cost`` (the roofline analysis captured at compile
        time) rides the header as ``roofline`` — NOT part of the
        load-or-miss contract, just provenance a warm process reads
        back instead of re-deriving. Returns False (with a stderr note)
        when the backend does not support executable serialization or
        the write fails — never raises."""
        if not self.enabled:
            return False
        try:
            from jax.experimental import serialize_executable as _se

            payload, _in_tree, _out_tree = _se.serialize(compiled)
            header = {
                **runtime_fingerprint(),
                **{k: str(v) for k, v in self.identity.items()},
                "program": program,
                "signature": signature,
                "payload_sha256": hashlib.sha256(payload).hexdigest(),
                "device_ids": _execution_device_ids(compiled),
            }
            if cost:
                header["roofline"] = cost
            os.makedirs(self.root, exist_ok=True)
            final = self._path(program, signature)
            tmp = f"{final}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(_MAGIC)
                f.write(json.dumps(header, sort_keys=True).encode())
                f.write(b"\n")
                f.write(payload)
            os.replace(tmp, final)
            return True
        except Exception as e:  # noqa: BLE001 — a failed save costs the
            # next process a compile, never this one its run
            sys.stderr.write(
                f"[dct_tpu] AOT save failed for {program}: "
                f"{type(e).__name__}: {e}\n"
            )
            return False

    # -- load ----------------------------------------------------------
    def _read(self, path: str) -> tuple[dict | None, bytes, str]:
        """(header, payload, miss_reason) — header None on any defect."""
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            return None, b"", "absent"
        except OSError as e:
            return None, b"", f"unreadable: {e}"
        if not raw.startswith(_MAGIC):
            return None, b"", "bad magic (corrupt or foreign file)"
        body = raw[len(_MAGIC):]
        nl = body.find(b"\n")
        if nl < 0:
            return None, b"", "truncated header"
        try:
            header = json.loads(body[:nl].decode())
        except (ValueError, UnicodeDecodeError):
            return None, b"", "unparsable header"
        payload = body[nl + 1:]
        if (
            hashlib.sha256(payload).hexdigest()
            != header.get("payload_sha256")
        ):
            return None, b"", "payload sha256 mismatch (corrupt)"
        return header, payload, ""

    def load(self, program: str, signature: str, fn, args):
        """Deserialize the artifact for (program, signature) into a
        callable ``Compiled``, or None on any mismatch — emitting the
        miss reason so a skewed artifact is on the record. ``fn`` and
        ``args`` rebuild the pytree metadata (module docstring)."""
        if not self.enabled:
            return None
        path = self._path(program, signature)
        header, payload, reason = self._read(path)
        if header is None:
            if reason != "absent":
                self._event(
                    "compile.cache_miss", program,
                    reason=reason, artifact=os.path.basename(path),
                )
            return None
        want = {
            **runtime_fingerprint(),
            **{k: str(v) for k, v in self.identity.items()},
            "program": program,
            "signature": signature,
        }
        skew = {
            k: (header.get(k), v)
            for k, v in want.items()
            if header.get(k) != v
        }
        if skew:
            self._event(
                "compile.cache_miss", program,
                reason="fingerprint skew",
                skew={k: f"{a!r}!={b!r}" for k, (a, b) in skew.items()},
                artifact=os.path.basename(path),
            )
            return None
        try:
            import jax
            from jax.experimental import serialize_executable as _se

            in_tree = jax.tree_util.tree_flatten((tuple(args), {}))[1]
            out_tree = jax.tree_util.tree_structure(
                jax.eval_shape(fn, *args)
            )
            # The devices the program was compiled for, in assignment
            # order: deserialize_and_load defaults to EVERY device of
            # the backend, so a single-device scorer or a sub-mesh stage
            # would otherwise be loaded as an N-device program and die
            # at its first call on a many-device host.
            by_id = {d.id: d for d in jax.devices()}
            loaded = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[
                    by_id[i] for i in header["device_ids"]
                ],
            )
            # Roofline provenance stamped at compile time reads back on
            # the warm path — a hit run reports the same analytic
            # FLOPs/HBM as the run that compiled the artifact. (If the
            # call later demotes this executable, the miss path's fresh
            # analysis overwrites it.) Same DCT_ROOFLINE gate as the
            # capture paths: disabled means NO roofline telemetry,
            # warm or cold.
            saved_cost = header.get("roofline")
            if isinstance(saved_cost, dict):
                from dct_tpu.observability import roofline as _roofline

                if _roofline.roofline_enabled():
                    self.note_cost(
                        program, {**saved_cost, "source": "header"}
                    )
            return loaded
        except Exception as e:  # noqa: BLE001 — any load defect is a miss
            self._event(
                "compile.cache_miss", program,
                reason=f"deserialize failed: {type(e).__name__}: {e}"[:300],
                artifact=os.path.basename(path),
            )
            return None

    # -- the wrapper ----------------------------------------------------
    def wrap(self, fn, program: str | None = None) -> "CachedProgram":
        """Wrap a jitted function in load-or-miss dispatch (see
        :class:`CachedProgram`). Always safe to call — with the store
        disabled the wrapper delegates straight to ``fn``."""
        return CachedProgram(fn, self, program=program)


def _stamp_dtypes(cost: dict | None, args) -> dict | None:
    """Join the dispatch's parameter/activation dtype summary into a
    roofline cost record (``dtypes`` field): precision is program
    identity on the efficiency plane — a bf16-rules step and its f32
    twin must be tellable apart from one scrape."""
    if not cost:
        return cost
    from dct_tpu.observability import roofline as _roofline

    summary = _roofline.dtype_summary(args)
    return {**cost, "dtypes": summary} if summary else cost


class CachedProgram:
    """A jitted function fronted by the executable store.

    First call per (program key, signature): try the store — a **hit**
    deserializes the executable and runs it; a **miss** compiles via
    ``fn.lower(*args).compile()``, publishes the artifact, and runs the
    fresh executable. Later calls dispatch the in-memory executable
    directly. With the store disabled, calls delegate to the jitted
    function untouched (state ``disabled``).

    ``key=`` overrides the program key per call — the trainer passes
    its goodput dispatch key (``scan_k<k>``) so the store's hit/miss
    states line up 1:1 with the ``compile.window`` accounting.

    A loaded executable whose first call is rejected at validation
    (pytree/aval mismatch, before any buffer is consumed) demotes to
    the miss path — stale artifacts degrade to a compile, never a
    crash or a wrong result. Failures DURING execution propagate: a
    donating program's inputs may already be gone, and an error the
    fresh compile would hit too must not be masked.
    """

    def __init__(self, fn, store: ExecutableStore, program: str | None = None):
        self._fn = fn
        self._store = store
        self._program = program or getattr(fn, "__name__", "program")
        self._entries: dict = {}
        self._analyzed: set = set()
        self._lock = threading.Lock()

    def _analyze_disabled(self, program: str, args) -> None:
        """Roofline capture on the store-DISABLED path (the default):
        the plain jit call below never exposes its executable, so the
        cost model is read off a pre-compile ``lower()`` — one extra
        trace per program, no extra compile. Once per program key."""
        with self._lock:
            if program in self._analyzed:
                return
            self._analyzed.add(program)
        from dct_tpu.observability import roofline as _roofline

        if not _roofline.roofline_enabled():
            return
        try:
            lowered = self._fn.lower(*args)
        except Exception:  # noqa: BLE001 — non-jit callables have no HLO
            return
        self._store.note_cost(
            program, _stamp_dtypes(_roofline.analyze_lowered(lowered), args)
        )

    def __call__(self, *args, key: str | None = None):
        program = key or self._program
        if not self._store.enabled:
            self._store._note(program, "disabled")
            self._analyze_disabled(program, args)
            return self._fn(*args)
        sig = signature_of(args)
        with self._lock:
            entry = self._entries.get((program, sig))
        if entry is not None:
            return entry(*args)
        return self._first_call(program, sig, args)

    def _first_call(self, program: str, sig: str, args):
        store = self._store
        loaded = store.load(program, sig, self._fn, args)
        if loaded is not None:
            try:
                out = loaded(*args)
            except (TypeError, ValueError) as e:
                # Pre-execution validation rejections (pytree/aval
                # mismatch — raised BEFORE any buffer is consumed, so
                # re-running args is safe even for donating programs):
                # degrade loudly to a fresh compile. Runtime failures
                # propagate instead — a donating executable may already
                # have consumed its inputs, and an error the fresh
                # compile would hit too must not be masked as a miss.
                store._event(
                    "compile.cache_miss", program,
                    reason=(
                        f"loaded executable rejected the call: "
                        f"{type(e).__name__}: {e}"
                    )[:300],
                )
            else:
                store._note(program, "hit")
                store._event(
                    "compile.cache_hit", program, signature=sig,
                )
                if program not in store.costs:
                    # Pre-roofline artifact (no stamped provenance):
                    # analyze the deserialized executable directly.
                    from dct_tpu.observability import (
                        roofline as _roofline,
                    )

                    if _roofline.roofline_enabled():
                        store.note_cost(
                            program,
                            _stamp_dtypes(
                                _roofline.analyze_compiled(loaded), args
                            ),
                        )
                with self._lock:
                    self._entries[(program, sig)] = loaded
                    store.executables[program] = loaded
                return out
        store._note(program, "miss")
        hits_before = _persistent_cache_hits()
        try:
            compiled = self._fn.lower(*args).compile()
        except Exception:
            # A function that cannot lower/compile ahead-of-time (e.g.
            # a non-jit callable slipped in) still runs: the plain call
            # is the universal fallback.
            with self._lock:
                self._entries[(program, sig)] = self._fn
            return self._fn(*args)
        from dct_tpu.observability import roofline as _roofline

        cost = (
            _stamp_dtypes(_roofline.analyze_compiled(compiled), args)
            if _roofline.roofline_enabled() else None
        )
        store.note_cost(program, cost)
        if _persistent_cache_hits() == hits_before:
            store.save(program, sig, compiled, cost=cost)
        with self._lock:
            self._entries[(program, sig)] = compiled
            store.executables[program] = compiled
        return compiled(*args)


def store_from_env(
    root: str | None,
    *,
    family: str = "",
    config_hash: str = "",
    mesh: str = "",
    extra: dict | None = None,
    emit=None,
) -> ExecutableStore:
    """An :class:`ExecutableStore` under the env contract: enabled when
    the compile cache is armed (``cache.enabled``), AOT is on, and a
    root is given.

    Multi-process worlds are supported with PER-RANK artifacts: a
    multi-process executable references cross-host topology from its
    own rank's perspective, so ``proc=<rank>`` joins the identity —
    rank 0's artifact can never be loaded by rank 1, and a relaunched
    world's rank N deserializes exactly the executable its dead
    predecessor rank N compiled (the sharded supervised-relaunch path).
    The runtime fingerprint already pins ``process_count``, so a world
    resized between runs is a loud miss, never a wrong execution.

    ``code=<source_digest>`` joins the identity, hence the artifact's
    name: two versions of the program sharing one store (an upgrade, two
    checkouts under one ``JAX_COMPILATION_CACHE_DIR``) keep separate
    artifacts and never load each other's."""
    from dct_tpu.compilecache.cache import aot_enabled

    on = bool(root) and aot_enabled()
    identity = {
        "family": family, "config_hash": config_hash, "mesh": mesh,
        "code": source_digest(),
    }
    if on:
        try:
            import jax

            if jax.process_count() > 1:
                identity["proc"] = jax.process_index()
        except Exception:  # noqa: BLE001 — no backend = nothing to cache
            on = False
    if extra:
        identity["extra"] = json.dumps(extra, sort_keys=True, default=str)
    return ExecutableStore(root, identity=identity, enabled=on, emit=emit)


def warm_package_scorer(
    package_dir: str, sizes: list[int] | None = None
) -> list[int]:
    """Pre-compile the jitted batched scorer into ``<package>/aot/`` at
    the given batch sizes (default: ``DCT_COMPILE_CACHE_WARM_SIZES``),
    so a deployed package carries its executables and an endpoint
    worker spins up pre-compiled. Returns the padded sizes actually
    compiled (deduped to the scorer's power-of-two padding). Best-
    effort: any failure leaves the package valid and un-warmed."""
    from dct_tpu.compilecache.cache import warm_sizes as _warm_sizes

    sizes = _warm_sizes() if sizes is None else sorted(set(sizes))
    if not sizes:
        return []
    try:
        import numpy as np

        from dct_tpu.serving.batching import _build_jax_scorer
        from dct_tpu.serving.runtime import assemble_weights

        npz = np.load(os.path.join(package_dir, "model.npz"))
        weights = assemble_weights({k: npz[k] for k in npz.files})
        with open(os.path.join(package_dir, "model_meta.json")) as f:
            meta = json.load(f)
        meta["_aot_dir"] = os.path.join(package_dir, "aot")
        score = _build_jax_scorer(weights, meta, force_store=True)
        padded_done: list[int] = []
        for n in sizes:
            padded = 1
            while padded < n:
                padded *= 2
            if padded in padded_done:
                continue
            x = _example_batch(meta, padded)
            score(x)
            padded_done.append(padded)
        return padded_done
    except Exception as e:  # noqa: BLE001 — warming is an optimization
        sys.stderr.write(
            f"[dct_tpu] package scorer warm-up skipped: "
            f"{type(e).__name__}: {e}\n"
        )
        return []


def _example_batch(meta: dict, n: int):
    """A shape-correct all-zeros batch for the package's family (row
    families [N, D]; sequence families [N, S, D])."""
    import numpy as np

    from dct_tpu.serving.runtime import _SEQUENCE_FAMILIES

    d = int(meta["input_dim"])
    if meta.get("model", "weather_mlp") in _SEQUENCE_FAMILIES:
        return np.zeros((n, int(meta["seq_len"]), d), np.float32)
    return np.zeros((n, d), np.float32)

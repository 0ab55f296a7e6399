"""Checkpointing: best/last policy + full-state resume.

Two tiers, mirroring and extending the reference:

1. **Deploy tier** (`*.ckpt` single files) — the analog of Lightning's
   ``ModelCheckpoint(dirpath=data/models, filename="weather-best-{epoch:02d}-
   {val_loss:.2f}", save_top_k=1, monitor=val_loss, mode=min, save_last=True)``
   (jobs/train_lightning_ddp.py:103-110). Same directory layout, same
   filename convention, same ``last.ckpt`` fallback — so the training DAG's
   ``ls *.ckpt`` verification gate (dags/2_pytorch_training.py:81-91) and the
   deploy DAG's "first .ckpt in best_checkpoints" pick
   (dags/azure_manual_deploy.py:46-50) work unchanged. Format: flax msgpack
   of ``{"meta": {...}, "params": ...}`` — self-describing (input_dim,
   feature names, architecture) so serving never hardcodes ``input_dim=5``
   like the reference's score.py does (dags/azure_manual_deploy.py:109).

2. **Resume tier** (per-process ``state.npz`` with crash-safe directory
   rotation) — full TrainState (params + Adam moments + step + rng), which
   the reference cannot do at all (``fit()`` never gets a ckpt_path;
   jobs/train_lightning_ddp.py:143). Continuous training can therefore
   actually continue rather than restart from scratch. Cross-process-
   sharded leaves (TP/SP spanning hosts) save as local shards and
   reassemble on restore — no allgather, no cross-process coordination.
"""

from __future__ import annotations

import hashlib
import os
from typing import Any

import jax
import numpy as np
from flax import serialization

from dct_tpu.observability import events as _events
from dct_tpu.observability import lineage as _lineage
from dct_tpu.observability import spans as _spans
from dct_tpu.resilience import faults as _faults


def needs_cross_process_gather(tree) -> bool:
    """True when any leaf is sharded across processes (not addressable
    from this host alone)."""
    return any(
        isinstance(a, jax.Array) and not a.is_fully_addressable
        for a in jax.tree.leaves(tree)
    )


def to_host(tree):
    """Device tree -> dense host numpy tree, through the partition
    rules' gather fns (:func:`dct_tpu.parallel.sharding_rules
    .gather_tree`): arrays sharded across processes (tensor/sequence
    parallelism spanning hosts) are assembled with a cross-process
    allgather, everything else is a device_get. NB: the allgather is a
    COLLECTIVE — when any leaf is non-addressable
    (:func:`needs_cross_process_gather`), every process must call this
    function (the Trainer does: it gathers on all ranks, then gates the
    file write on the coordinator).
    """
    from dct_tpu.parallel.sharding_rules import gather_tree

    return gather_tree(tree)


def save_checkpoint(path: str, params: Any, meta: dict) -> str:  # dct: noqa[rank0-io] — caller-gated: the trainer invokes the deploy tier under its coordinator gate; the write itself must stay rank-agnostic for tests and single-process tools
    """Serialize {meta, params} to a single msgpack file.

    Write-to-temp + ``os.replace``: a crash anywhere in the window (now
    injectable — ``slow_save`` widens it, ``crash_save`` dies inside it)
    can never publish a torn best/last file; at worst ``*.tmp`` debris
    remains and the previous publish stays intact. The temp name is
    pid-suffixed so concurrent writers (another rank, a stale zombie)
    cannot tear each other's in-flight temp.

    Three spans, one per thing the call's seconds can go to: serialise,
    disk, hash (``bytes`` = the file's size on each).
    """
    tracer = _spans.get_default()
    base = os.path.basename(path)
    with tracer.span("checkpoint.serialize", path=base) as sp:
        payload = {"meta": dict(meta), "params": to_host(params)}
        data = serialization.msgpack_serialize(payload)
        sp.set(bytes=len(data))
    with tracer.span("checkpoint.file_write", path=base, bytes=len(data)):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(data)
        # Fault hook INSIDE the vulnerable window: tmp written, final
        # not yet renamed — the exact instant a preemption would tear a
        # non-atomic write.
        _faults.get_default().maybe_fire(
            "save", save_kind="deploy", path=path
        )
        os.replace(tmp, path)  # atomic: no torn ckpt if a rank dies mid-write
    lin = _lineage.get_default()
    if lin.enabled:
        # Content address from the serialized bytes already in hand (no
        # file re-read); edges to whatever training inputs the trainer
        # declared (dataset snapshot, a restored trajectory) make every
        # published checkpoint a walkable graph hop.
        with tracer.span(
            "checkpoint.lineage_hash", path=base, bytes=len(data)
        ):
            nid = lin.node(
                "checkpoint", path=path,
                sha256=hashlib.sha256(data).hexdigest(),
                attrs={"epoch": dict(meta).get("epoch")},
            )
            for src in _lineage.run_inputs():
                lin.edge("consumed", nid, src)
    return path


def load_checkpoint(path: str) -> tuple[Any, dict]:
    """Returns (params, meta)."""
    with open(path, "rb") as f:
        payload = serialization.msgpack_restore(f.read())
    return payload["params"], dict(payload["meta"])


class BestLastCheckpointer:
    """save_top_k=1 on min val_loss, plus always-updated last.ckpt."""

    def __init__(
        self,
        dirpath: str,
        *,
        filename_template: str = "weather-best-{epoch:02d}-{val_loss:.2f}",
        monitor: str = "val_loss",
        mode: str = "min",
    ):
        self.dirpath = dirpath
        self.filename_template = filename_template
        self.monitor = monitor
        self.sign = 1.0 if mode == "min" else -1.0
        self.best_value: float | None = None
        self.best_model_path: str = ""
        os.makedirs(dirpath, exist_ok=True)

    @property
    def last_path(self) -> str:
        return os.path.join(self.dirpath, "last.ckpt")

    def update(self, *, epoch: int, metrics: dict, params: Any, meta: dict) -> bool:  # dct: noqa[rank0-io] — caller-gated: Trainer.fit calls update() under `if self.coordinator:`; the checkpointer has no rank identity of its own
        """Write last.ckpt; if monitor improved, replace the best file.
        Returns True when a new best was saved."""
        meta = {**meta, "epoch": int(epoch), **{k: float(v) for k, v in metrics.items()}}
        with _spans.get_default().span(
            "checkpoint.deploy_write", component="checkpoint",
            epoch=int(epoch),
        ) as sp:
            save_checkpoint(self.last_path, params, meta)

            value = float(metrics[self.monitor])
            improved = self.best_value is None or self.sign * value < self.sign * self.best_value
            if improved:
                name = self.filename_template.format(epoch=epoch, **metrics) + ".ckpt"
                new_path = os.path.join(self.dirpath, name)
                save_checkpoint(new_path, params, meta)
                if self.best_model_path and os.path.exists(self.best_model_path):
                    if os.path.abspath(self.best_model_path) != os.path.abspath(new_path):
                        os.remove(self.best_model_path)
                        # Retention tombstone: the pruned best is gone on
                        # purpose; without this the integrity audit would
                        # flag it MISSING.
                        _lineage.get_default().retire(
                            self.best_model_path, reason="superseded_best",
                        )
                self.best_value = value
                self.best_model_path = new_path
            sp.set(improved=improved)
        _events.get_default().emit(
            "checkpoint", "best_saved" if improved else "last_saved",
            epoch=int(epoch),
            path=self.best_model_path if improved else self.last_path,
            **{self.monitor: value},
        )
        return improved


class TrainStateCheckpointer:  # dct: noqa[rank0-io] — per-process BY DESIGN: every rank owns its private p<rank>/ rotation dir (shard-local saves, no cross-rank file is ever shared), so rank-0 gating would lose all nonzero ranks' resume state
    """Full train-state save/restore for true resume (per-process npz
    with crash-safe rotation; shard-local for cross-process arrays)."""

    def __init__(self, dirpath: str):
        self.dirpath = os.path.abspath(dirpath)
        os.makedirs(self.dirpath, exist_ok=True)

    # Crash-safe directory rotation: a new checkpoint is fully written to
    # ``state.next`` before the live ``state`` is touched, so at every
    # instant at least one *complete* checkpoint exists (restore prefers
    # state > state.next > state.old). A plain force=True overwrite of the
    # single live dir would destroy the only resume point if the process
    # died mid-save — the exact preemption scenario resume exists for.
    _LIVE, _NEXT, _OLD = "state", "state.next", "state.old"

    def _dir(self, name: str) -> str:
        return os.path.join(self.dirpath, name)

    def _rotation_dirs(self) -> tuple[str, ...]:
        return (
            self._dir(self._LIVE), self._dir(self._NEXT), self._dir(self._OLD)
        )

    def _restore_candidates(self) -> list[str]:
        return [
            d
            for d in self._rotation_dirs()
            if os.path.exists(os.path.join(d, "state.npz"))
        ]

    @staticmethod
    def _dir_is_torn(d: str) -> bool:
        """A rotation dir left by a save preempted before its atomic
        rename: empty, or containing only *.tmp debris."""
        try:
            names = os.listdir(d)
        except OSError:
            # Unreadable is NOT torn: route into restore()'s loud error
            # rather than silently restarting over existing progress.
            return False
        return all(n.endswith(".tmp") for n in names)

    @staticmethod
    def _tree(state) -> dict:
        return {
            "step": state.step,
            "params": state.params,
            "opt_state": state.opt_state,
            "rng": state.rng,
        }

    @staticmethod
    def _index_key(index) -> tuple:
        """Deterministic key for a shard's global position (start offsets).
        Replicated copies on different local devices share a key — saved
        once, fanned back out on restore."""
        return tuple(sl.start or 0 for sl in index)

    def _layout(self, state) -> dict:
        """The LAYOUT MANIFEST saved beside the arrays (``layout.json``):
        per-leaf global shape + declared PartitionSpec + whether the
        leaf was saved whole or as local shards, plus the saving run's
        mesh shape and process topology. Restore uses it to (a) name a
        topology change precisely and (b) re-map saved shards onto a
        DIFFERENT mesh (``shard.topology_remap``) instead of refusing —
        docs/PARALLELISM.md §layout manifest."""
        from dct_tpu.parallel.sharding_rules import leaf_spec, spec_to_json

        leaves = jax.tree.leaves(self._tree(state))
        mesh_shape = None
        entries = []
        for i, leaf in enumerate(leaves):
            sharding = getattr(leaf, "sharding", None)
            if mesh_shape is None and hasattr(sharding, "mesh"):
                try:
                    mesh_shape = {
                        str(k): int(v)
                        for k, v in dict(sharding.mesh.shape).items()
                    }
                except (TypeError, ValueError):
                    mesh_shape = None
            spec = leaf_spec(leaf)
            entries.append({
                "leaf": i,
                "shape": [int(s) for s in getattr(leaf, "shape", ())],
                "spec": spec_to_json(spec) if spec is not None else None,
                "saved": (
                    "shards"
                    if isinstance(leaf, jax.Array)
                    and not leaf.is_fully_addressable
                    else "whole"
                ),
            })
        from dct_tpu.parallel.sharding_rules import dtype_rules_digest

        return {
            "version": 1,
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
            "mesh": mesh_shape,
            # Precision provenance (docs/PARALLELISM.md §dtype rules):
            # the SAVED arrays are always the dense f32 masters — the
            # dtype rules only shape the traced compute — but a
            # checkpoint written under active rules records which, so
            # a trajectory's precision history is auditable from its
            # manifests alone. "off" = the bitwise status quo.
            "dtype_rules": dtype_rules_digest(),
            "leaves": entries,
        }

    def save(self, state, meta: dict | None = None) -> str:
        """Persist this process's ADDRESSABLE view of the train state.

        ``meta``: small JSON-able run facts (epochs_completed,
        target_epochs, ...) stored beside the arrays and returned by
        :meth:`load_meta` — the continuous-training re-run semantics
        (Trainer.fit) are decided from these, not from step arithmetic
        that breaks when the dataset size changes between daily runs.

        Fully-addressable leaves (replicated params, single-host runs) are
        saved whole; leaves sharded across processes (TP/SP spanning
        hosts) are saved as this process's local shards only — RAM and
        disk stay proportional to the local share, with no allgather, at
        exactly the scale cross-host sharding exists for. Each leaf i is
        stored as key ``"i"`` (whole) or keys ``"i_s<off0>x<off1>..."``
        (shards, named by their GLOBAL start offsets so restore matches by
        position, not ordinal — a changed process->device mapping is then
        a detected error instead of a silent global permutation).

        Storage is a plain ``state.npz`` per process — deliberately NOT an
        orbax pytree directory: orbax's save finalization (structure
        metadata, ocdbt manifest merge) is gated on the primary host even
        with ``primary_host=None``, so nonzero ranks' private directories
        end up unreadable. This tier is host-local numpy by construction
        and needs zero cross-process coordination.
        """
        self.wait()
        return self._publish(self._entries(state), meta, self._layout(state))

    def _entries(self, state) -> dict:
        """Device state -> host {key: ndarray} dict (the npz payload).

        Flattened to an index-keyed dict: optax opt_states contain
        namedtuples that do not round-trip through generic tree
        serialization; the target treedef at restore time supplies the
        structure instead."""
        leaves = jax.tree.leaves(self._tree(state))
        entries: dict[str, np.ndarray] = {}
        for i, leaf in enumerate(leaves):
            if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                # One copy per distinct global position: replicated copies
                # on several local devices dedupe to a single entry.
                by_key = {}
                for s in leaf.addressable_shards:
                    by_key.setdefault(self._index_key(s.index), s)
                for k, s in by_key.items():
                    off = "x".join(map(str, k))
                    entries[f"{i}_s{off}"] = np.asarray(s.data)
            else:
                entries[str(i)] = np.asarray(jax.device_get(leaf))
        return entries

    def _publish(
        self, entries: dict, meta: dict | None = None,
        layout: dict | None = None,
    ) -> str:
        """Write ``entries`` (+ meta + layout) into state.next, then
        rotate."""
        # A stack span on whichever thread publishes (save_async's
        # worker included): the resume-save I/O window on the trace
        # timeline. A FAILED write (ENOSPC — exactly the window an
        # operator opens the trace to diagnose) is still recorded, with
        # its error.
        with _spans.get_default().span(
            "checkpoint.resume_save",
            epochs_completed=(meta or {}).get("epochs_completed"),
        ):
            return self._publish_inner(entries, meta, layout)

    def _publish_inner(
        self, entries: dict, meta: dict | None = None,
        layout: dict | None = None,
    ) -> str:
        import shutil

        next_dir = self._dir(self._NEXT)
        if os.path.isdir(next_dir):
            shutil.rmtree(next_dir)
        os.makedirs(next_dir)
        # Atomic publish: a save preempted mid-write must never leave a
        # torn state.npz that _restore_candidates would accept.
        final = os.path.join(next_dir, "state.npz")
        tmp = final + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **entries)
        # Fault hook between the shard write and its atomic rename: a
        # ``crash_save`` here leaves state.next holding only *.tmp
        # debris — the torn dir _restore_candidates must skip so the
        # previous publish restores (``slow_save`` widens the window for
        # kill-based tests instead).
        _faults.get_default().maybe_fire(
            "save", save_kind="resume_state", dir=next_dir
        )
        os.replace(tmp, final)
        if meta is not None:
            import json

            mfinal = os.path.join(next_dir, "meta.json")
            mtmp = mfinal + ".tmp"
            with open(mtmp, "w") as f:
                json.dump(meta, f)
            os.replace(mtmp, mfinal)
        if layout is not None:
            import json

            lfinal = os.path.join(next_dir, "layout.json")
            ltmp = lfinal + ".tmp"
            with open(ltmp, "w") as f:
                json.dump(layout, f)
            os.replace(ltmp, lfinal)

        live, old = self._dir(self._LIVE), self._dir(self._OLD)
        if os.path.isdir(old):
            shutil.rmtree(old)
        if os.path.isdir(live):
            os.rename(live, old)
        os.rename(next_dir, live)
        if os.path.isdir(old):
            shutil.rmtree(old)
        # Emitted from whichever thread published (EventLog is locked);
        # the resume tier is per-process, so every rank's event appears,
        # rank-stamped, in the shared log.
        _events.get_default().emit(
            "checkpoint", "resume_state_saved", dir=live,
            epochs_completed=(meta or {}).get("epochs_completed"),
        )
        lin = _lineage.get_default()
        if lin.enabled:
            nid = lin.node(
                "checkpoint", path=os.path.join(live, "state.npz"),
                attrs={
                    "tier": "resume",
                    "epochs_completed": (meta or {}).get("epochs_completed"),
                },
            )
            for src in _lineage.run_inputs():
                lin.edge("consumed", nid, src)
        return live

    def save_async(self, state, meta: dict | None = None) -> None:
        """Overlap the checkpoint write with the next epoch's compute: the
        device->host snapshot happens NOW (the worker must not touch
        device arrays a donated train step may alias next epoch), and the
        npz write + rotation run on a worker thread. At most one write is
        in flight — a second call joins the first, so the rotation
        protocol's invariants hold unchanged. Call :meth:`wait` (or any
        ``save``/``restore``) before reading the checkpoint back."""
        import threading

        tracer = _spans.get_default()
        # The two things the caller's thread waits for here: the previous
        # write's worker, then the device-to-host copy of the state.
        with tracer.span("checkpoint.resume_wait_prev"):
            self.wait()
        with tracer.span("checkpoint.resume_snapshot"):
            entries = self._entries(state)
            layout = self._layout(state)

        def work():
            try:
                self._publish(entries, meta, layout)
            except BaseException as e:  # surfaced by the next wait()
                self._error = e

        self._pending = threading.Thread(target=work, daemon=True)
        self._pending.start()

    def wait(self) -> None:
        """Join any in-flight async write; re-raise its failure — a lost
        background write must be as loud as a failed synchronous save
        (ENOSPC on the final epoch would otherwise report success while
        the resume state silently stays one epoch stale)."""
        t = getattr(self, "_pending", None)
        if t is not None:
            t.join()
            self._pending = None
        err = getattr(self, "_error", None)
        if err is not None:
            self._error = None
            raise RuntimeError(
                f"async train-state checkpoint write failed: {err!r}"
            ) from err

    def _sibling_candidate_dirs(self) -> list[str]:
        """Sibling ranks' newest restorable rotation dirs (``p<rank>/``
        siblings under the shared ``train_state`` parent). A topology-
        change restore reads shards the SAVING topology placed in other
        processes' files — possible exactly when the resume tier sits
        on a shared filesystem (the test rig and pod-slice NFS case);
        private-disk pods keep the loud same-topology contract."""
        parent = os.path.dirname(self.dirpath)
        out: list[str] = []
        try:
            names = os.listdir(parent)
        except OSError:
            return out
        for n in sorted(names):
            d = os.path.join(parent, n)
            if os.path.abspath(d) == self.dirpath:
                continue
            if not (n.startswith("p") and n[1:].isdigit()):
                continue
            for rot in (self._LIVE, self._NEXT, self._OLD):
                cand = os.path.join(d, rot)
                if os.path.exists(os.path.join(cand, "state.npz")):
                    out.append(cand)
                    break
        return out

    def load_layout(self) -> dict:
        """The layout manifest saved beside the newest restorable
        checkpoint (own dir first, siblings as fallback; empty dict for
        pre-manifest checkpoints)."""
        import json

        self.wait()
        for d in self._restore_candidates() + self._sibling_candidate_dirs():
            path = os.path.join(d, "layout.json")
            if os.path.exists(path):
                try:
                    with open(path) as f:
                        return dict(json.load(f))
                except (OSError, ValueError):
                    return {}
        return {}

    def load_meta(self) -> dict:
        """Run facts saved beside the newest restorable checkpoint
        (empty dict when the checkpoint predates meta support). Falls
        back to a SIBLING rank's meta when this process has no
        checkpoint of its own — the topology-growth restore (e.g. 2
        saving processes resumed as 4) must agree on epochs_completed
        with the ranks that do."""
        import json

        self.wait()
        candidates = self._restore_candidates()
        if not candidates:
            candidates = self._sibling_candidate_dirs()[:1]
        if not candidates:
            return {}
        # candidates[0] to stay paired with restore(), which reads the
        # same directory's arrays.
        path = os.path.join(candidates[0], "meta.json")
        if not os.path.exists(path):
            return {}
        with open(path) as f:
            return dict(json.load(f))

    def exists(self) -> bool:
        self.wait()
        # A readable checkpoint, or a dir in an unreadable (legacy) format
        # — the latter must route resume into restore()'s loud error, not
        # a silent from-scratch restart that overwrites the old progress.
        # Torn-save debris (only *.tmp content) does NOT count: the save
        # protocol itself creates those and a fresh start is correct.
        if self._restore_candidates():
            return True
        if any(
            os.path.isdir(d) and not self._dir_is_torn(d)
            for d in self._rotation_dirs()
        ):
            return True
        # Topology growth: a rank with no checkpoint of its own can
        # still restore from sibling ranks' files (shared fs) — resume
        # must say yes or the new rank would restart epoch 0 while the
        # old ranks resume, and the start-epoch allgather check in
        # Trainer.fit would abort the whole world.
        return bool(self._sibling_candidate_dirs())

    @staticmethod
    def _assemble_dense(gshape: tuple, part_by_key: dict):
        """Offset-keyed shards -> one dense host array, or None when
        the shards do not cleanly tile the global shape (out-of-bounds
        placement, gaps, overlaps). Replicated copies saved under the
        same offsets by different processes have already deduped to one
        entry per distinct offset key."""
        first = next(iter(part_by_key.values()))
        dense = np.zeros(gshape, dtype=first.dtype)
        covered = 0
        for off, arr in part_by_key.items():
            off = tuple(off) + (0,) * (len(gshape) - len(off))
            if arr.ndim != len(gshape) or any(
                o + s > g for o, s, g in zip(off, arr.shape, gshape)
            ):
                return None
            dense[tuple(
                slice(o, o + s) for o, s in zip(off, arr.shape)
            )] = arr
            covered += arr.size
        if covered != dense.size:
            return None
        return dense

    def _reassemble(self, template, part_by_key: dict, extra_shards=None):
        """Offset-keyed local shards -> global jax.Array with the
        template's sharding.

        Fast path: the stored global offsets match the current
        topology's shard positions exactly — each shard device_puts
        straight onto its device (no dense copy). Otherwise the shards
        are RE-MAPPED: the dense global array is assembled from every
        available shard (this process's file plus, via
        ``extra_shards``, sibling ranks' files on a shared filesystem)
        and re-placed under the template's sharding — a checkpoint
        saved on data=2/model=2 resumes on data=4/model=1 with the
        values bit-identical. Shards that cannot tile the full global
        shape (private-disk pod, missing sibling files) fail loudly
        instead of permuting data."""
        sharding = template.sharding
        gshape = tuple(template.shape)
        dev_idx = sharding.addressable_devices_indices_map(gshape)
        want = {self._index_key(ix) for ix in dev_idx.values()}

        def _extent(ix) -> tuple:
            return tuple(
                len(range(*sl.indices(g))) for sl, g in zip(ix, gshape)
            )

        # Same-topology fast path needs offsets AND extents to match: a
        # saving topology's shard can share offset (0, 0) with the new
        # topology's (every layout has a shard there) while holding a
        # different slice of the array.
        if want == set(part_by_key) and all(
            tuple(part_by_key[self._index_key(ix)].shape) == _extent(ix)
            for ix in dev_idx.values()
        ):
            arrays = [
                jax.device_put(part_by_key[self._index_key(ix)], d)
                for d, ix in dev_idx.items()
            ]
            return jax.make_array_from_single_device_arrays(
                gshape, sharding, arrays
            ), False
        merged = dict(part_by_key)
        for key, arr in (extra_shards() if extra_shards else {}).items():
            merged.setdefault(key, arr)
        dense = self._assemble_dense(gshape, merged)
        if dense is None:
            raise ValueError(
                f"Shard-saved leaf holds offsets {sorted(part_by_key)} but "
                f"the current topology needs {sorted(want)}, and the "
                "available shards (this process's file + any sibling "
                "p<rank>/ files) do not tile the full global shape "
                f"{gshape} — a topology re-map needs every saving rank's "
                "state file on a shared filesystem. Restore with the "
                "saving mesh/process topology, or clear the train_state "
                "dir to restart from the deploy checkpoint."
            )
        return jax.make_array_from_callback(
            gshape, sharding, lambda idx: dense[idx]
        ), True

    def restore(self, state):
        """Restore into the structure (and shardings) of ``state``
        (apply_fn/tx kept). Whole-saved leaves come back as host numpy;
        shard-saved leaves are reassembled onto this process's devices
        under the template leaf's sharding."""
        self.wait()
        with _spans.get_default().span(
            "checkpoint.restore", component="checkpoint",
        ):
            return self._restore(state)

    @staticmethod
    def _dir_meta(d: str) -> dict:
        import json

        try:
            with open(os.path.join(d, "meta.json")) as f:
                return dict(json.load(f))
        except (OSError, ValueError):
            return {}

    def _sibling_entries(self) -> dict:
        """Every CONSISTENT sibling rank's npz entries, merged (first
        sibling wins per key) — the shard pool a topology re-map draws
        from. Loaded lazily, once per restore.

        Consistency gate: a sibling is admitted only when its saved
        ``epochs_completed`` matches the reference meta (this process's
        own checkpoint when it has one, else the first sibling's). A
        rank that died before publishing its last rotation leaves a
        one-save-older file behind — tiling ITS shards next to the
        others' would silently assemble a parameter array mixed across
        two optimizer steps, exactly the torn state the loud offset
        refusal used to prevent. A stale sibling here means the re-map
        falls back to "cannot tile" and raises instead."""
        cached = getattr(self, "_sibling_cache", None)
        if cached is not None:
            return cached
        own = self._restore_candidates()
        ref_epochs = self._dir_meta(own[0]).get("epochs_completed") if own else None
        merged: dict[str, np.ndarray] = {}
        for d in self._sibling_candidate_dirs():
            sib_epochs = self._dir_meta(d).get("epochs_completed")
            if ref_epochs is None:
                # Growth restore (no own checkpoint): the first
                # readable sibling sets the reference generation.
                ref_epochs = sib_epochs
            if sib_epochs != ref_epochs:
                continue
            try:
                npz = np.load(os.path.join(d, "state.npz"))
            except (OSError, ValueError):
                continue
            for k in npz.files:
                merged.setdefault(k, npz[k])
        self._sibling_cache = merged
        return merged

    def _restore(self, state):
        self._sibling_cache = None
        candidates = self._restore_candidates()
        if not candidates:
            legacy = [
                d
                for d in self._rotation_dirs()
                if os.path.isdir(d) and not self._dir_is_torn(d)
            ]
            if legacy:
                raise RuntimeError(
                    f"Checkpoint dir(s) {legacy} exist but contain no "
                    "state.npz — an unreadable (pre-npz/orbax) format. "
                    "Delete them to restart from scratch, or restore with "
                    "the version that wrote them."
                )
            # Topology growth: this rank saved nothing, but sibling
            # ranks' files on the shared filesystem can rebuild the
            # full state (whole leaves from any sibling, shard-saved
            # leaves re-mapped below).
            if self._sibling_entries():
                restored = dict(self._sibling_entries())
                return self._restore_from(state, restored, source="siblings")
            raise FileNotFoundError(f"No train-state checkpoint under {self.dirpath}")
        npz = np.load(os.path.join(candidates[0], "state.npz"))
        restored = {k: npz[k] for k in npz.files}
        return self._restore_from(state, restored, source=candidates[0])

    def _restore_from(self, state, restored: dict, *, source: str):
        template = self._tree(state)
        treedef = jax.tree.structure(template)
        tleaves = jax.tree.leaves(template)

        def _mismatch(detail: str) -> KeyError:
            # The most common cause is a CONFIG change between runs — a
            # different DCT_OPTIMIZER restructures opt_state, so the
            # saved flat leaves no longer line up with this run's
            # template. Name that instead of a bare index; a silent
            # misaligned restore would train from garbage weights.
            return KeyError(
                f"Checkpoint {source} does not match this run's "
                f"TrainState: {detail}. Typically DCT_OPTIMIZER (or "
                "another state-shaping knob) changed since the "
                "checkpoint was written. Restore the original setting, "
                f"or clear {self.dirpath} to restart the trajectory."
            )

        # Count check BOTH directions: a template with FEWER leaves than
        # the checkpoint would otherwise restore silently with every flat
        # index shifted onto the wrong (often identically-shaped) array.
        saved_groups = {
            k.split("_s")[0] for k in restored if k and k[0].isdigit()
        }
        if len(saved_groups) != len(tleaves):
            raise _mismatch(
                f"{len(saved_groups)} leaf groups saved, "
                f"{len(tleaves)} expected"
            )
        def _parts_for(entries: dict, i: int) -> dict:
            prefix = f"{i}_s"
            return {
                # 0-d leaves have an empty offset suffix -> key ().
                tuple(
                    int(o) for o in k[len(prefix):].split("x")
                ) if k[len(prefix):] else (): v
                for k, v in entries.items()
                if k.startswith(prefix)
            }

        leaves = []
        remapped: list[int] = []
        for i, t in enumerate(tleaves):
            if str(i) in restored:
                whole = restored[str(i)]
                if tuple(whole.shape) != tuple(getattr(t, "shape", ())):
                    raise _mismatch(
                        f"leaf {i} has shape {tuple(whole.shape)} on disk "
                        f"but {tuple(getattr(t, 'shape', ()))} in the "
                        "template"
                    )
                leaves.append(whole)
                continue
            part_by_key = _parts_for(restored, i)
            if not part_by_key:
                raise _mismatch(f"no data for template leaf {i}")
            arr, was_remapped = self._reassemble(
                t, part_by_key,
                extra_shards=lambda i=i: _parts_for(
                    self._sibling_entries(), i
                ),
            )
            if was_remapped:
                remapped.append(i)
            leaves.append(arr)
        if remapped:
            # A different mesh topology adopted this trajectory: on the
            # record (docs/PARALLELISM.md §topology re-map), values
            # bit-identical by construction (pure data movement).
            saved_layout = self.load_layout()
            to_mesh = None
            for t in tleaves:
                sh = getattr(t, "sharding", None)
                if hasattr(sh, "mesh"):
                    to_mesh = {
                        str(k): int(v)
                        for k, v in dict(sh.mesh.shape).items()
                    }
                    break
            self.last_remap = {
                "leaves": len(remapped),
                "from_mesh": saved_layout.get("mesh"),
                "from_processes": saved_layout.get("process_count"),
                "to_mesh": to_mesh,
            }
            _events.get_default().emit(
                "shard", "shard.topology_remap",
                dir=source, **self.last_remap,
            )
        tree = jax.tree.unflatten(treedef, leaves)
        # Drop the sibling shard pool: it holds full copies of every
        # sibling's arrays and is only valid for THIS restore.
        self._sibling_cache = None
        lin = _lineage.get_default()
        if lin.enabled and source != "siblings":
            # The adopted trajectory becomes a training input: the next
            # checkpoint this run publishes gets a ``consumed`` edge to
            # the state it resumed from — lineage across preemptions.
            _lineage.add_run_input(lin.node(
                "checkpoint", path=os.path.join(source, "state.npz"),
                attrs={"tier": "resume", "restored": True},
            ))
        return state.replace(
            step=jax.numpy.asarray(tree["step"]),
            params=tree["params"],
            opt_state=tree["opt_state"],
            rng=jax.numpy.asarray(tree["rng"]),
        )

"""Pod-scale sharded continuous training (ISSUE 11): the declarative
partition-rule surface and its end-to-end wiring through checkpoint,
eval, publish, and relaunch.

Bit-identity policy (measured on this rig, pinned here so the claims
stay honest):

- SAME layout through different machinery (loop vs serial, save ->
  topology-remap -> restore, gather -> publish) is BIT-identical —
  those paths move data, they do not compute.
- DIFFERENT layouts (DP-replicated vs ZeRO-1/TP) compile DIFFERENT XLA
  programs whose update math can differ by 1 ulp per step (measured:
  5.96e-8 on step 3 of a 5-step MLP run, zero on the other four), so
  cross-layout trajectories pin at <= 1e-6 — a genuinely wrong program
  (dropped term, wrong collective) moves losses by 1e-2+.
"""

import glob
import json
import os

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dct_tpu.config import MeshConfig, ModelConfig
from dct_tpu.models.registry import get_model
from dct_tpu.parallel.mesh import make_mesh
from dct_tpu.parallel.sharding_rules import (
    gather_tree,
    layout_mismatches,
    make_shard_and_gather_fns,
    match_partition_rules,
    parse_rules,
    rules_digest,
    rules_for_family,
    shard_state_with_rules,
    state_shardings,
)
from dct_tpu.train.state import create_train_state

F = 5

TRANSFORMER = dict(
    name="weather_transformer", seq_len=8, d_model=16, n_heads=2,
    n_layers=1, d_ff=32,
)


def _transformer_state(mesh, **shard_kwargs):
    cfg = ModelConfig(**TRANSFORMER)
    model = get_model(cfg, input_dim=F)
    state = create_train_state(
        model, input_dim=F, lr=1e-3, seed=0,
        example_shape=(1, cfg.seq_len, F),
    )
    return shard_state_with_rules(
        state, mesh, family="weather_transformer", **shard_kwargs
    )


# ----------------------------------------------------------------------
# Rule table + grammar


def test_parse_rules_grammar():
    rules = parse_rules(".*dense.*/kernel$=-,model; head/bias$=data ;x$=")
    assert rules[0] == (".*dense.*/kernel$", P(None, "model"))
    assert rules[1] == ("head/bias$", P("data"))
    assert rules[2] == ("x$", P())


@pytest.mark.parametrize(
    "bad",
    ["no-equals-clause", "a=(model", "k$=model,upside"],
    ids=["no-eq", "bad-regex", "bad-axis"],
)
def test_parse_rules_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_rules(bad)


def test_env_rules_override_family_defaults(monkeypatch):
    base = rules_for_family("weather_transformer")
    d0 = rules_digest("weather_transformer")
    monkeypatch.setenv("DCT_SHARD_RULES", "qkv_proj.*/kernel$=")
    assert rules_for_family("weather_transformer")[0] == (
        "qkv_proj.*/kernel$", P()
    )
    assert rules_for_family("weather_transformer")[1:] == base
    # The digest moves with the table: the AOT identity must recompile.
    assert rules_digest("weather_transformer") != d0
    # And the override actually changes the resolved placement.
    mesh = make_mesh(MeshConfig(data=4, model=2))
    state = _transformer_state(mesh)
    specs = {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf.sharding.spec
        for path, leaf in
        jax.tree_util.tree_flatten_with_path(state.params)[0]
    }
    qkv = {k: v for k, v in specs.items() if "qkv_proj/kernel" in k}
    assert qkv and all(v == P() for v in qkv.values()), qkv


def test_match_partition_rules_covers_trainstate(monkeypatch):
    """One rule table resolves specs for the WHOLE TrainState: the Adam
    moments mirror the param paths, so matched params and their moments
    shard identically while scalars/unmatched leaves replicate."""
    mesh = make_mesh(MeshConfig(data=4, model=2))
    state = _transformer_state(mesh)
    tree = {
        "step": state.step, "params": state.params,
        "opt_state": state.opt_state,
    }
    specs = match_partition_rules(rules_for_family("weather_transformer"), tree)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    by_path = {
        "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path): s
        for path, s in flat
    }
    param_qkv = [
        v for k, v in by_path.items()
        if "qkv_proj/kernel" in k and k.startswith("params")
    ]
    moment_qkv = [
        v for k, v in by_path.items()
        if "qkv_proj/kernel" in k and "opt_state" in k
    ]
    assert param_qkv and moment_qkv
    assert set(param_qkv) == set(moment_qkv) == {P(None, "model")}
    assert by_path["step"] == P()


def test_shard_and_gather_fns_round_trip():
    """shard -> gather is the identity, bitwise: the publish path's
    dense arrays are exactly what went onto the mesh."""
    mesh = make_mesh(MeshConfig(data=4, model=2))
    state = _transformer_state(mesh, shard_opt=True)
    shardings = state_shardings(
        state, mesh, shard_opt=True, family="weather_transformer"
    )
    shard_fns, gather_fns = make_shard_and_gather_fns(shardings)
    host = gather_tree(state.params)
    replaced = jax.tree.map(
        lambda fn, a: fn(a), shard_fns.params, host
    )
    back = jax.tree.map(lambda fn, a: fn(a), gather_fns.params, replaced)
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(back)):
        assert np.array_equal(a, b)
    # ...and the re-placed leaves carry the declared layout.
    declared = jax.tree.leaves(shardings.params)
    for leaf, want in zip(jax.tree.leaves(replaced), declared):
        assert leaf.sharding.spec == want.spec


# ----------------------------------------------------------------------
# Declared-vs-actual layout (the trainer.py ~L431 wart, fixed)


def test_layout_mismatches_detects_zero1_output_drift(rng):
    """Under ZeRO-1 the jitted step's output params come back
    data-sharded while the declared layout replicates them — the drift
    the ``shard.layout_mismatch`` event names (measured on this rig: 2
    drifted leaves on the parity MLP at data=8)."""
    from dct_tpu.parallel.mesh import batch_sharding
    from dct_tpu.train.steps import make_train_step

    mesh = make_mesh(MeshConfig(data=8))
    model = get_model(ModelConfig(hidden_dim=64), input_dim=F)
    state = shard_state_with_rules(
        create_train_state(model, input_dim=F, lr=0.01, seed=0),
        mesh, shard_opt=True,
    )
    declared = state_shardings(state, mesh, shard_opt=True)
    assert layout_mismatches(state, declared) == []
    x = jax.device_put(
        rng.standard_normal((32, F)).astype(np.float32),
        batch_sharding(mesh),
    )
    y = jax.device_put(
        rng.integers(0, 2, 32).astype(np.int32), batch_sharding(mesh)
    )
    w = jax.device_put(np.ones(32, np.float32), batch_sharding(mesh))
    out, _m = make_train_step(donate=False)(state, x, y, w)
    drift = layout_mismatches(out, declared)
    assert drift, "expected ZeRO-1 output-layout drift on this rig"
    assert all(d["actual"] == ["data"] for d in drift), drift
    # Reconciliation: the re-pin the trainer runs before checkpointing
    # restores the declared layout exactly.
    repinned = jax.device_put(out, declared)
    assert layout_mismatches(repinned, declared) == []


# ----------------------------------------------------------------------
# Trainer end-to-end: sharded vs DP, and the sharded continuous path


def _fit(tmp_path, tag, *, mesh, processed_dir, epochs=2, resume=False,
         shard_opt=False, shard_params=False, batch_size=16):
    from dct_tpu.config import (
        DataConfig, ObservabilityConfig, RunConfig, TrainConfig,
    )
    from dct_tpu.tracking.client import LocalTracking
    from dct_tpu.train.trainer import Trainer

    base = tmp_path / tag
    cfg = RunConfig(
        data=DataConfig(
            processed_dir=processed_dir, models_dir=str(base / "models")
        ),
        model=ModelConfig(**TRANSFORMER),
        train=TrainConfig(
            epochs=epochs, batch_size=batch_size, lr=1e-3,
            bf16_compute=False, resume=resume, shard_opt_state=shard_opt,
            shard_params=shard_params,
        ),
        mesh=mesh,
        obs=ObservabilityConfig(
            enabled=True, events_dir=str(base / "events"),
            heartbeat_dir="", spans_dir="",
        ),
    )
    tracker = LocalTracking(root=str(base / "mlruns"))
    return Trainer(cfg, tracker=tracker).fit(), cfg


def _read_events(cfg):
    path = os.path.join(cfg.obs.events_dir, "events.jsonl")
    out = []
    with open(path) as f:
        for line in f:
            try:
                out.append(json.loads(line))
            except ValueError:
                continue
    return out


def test_zero_sharded_fit_matches_dp_and_publishes_dense(
    tmp_path, processed_dir
):
    """The tentpole's oracle pin on the SAME mesh: a fully
    rules-sharded fit (ZeRO-1 moments + FSDP params over ``data`` —
    the cross-replica weight-update sharding the motivation cites)
    follows the replicated-DP trajectory to <= 1e-6 per epoch (1-ulp
    layout-compile drift, module docstring) and the PUBLISHED package
    gathers dense — full global shapes, elementwise against the DP
    export at the same bound."""
    from dct_tpu.continuous.evaluator import package_checkpoint

    r_dp, _ = _fit(
        tmp_path, "dp", mesh=MeshConfig(data=8),
        processed_dir=processed_dir,
    )
    r_sh, _cfg = _fit(
        tmp_path, "sharded", mesh=MeshConfig(data=8),
        processed_dir=processed_dir, shard_opt=True, shard_params=True,
    )
    vl_dp = [h["val_loss"] for h in r_dp.history]
    vl_sh = [h["val_loss"] for h in r_sh.history]
    np.testing.assert_allclose(vl_sh, vl_dp, atol=1e-6, rtol=0)

    def pkg(tag, result):
        d = str(tmp_path / f"pkg_{tag}")
        package_checkpoint(result.best_model_path, d)
        npz = np.load(os.path.join(d, "model.npz"))
        return {k: npz[k] for k in npz.files}

    w_dp, w_sh = pkg("dp", r_dp), pkg("sh", r_sh)
    assert sorted(w_dp) == sorted(w_sh)
    qkv = [k for k in w_sh if k.endswith("qkv_proj/kernel")]
    assert qkv and w_sh[qkv[0]].shape == (16, 48)  # dense, not a shard
    for k in w_dp:
        np.testing.assert_allclose(w_sh[k], w_dp[k], atol=1e-6, rtol=0)


def test_tp_sharded_fit_tracks_dp_and_publishes_dense(
    tmp_path, processed_dir
):
    """The model-axis story at matched GLOBAL batch (the mesh data
    axis sizes the global batch, so dp@data=8 runs batch 8/rank vs
    tp@data=4 batch 16/rank = 64 rows either way): a TP+ZeRO-1 mesh
    tracks the DP trajectory to the cross-mesh reduction-order bound
    (1e-3 — the bound test_opt_sharding/test_multihost_tp pin; a wrong
    program moves losses 10x that) and publishes the full dense
    matrices."""
    from dct_tpu.continuous.evaluator import package_checkpoint

    r_dp, _ = _fit(
        tmp_path, "tp_dp", mesh=MeshConfig(data=8),
        processed_dir=processed_dir, batch_size=8,
    )
    r_tp, _cfg = _fit(
        tmp_path, "tp_sh", mesh=MeshConfig(data=4, model=2),
        processed_dir=processed_dir, shard_opt=True, batch_size=16,
    )
    vl_dp = [h["val_loss"] for h in r_dp.history]
    vl_tp = [h["val_loss"] for h in r_tp.history]
    np.testing.assert_allclose(vl_tp, vl_dp, atol=1e-3, rtol=0)
    d = str(tmp_path / "pkg_tp")
    package_checkpoint(r_tp.best_model_path, d)
    npz = np.load(os.path.join(d, "model.npz"))
    qkv = [k for k in npz.files if k.endswith("qkv_proj/kernel")]
    assert qkv and npz[qkv[0]].shape == (16, 48)  # dense, not a shard


def test_sharded_resume_across_mesh_topology_change(tmp_path, processed_dir):
    """The continuous path's topology pivot: train sharded on
    data=4/model=2, RESUME the same trajectory on data=8/model=1 at
    matched global batch — the restore re-maps the saved layout onto
    the new mesh (bit-identity pinned at the checkpoint layer by
    test_topology_remap_restores_bitwise) and the run EXTENDS instead
    of refusing. The control continuation on the unchanged mesh bounds
    the pivoted trajectory at the cross-mesh reduction-order tolerance."""
    import shutil

    _r1, _cfg1 = _fit(
        tmp_path, "pivot", mesh=MeshConfig(data=4, model=2),
        processed_dir=processed_dir, shard_opt=True, batch_size=16,
    )
    # Control: copy the trained state and continue on the SAME mesh.
    shutil.copytree(tmp_path / "pivot", tmp_path / "pivot_ctl")
    r_ctl, _ = _fit(
        tmp_path, "pivot_ctl", mesh=MeshConfig(data=4, model=2),
        processed_dir=processed_dir, shard_opt=True, resume=True,
        batch_size=16,
    )
    # Pivot: same trajectory, NEW topology, same 64-row global batch.
    r2, _cfg2 = _fit(
        tmp_path, "pivot", mesh=MeshConfig(data=8),
        processed_dir=processed_dir, resume=True, batch_size=8,
    )
    assert [h["epoch"] for h in r2.history] == [2, 3]
    vl_new = [h["val_loss"] for h in r2.history]
    vl_ctl = [h["val_loss"] for h in r_ctl.history]
    np.testing.assert_allclose(vl_new, vl_ctl, atol=1e-3, rtol=0)


def test_trainer_emits_layout_mismatch_event(tmp_path, processed_dir):
    """A ZeRO-1 fit whose step output drifts from the declared layout
    puts ``shard.layout_mismatch`` on the event log (reconciled — the
    checkpoint still lands in the declared layout and resumes clean)."""
    _r, cfg = _fit(
        tmp_path, "drift", mesh=MeshConfig(data=8),
        processed_dir=processed_dir, shard_opt=True, epochs=1,
    )
    ev = [
        r for r in _read_events(cfg)
        if r.get("event") == "shard.layout_mismatch"
    ]
    assert ev and ev[0]["reconciled"] is True and ev[0]["leaves"] >= 1
    # The reconciliation is real: the saved resume state restores onto
    # the declared layout without a topology error.
    from dct_tpu.checkpoint.manager import TrainStateCheckpointer

    ck = TrainStateCheckpointer(
        os.path.join(cfg.data.models_dir, "train_state", "p0")
    )
    assert ck.load_layout()["mesh"]["data"] == 8


# ----------------------------------------------------------------------
# Checkpoint layer: layout manifest + topology re-map


def _mlp_state(mesh, **kw):
    model = get_model(ModelConfig(hidden_dim=64), input_dim=F)
    return shard_state_with_rules(
        create_train_state(model, input_dim=F, lr=0.01, seed=0), mesh, **kw
    )


def _state_leaves(state):
    return jax.tree.leaves({
        "step": state.step, "params": state.params,
        "opt_state": state.opt_state, "rng": state.rng,
    })


def test_layout_manifest_written_and_loadable(tmp_path):
    from dct_tpu.checkpoint.manager import TrainStateCheckpointer

    mesh = make_mesh(MeshConfig(data=4, model=2))
    state = _transformer_state(mesh, shard_opt=True)
    ck = TrainStateCheckpointer(str(tmp_path / "ts" / "p0"))
    ck.save(state, meta={"epochs_completed": 1})
    layout = ck.load_layout()
    assert layout["mesh"] == {"data": 4, "model": 2, "seq": 1, "pipe": 1}
    assert layout["process_count"] == 1
    specs = {tuple(e["spec"] or []) for e in layout["leaves"] if e["spec"]}
    assert ("model",) in specs or (None, "model") in {
        tuple(s) for s in
        [tuple(x) for x in (e["spec"] for e in layout["leaves"] if e["spec"])]
    }
    # Async save writes the manifest too.
    ck.save_async(state, meta={"epochs_completed": 2})
    ck.wait()
    assert ck.load_meta()["epochs_completed"] == 2
    assert ck.load_layout()["leaves"]


def _split_leaf_into_shards(npz_path: str, *, parts: int = 2) -> str:
    """Rewrite a live state.npz turning one whole 2-D leaf into
    offset-keyed shard entries — the on-disk shape a DIFFERENT saving
    topology (cross-process sharded leaves) produces."""
    npz = np.load(npz_path)
    entries = {k: npz[k] for k in npz.files}
    key = next(
        k for k in entries
        if "_s" not in k and entries[k].ndim == 2
        and entries[k].shape[0] % parts == 0
    )
    arr = entries.pop(key)
    h = arr.shape[0] // parts
    for p in range(parts):
        entries[f"{key}_s{p * h}x0"] = arr[p * h:(p + 1) * h]
    with open(npz_path, "wb") as f:
        np.savez(f, **entries)
    return key


def test_topology_remap_restores_bitwise_and_emits_event(tmp_path):
    """Shard entries whose offsets match NO current-topology position
    re-map through the dense assembly: restored values bit-identical,
    ``shard.topology_remap`` on the event log, last_remap populated."""
    from dct_tpu.checkpoint.manager import TrainStateCheckpointer
    from dct_tpu.observability import events as _events

    mesh = make_mesh(MeshConfig(data=8))
    state = _mlp_state(mesh, shard_opt=True)
    ck = TrainStateCheckpointer(str(tmp_path / "ts" / "p0"))
    ck.save(state, meta={"epochs_completed": 3})
    _split_leaf_into_shards(
        os.path.join(ck.dirpath, "state", "state.npz")
    )

    log_path = str(tmp_path / "events.jsonl")
    prev = _events.get_default()
    _events.set_default(_events.EventLog(log_path, run_id="remap-test"))
    try:
        restored = ck.restore(_mlp_state(mesh, shard_opt=True))
    finally:
        _events.set_default(prev)
    for a, b in zip(_state_leaves(state), _state_leaves(restored)):
        assert np.array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))
        )
    assert ck.last_remap["leaves"] == 1
    assert ck.last_remap["from_mesh"]["data"] == 8
    with open(log_path) as f:
        recs = [json.loads(line) for line in f]
    assert any(r["event"] == "shard.topology_remap" for r in recs)


def test_topology_remap_refuses_untileable_shards(tmp_path):
    """Missing shards (a private-disk pod's lone local file) still fail
    LOUDLY — a partial tiling must never restore zero-filled weights."""
    from dct_tpu.checkpoint.manager import TrainStateCheckpointer

    mesh = make_mesh(MeshConfig(data=8))
    state = _mlp_state(mesh, shard_opt=True)
    ck = TrainStateCheckpointer(str(tmp_path / "ts" / "p0"))
    ck.save(state)
    npz_path = os.path.join(ck.dirpath, "state", "state.npz")
    key = _split_leaf_into_shards(npz_path)
    npz = np.load(npz_path)
    entries = {k: npz[k] for k in npz.files}
    # Drop one of the two shards: the leaf can no longer be tiled.
    entries.pop(next(k for k in entries if k.startswith(f"{key}_s0")))
    with open(npz_path, "wb") as f:
        np.savez(f, **entries)
    with pytest.raises(ValueError, match="do not tile"):
        ck.restore(_mlp_state(mesh, shard_opt=True))


def test_process_growth_restores_from_siblings(tmp_path):
    """A rank with NO checkpoint of its own (process-count growth)
    restores whole leaves and shard halves from sibling p<rank>/ files:
    exists() says yes, meta rides along, values bitwise."""
    from dct_tpu.checkpoint.manager import TrainStateCheckpointer

    mesh = make_mesh(MeshConfig(data=8))
    state = _mlp_state(mesh, shard_opt=True)
    ck0 = TrainStateCheckpointer(str(tmp_path / "ts" / "p0"))
    ck0.save(state, meta={"epochs_completed": 5})
    key = _split_leaf_into_shards(
        os.path.join(ck0.dirpath, "state", "state.npz")
    )
    # Move ONE shard into a sibling rank's file: p0 alone cannot tile.
    npz_path = os.path.join(ck0.dirpath, "state", "state.npz")
    npz = np.load(npz_path)
    entries = {k: npz[k] for k in npz.files}
    shard_key = next(k for k in entries if k.startswith(f"{key}_s0"))
    p1_dir = str(tmp_path / "ts" / "p1" / "state")
    os.makedirs(p1_dir)
    with open(os.path.join(p1_dir, "state.npz"), "wb") as f:
        np.savez(f, **{shard_key: entries.pop(shard_key)})
    # Siblings are admitted to the shard pool only when their saved
    # generation matches (epochs_completed consistency gate).
    with open(os.path.join(p1_dir, "meta.json"), "w") as f:
        json.dump({"epochs_completed": 5}, f)
    with open(npz_path, "wb") as f:
        np.savez(f, **entries)

    # p0 itself now needs the sibling's shard...
    restored0 = ck0.restore(_mlp_state(mesh, shard_opt=True))
    for a, b in zip(_state_leaves(state), _state_leaves(restored0)):
        assert np.array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))
        )
    # ...and a brand-new rank with NO dir restores entirely from
    # siblings, meta included.
    ck2 = TrainStateCheckpointer(str(tmp_path / "ts" / "p2"))
    assert ck2.exists()
    assert ck2.load_meta()["epochs_completed"] == 5
    restored2 = ck2.restore(_mlp_state(mesh, shard_opt=True))
    for a, b in zip(_state_leaves(state), _state_leaves(restored2)):
        assert np.array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))
        )


def test_stale_sibling_shards_are_refused(tmp_path):
    """A sibling whose checkpoint is one save GENERATION behind (its
    rank died before publishing the last rotation) must not contribute
    shards: tiling epoch-N shards next to epoch-N-1 shards would
    silently restore a parameter array mixed across two optimizer
    steps. The consistency gate drops the stale sibling and the re-map
    fails loudly instead."""
    from dct_tpu.checkpoint.manager import TrainStateCheckpointer

    mesh = make_mesh(MeshConfig(data=8))
    state = _mlp_state(mesh, shard_opt=True)
    ck = TrainStateCheckpointer(str(tmp_path / "ts" / "p0"))
    ck.save(state, meta={"epochs_completed": 5})
    npz_path = os.path.join(ck.dirpath, "state", "state.npz")
    key = _split_leaf_into_shards(npz_path)
    npz = np.load(npz_path)
    entries = {k: npz[k] for k in npz.files}
    shard_key = next(k for k in entries if k.startswith(f"{key}_s0"))
    p1_dir = str(tmp_path / "ts" / "p1" / "state")
    os.makedirs(p1_dir)
    with open(os.path.join(p1_dir, "state.npz"), "wb") as f:
        np.savez(f, **{shard_key: entries.pop(shard_key)})
    with open(os.path.join(p1_dir, "meta.json"), "w") as f:
        json.dump({"epochs_completed": 4}, f)  # one save behind
    with open(npz_path, "wb") as f:
        np.savez(f, **entries)
    with pytest.raises(ValueError, match="do not tile"):
        ck.restore(_mlp_state(mesh, shard_opt=True))


# ----------------------------------------------------------------------
# Gather-on-publish + the eval harness under rules


def test_weights_from_state_gathers_dense_bitwise(tmp_path):
    """The live-state publish path: a TP+ZeRO-1-sharded TrainState
    exports byte-identical weights to the checkpoint-file path — the
    gather fns make the layout invisible to serving."""
    from dct_tpu.checkpoint.manager import save_checkpoint
    from dct_tpu.serving.score_gen import (
        weights_from_checkpoint, weights_from_state,
    )

    mesh = make_mesh(MeshConfig(data=4, model=2))
    state = _transformer_state(mesh, shard_opt=True)
    meta = dict(TRANSFORMER, model="weather_transformer", input_dim=F)
    meta.pop("name")
    w_live, _ = weights_from_state(state, meta)
    ckpt = str(tmp_path / "m.ckpt")
    save_checkpoint(ckpt, state.params, meta)
    w_file, _ = weights_from_checkpoint(ckpt)
    assert sorted(w_live) == sorted(w_file)
    for k in w_live:
        assert np.array_equal(w_live[k], w_file[k]), k
        assert isinstance(w_live[k], np.ndarray)


def test_harness_jax_engine_scores_under_rules(tmp_path, monkeypatch):
    """The jax engine places challenger params by the family rule table
    on the env-configured mesh: on model=2 the scored probabilities
    match the replicated numpy twin to engine tolerance (2e-6 — the
    documented jax/numpy parity bound)."""
    from dct_tpu.checkpoint.manager import save_checkpoint
    from dct_tpu.evaluation.harness import batched_probs
    from dct_tpu.serving.score_gen import weights_from_checkpoint

    mesh = make_mesh(MeshConfig(data=4, model=2))
    state = _transformer_state(mesh)
    meta = dict(TRANSFORMER, model="weather_transformer", input_dim=F)
    meta.pop("name")
    ckpt = str(tmp_path / "m.ckpt")
    save_checkpoint(ckpt, state.params, meta)
    weights, meta = weights_from_checkpoint(ckpt)

    rng = np.random.default_rng(0)
    x = rng.standard_normal((24, TRANSFORMER["seq_len"], F)).astype(
        np.float32
    )
    p_np = batched_probs(weights, meta, x, engine="numpy")
    monkeypatch.setenv("DCT_MESH_DATA", "4")
    monkeypatch.setenv("DCT_MESH_MODEL", "2")
    p_jax = batched_probs(weights, meta, x, engine="jax", batch_size=8)
    np.testing.assert_allclose(p_jax, p_np, atol=2e-6)


# ----------------------------------------------------------------------
# Loop + AOT wiring


def test_loop_forwards_sharding_knobs_to_child_ranks(tmp_path, monkeypatch):
    """Supervised rounds must rebuild the loop's mesh/sharding config
    in every child rank: the env the launcher receives carries the
    DCT_MESH_* / DCT_SHARD_* knobs from the loop's RunConfig."""
    from dct_tpu.config import (
        DataConfig, LoopConfig, RunConfig, TrainConfig,
    )
    from dct_tpu.continuous.loop import AlwaysOnLoop

    captured = {}

    class FakeLauncher:
        def supervise(self, cmd, *, world_size, env, **kw):
            captured.update(env)

            class R:
                success = True
                classification = "clean"
                restarts = 0
            return R()

    import dct_tpu.launch.launcher as launcher_mod

    monkeypatch.setattr(
        launcher_mod, "LocalProcessLauncher", lambda: FakeLauncher()
    )
    monkeypatch.setenv("DCT_SHARD_RULES", "qkv_proj.*/kernel$=")
    cfg = RunConfig(
        data=DataConfig(
            processed_dir=str(tmp_path / "proc"),
            models_dir=str(tmp_path / "models"),
            raw_csv=str(tmp_path / "raw.csv"),
        ),
        train=TrainConfig(shard_opt_state=True),
        mesh=MeshConfig(data=2, model=2),
        loop=LoopConfig(
            train_mode="supervised", packages_dir=str(tmp_path / "pkgs"),
        ),
    )
    loop = AlwaysOnLoop(cfg, client=object())
    loop._run_round_supervised()
    assert captured["DCT_MESH_DATA"] == "2"
    assert captured["DCT_MESH_MODEL"] == "2"
    assert captured["DCT_SHARD_OPT_STATE"] == "1"
    assert captured["DCT_SHARD_PARAMS"] == "0"
    assert captured["DCT_SHARD_RULES"] == "qkv_proj.*/kernel$="


def test_rules_digest_partitions_aot_identity(tmp_path):
    """Two stores differing only in the rule-table digest mint DISJOINT
    artifact paths: a layout change can never load the other layout's
    executable."""
    from dct_tpu.compilecache.aot import ExecutableStore

    a = ExecutableStore(
        str(tmp_path / "aot"),
        identity={"family": "f", "mesh": "m", "extra": "rules=aaaa"},
    )
    b = ExecutableStore(
        str(tmp_path / "aot"),
        identity={"family": "f", "mesh": "m", "extra": "rules=bbbb"},
    )
    assert a._path("scan_k1", "sig") != b._path("scan_k1", "sig")


@pytest.mark.slow
def test_sharded_two_process_relaunch_hits_aot_cache(tmp_path):
    """ISSUE 11 acceptance: a REAL 2-process sharded world (transformer
    TP spanning the ranks), SIGKILLed by a crash fault and healed by
    the PR 3 supervisor, warm-relaunches through the AOT store — the
    healed attempt's compile windows all carry cache=hit, and each rank
    minted its own artifact (per-rank identity)."""
    from dct_tpu.compilecache import spinup

    work = str(tmp_path / "spin")
    os.makedirs(work)
    spinup.prepare_processed(work, rows=400)
    menv = {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "DCT_MODEL": "weather_transformer",
        "DCT_SEQ_LEN": "8", "DCT_D_MODEL": "16", "DCT_N_HEADS": "2",
        "DCT_N_LAYERS": "1", "DCT_D_FF": "32", "DCT_BF16_COMPUTE": "0",
        "DCT_MESH_DATA": "1", "DCT_MESH_MODEL": "2",
        # Serial donation keeps the crashed (fault-armed, auto-serial)
        # and healed attempts on ONE program identity, same as the DP
        # warm-relaunch e2e (test_compilecache).
        "DCT_PREFETCH_SPANS": "0",
    }
    warm = spinup.measure_relaunch(
        work, cache_on=True, world_size=2, model_env=menv, prewarm=True,
    )
    assert warm["returncode"] == 0, warm
    assert warm["relaunch_cache"] == ["hit"], warm
    artifacts = os.listdir(os.path.join(work, "aot"))
    # Per-rank identities: two ranks, each minted its own artifact.
    assert len({a.split("-")[1] for a in artifacts}) >= 2, artifacts


@pytest.mark.slow
def test_sharded_resume_after_cross_process_save(
    tmp_path, processed_dir
):
    """The cross-process topology pivot: train on a REAL 2-process
    model=2 world (params shard-saved per rank), then resume the SAME
    trajectory in ONE process on the 8-device mesh — the restore
    re-maps rank-local shards (pulling p1's halves via the sibling
    pool) onto the new topology, emits ``shard.topology_remap``, and
    the run extends."""
    from tests.test_multihost_tp import launch_training

    from dct_tpu.config import (
        DataConfig, ObservabilityConfig, RunConfig, TrainConfig,
    )
    from dct_tpu.train.trainer import Trainer

    launch_training(
        processed_dir, tmp_path, world_size=2, port=29573,
        models_sub="m_flow", runs_sub="r_flow",
        env_overrides={
            "DCT_MODEL": "weather_transformer",
            "DCT_N_LAYERS": "1",
            "DCT_MESH_MODEL": "2",
        },
    )
    models_dir = str(tmp_path / "m_flow")
    p0 = os.path.join(
        models_dir, "train_state", "p0", "state", "state.npz"
    )
    assert any("_s" in k for k in np.load(p0).files)

    cfg = RunConfig(
        data=DataConfig(
            processed_dir=processed_dir, models_dir=models_dir
        ),
        model=ModelConfig(**TRANSFORMER),
        train=TrainConfig(
            epochs=1, batch_size=16, lr=1e-3, bf16_compute=False,
            resume=True,
        ),
        mesh=MeshConfig(data=8),
        obs=ObservabilityConfig(
            enabled=True, events_dir=str(tmp_path / "ev_flow"),
            heartbeat_dir="", spans_dir="",
        ),
    )
    from dct_tpu.tracking.client import LocalTracking

    tracker = LocalTracking(root=str(tmp_path / "mlruns_flow"))
    result = Trainer(cfg, tracker=tracker).fit()
    assert np.isfinite(result.val_loss)
    # epoch 0 ran in the 2-proc world; this is its continuation.
    assert [h["epoch"] for h in result.history] == [1]
    ev_path = os.path.join(str(tmp_path / "ev_flow"), "events.jsonl")
    with open(ev_path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    assert any(r.get("event") == "shard.topology_remap" for r in recs)

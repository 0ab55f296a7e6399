"""Compile cache + AOT executables (ISSUE 9, docs/OBSERVABILITY.md
§compile cache): artifact round trips, loud-miss degradation, cache
labels on the compile accounting, bit-identity of cache-hit runs, and
the supervised-relaunch e2e.

The correctness contract under test:

- a HIT deserializes the exact executable the miss path built — same
  machine code, bit-identical losses and checkpoint bytes;
- a corrupted / fingerprint-skewed / foreign artifact is a LOUD miss
  (``compile.cache_miss`` with the reason) that falls back to a normal
  jit compile — never a crash, never a wrong result;
- ``compile.window`` events carry ``cache=hit|miss|disabled`` so a
  warm relaunch can PROVE it paid zero fresh XLA compiles.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dct_tpu.compilecache import cache as cc_cache  # noqa: E402
from dct_tpu.compilecache.aot import (  # noqa: E402
    ExecutableStore,
    signature_of,
    store_from_env,
)


def _collect(events: list):
    def emit(component, event, **fields):
        events.append({"component": component, "event": event, **fields})

    return emit


def _mk_store(root, events=None, **identity):
    identity.setdefault("family", "weather_mlp")
    identity.setdefault("config_hash", "abcd1234")
    identity.setdefault("mesh", "data1_model1_seq1_pipe1")
    return ExecutableStore(
        str(root), identity=identity, enabled=True,
        emit=_collect(events) if events is not None else None,
    )


def _jit_fn():
    def f(x, y):
        return jnp.tanh(x @ y).sum(axis=-1)

    return jax.jit(f)


ARGS = (
    jnp.asarray(np.random.default_rng(0).normal(size=(8, 16)), jnp.float32),
    jnp.asarray(np.random.default_rng(1).normal(size=(16, 4)), jnp.float32),
)


# ======================================================================
# store unit semantics


def test_miss_publishes_artifact_then_fresh_store_hits(tmp_path):
    events: list = []
    store = _mk_store(tmp_path, events)
    prog = store.wrap(_jit_fn(), program="p")
    out_miss = np.asarray(prog(*ARGS))
    assert store.states == {"p": "miss"}
    files = [f for f in os.listdir(tmp_path) if f.endswith(".aotx")]
    assert len(files) == 1
    assert not any(".tmp." in f for f in os.listdir(tmp_path))

    # A fresh store + wrapper (a "new process"): loads, same bits.
    events2: list = []
    store2 = _mk_store(tmp_path, events2)
    prog2 = store2.wrap(_jit_fn(), program="p")
    out_hit = np.asarray(prog2(*ARGS))
    assert store2.states == {"p": "hit"}
    # The hit reads the roofline provenance off the artifact header
    # (ISSUE 14) before announcing the hit.
    assert [e["event"] for e in events2] == [
        "roofline.program", "compile.cache_hit",
    ]
    assert store2.costs["p"]["source"] == "header"
    np.testing.assert_array_equal(out_miss, out_hit)
    # Steady state: the in-memory entry dispatches without re-loading.
    np.testing.assert_array_equal(np.asarray(prog2(*ARGS)), out_hit)


def test_submesh_program_warm_loads_on_its_own_devices(tmp_path):
    """A program compiled for a sub-mesh of a many-device backend loads
    back onto exactly those devices (deserialize_and_load defaults to
    EVERY device of the backend, which makes a 2-device program an
    8-device one that dies at its first call)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()[2:4]
    mesh = Mesh(np.array(devs), ("data",))
    x = jax.device_put(ARGS[0], NamedSharding(mesh, P("data")))
    y = jax.device_put(ARGS[1], NamedSharding(mesh, P()))
    ref = np.asarray(_mk_store(tmp_path).wrap(_jit_fn(), program="p")(x, y))
    (art,) = [f for f in os.listdir(tmp_path) if f.endswith(".aotx")]
    with open(tmp_path / art, "rb") as f:
        header = json.loads(f.read().split(b"\n", 2)[1])
    assert header["device_ids"] == [d.id for d in devs]

    events: list = []
    store = _mk_store(tmp_path, events)
    out = store.wrap(_jit_fn(), program="p")(x, y)
    assert store.states == {"p": "hit"}
    assert not [e for e in events if e["event"] == "compile.cache_miss"]
    assert {d.id for d in out.sharding.device_set} == {d.id for d in devs}
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_corrupt_artifact_is_loud_miss_with_identical_results(tmp_path):
    store = _mk_store(tmp_path)
    ref = np.asarray(store.wrap(_jit_fn(), program="p")(*ARGS))
    (art,) = [f for f in os.listdir(tmp_path) if f.endswith(".aotx")]
    path = os.path.join(tmp_path, art)
    blob = bytearray(open(path, "rb").read())
    blob[-20] ^= 0xFF  # flip a payload byte
    open(path, "wb").write(bytes(blob))

    events: list = []
    store2 = _mk_store(tmp_path, events)
    out = np.asarray(store2.wrap(_jit_fn(), program="p")(*ARGS))
    np.testing.assert_array_equal(ref, out)
    assert store2.states == {"p": "miss"}
    misses = [e for e in events if e["event"] == "compile.cache_miss"]
    assert misses and "sha256" in misses[0]["reason"]


def test_fingerprint_skew_is_loud_miss(tmp_path):
    store = _mk_store(tmp_path)
    store.wrap(_jit_fn(), program="p")(*ARGS)
    (art,) = [f for f in os.listdir(tmp_path) if f.endswith(".aotx")]
    path = os.path.join(tmp_path, art)
    raw = open(path, "rb").read()
    magic, rest = raw[:8], raw[8:]
    nl = rest.find(b"\n")
    header = json.loads(rest[:nl])
    header["jaxlib"] = "0.0.0"  # a foreign build's artifact
    open(path, "wb").write(
        magic + json.dumps(header, sort_keys=True).encode()
        + b"\n" + rest[nl + 1:]
    )

    events: list = []
    store2 = _mk_store(tmp_path, events)
    out = np.asarray(store2.wrap(_jit_fn(), program="p")(*ARGS))
    assert store2.states == {"p": "miss"}
    misses = [e for e in events if e["event"] == "compile.cache_miss"]
    assert misses and misses[0]["reason"] == "fingerprint skew"
    assert "jaxlib" in misses[0]["skew"]
    assert np.isfinite(out).all()


def test_identity_mismatch_never_loads_foreign_program(tmp_path):
    """Same shapes, different baked constants (config_hash): the
    artifact filename/header keying must keep them apart."""
    a = _mk_store(tmp_path, config_hash="aaaa0000")
    a.wrap(_jit_fn(), program="p")(*ARGS)
    b = _mk_store(tmp_path, config_hash="bbbb1111")
    b.wrap(_jit_fn(), program="p")(*ARGS)
    assert a.states == {"p": "miss"}
    assert b.states == {"p": "miss"}  # own compile, not a's artifact
    assert len([f for f in os.listdir(tmp_path) if f.endswith(".aotx")]) == 2


def test_code_change_never_loads_the_previous_versions_artifact(
        tmp_path, monkeypatch):
    """Same configuration, changed program source (a kernel rewritten
    under an unchanged config): the store must miss, not run the old
    machine code. Two versions sharing one store keep separate artifacts."""
    from dct_tpu.compilecache import aot

    monkeypatch.setenv("DCT_COMPILE_CACHE", "on")
    digest = aot.source_digest()
    assert digest == aot.source_digest() and len(digest) == 16
    old = store_from_env(str(tmp_path), family="f", config_hash="c")
    assert old.identity["code"] == digest
    old.wrap(_jit_fn(), program="p")(*ARGS)
    assert store_from_env(
        str(tmp_path), family="f", config_hash="c"
    ).wrap(_jit_fn(), program="p")(*ARGS) is not None
    monkeypatch.setattr(aot, "source_digest", lambda: "0" * 16)
    new = store_from_env(str(tmp_path), family="f", config_hash="c")
    new.wrap(_jit_fn(), program="p")(*ARGS)
    assert old.states == {"p": "miss"} and new.states == {"p": "miss"}
    assert len([f for f in os.listdir(tmp_path) if f.endswith(".aotx")]) == 2


def test_disabled_store_is_transparent(tmp_path):
    store = ExecutableStore(str(tmp_path), enabled=False)
    prog = store.wrap(_jit_fn(), program="p")
    out = np.asarray(prog(*ARGS))
    assert np.isfinite(out).all()
    assert store.states == {"p": "disabled"}
    assert not os.listdir(tmp_path)


def test_signature_separates_shapes_and_weak_types(tmp_path):
    store = _mk_store(tmp_path)
    prog = store.wrap(_jit_fn(), program="p")
    prog(*ARGS)
    x2 = jnp.asarray(np.zeros((4, 16)), jnp.float32)
    prog(x2, ARGS[1])
    assert len([f for f in os.listdir(tmp_path) if f.endswith(".aotx")]) == 2
    assert signature_of(ARGS) != signature_of((x2, ARGS[1]))


def test_non_jit_callable_degrades_to_plain_call(tmp_path):
    store = _mk_store(tmp_path)
    prog = store.wrap(lambda x, y: np.asarray(x) @ np.asarray(y))
    out = prog(*ARGS)
    assert out.shape == (8, 4)
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".aotx")]


# ======================================================================
# env contract


def test_cache_mode_resolution(monkeypatch):
    monkeypatch.delenv("DCT_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cc_cache.cache_mode() == "auto"
    assert cc_cache.resolve_cache_dir() is None
    assert not cc_cache.enabled() and not cc_cache.aot_enabled()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/cc")
    assert cc_cache.resolve_cache_dir() == "/tmp/cc"
    assert cc_cache.enabled() and cc_cache.aot_enabled()
    monkeypatch.setenv("DCT_COMPILE_CACHE", "off")
    assert not cc_cache.enabled()
    monkeypatch.setenv("DCT_COMPILE_CACHE", "on")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cc_cache.resolve_cache_dir() == cc_cache.DEFAULT_CACHE_DIR
    monkeypatch.setenv("DCT_COMPILE_CACHE_AOT", "0")
    assert cc_cache.enabled() and not cc_cache.aot_enabled()


def test_store_from_env_gating(tmp_path, monkeypatch):
    monkeypatch.setenv("DCT_COMPILE_CACHE", "off")
    assert not store_from_env(str(tmp_path)).enabled
    monkeypatch.setenv("DCT_COMPILE_CACHE", "on")
    assert store_from_env(str(tmp_path)).enabled
    assert not store_from_env(None).enabled
    monkeypatch.setenv("DCT_COMPILE_CACHE_AOT", "0")
    assert not store_from_env(str(tmp_path)).enabled


def test_export_env_pins_resolved_dir(monkeypatch):
    monkeypatch.delenv("DCT_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    child: dict = {}
    cc_cache.export_env(child)
    assert "JAX_COMPILATION_CACHE_DIR" not in child  # cache off -> no-op
    child = {"DCT_COMPILE_CACHE": "on"}
    cc_cache.export_env(child)
    assert child["JAX_COMPILATION_CACHE_DIR"] == os.path.abspath(
        cc_cache.DEFAULT_CACHE_DIR
    )
    # An explicit parent-env dir is pinned verbatim (absolute), so
    # every relaunch attempt resolves the SAME directory even if the
    # supervisor and ranks run from different cwds.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/mine")
    child = {"DCT_COMPILE_CACHE": "on"}
    cc_cache.export_env(child)
    assert child["JAX_COMPILATION_CACHE_DIR"] == os.path.abspath("/tmp/mine")
    monkeypatch.setenv("DCT_COMPILE_CACHE", "off")
    child = {"DCT_COMPILE_CACHE": "off"}
    cc_cache.export_env(child)
    assert "JAX_COMPILATION_CACHE_DIR" not in child


def test_warm_sizes_parse(monkeypatch):
    monkeypatch.setenv("DCT_COMPILE_CACHE_WARM_SIZES", "64, 1,8,bogus,8")
    assert cc_cache.warm_sizes() == [1, 8, 64]
    monkeypatch.setenv("DCT_COMPILE_CACHE_WARM_SIZES", "")
    assert cc_cache.warm_sizes() == []


# ======================================================================
# compile accounting labels


def test_compile_report_carries_cache_states():
    from dct_tpu.observability.goodput import compile_report

    report = compile_report(
        [("scan_k1", 3.0), ("scan_k4", 1.0), ("eager_step", 0.2)],
        family="weather_mlp", config_hash="ff00", mesh="data1",
        cache_states={"scan_k1": "hit", "scan_k4": "miss"},
    )
    by_prog = {r["program"]: r["cache"] for r in report}
    assert by_prog == {
        "scan_k1": "hit", "scan_k4": "miss", "eager_step": "disabled",
    }


def test_dump_labels_compile_series_with_cache(tmp_path):
    from dct_tpu.observability.dump import write_train_metrics_prom
    from dct_tpu.observability.goodput import GoodputLedger

    led = GoodputLedger()
    led.start()
    path = str(tmp_path / "m.prom")
    write_train_metrics_prom(
        path, led.summary(), run_id="r",
        compile_windows=[{
            "program": "scan_k1", "family": "f", "config_hash": "c",
            "mesh": "m", "cache": "hit", "count": 1, "seconds": 0.01,
        }],
    )
    body = open(path).read()
    assert 'cache="hit"' in body
    assert "dct_compile_windows_total" in body


def test_inspect_compile_section_counts_cache_states():
    from dct_tpu.observability.inspect import build_report

    events = [
        {"ts": 1.0, "run_id": "r", "component": "compile",
         "event": "compile.window", "program": "scan_k1", "family": "f",
         "config_hash": "c", "mesh": "m", "cache": "hit", "count": 2,
         "seconds": 0.04},
        {"ts": 1.1, "run_id": "r", "component": "compile",
         "event": "compile.window", "program": "serve_scorer",
         "family": "f", "config_hash": "c", "mesh": "m", "cache": "miss",
         "count": 1, "seconds": 0.8},
    ]
    report = build_report(events, [], [], "r", None)
    assert "cache=hit" in report and "cache=miss" in report
    assert "hit 2 / miss 1" in report


# ======================================================================
# trainer integration: bit-identity + labels (the acceptance core)


def _processed_dir(tmp_path) -> str:
    from dct_tpu.data.synthetic import generate_weather_csv
    from dct_tpu.etl.preprocess import preprocess_csv_to_parquet

    csv = str(tmp_path / "raw.csv")
    processed = str(tmp_path / "processed")
    generate_weather_csv(csv, rows=300, seed=0)
    preprocess_csv_to_parquet(csv, processed)
    return processed


def _fit_once(tmp_path, tag, monkeypatch, processed):
    from dct_tpu.config import RunConfig
    from dct_tpu.train.trainer import Trainer

    monkeypatch.setenv("DCT_PROCESSED_DIR", processed)
    monkeypatch.setenv("DCT_MODELS_DIR", str(tmp_path / f"models_{tag}"))
    monkeypatch.setenv("DCT_EVENTS_DIR", str(tmp_path / f"events_{tag}"))
    monkeypatch.setenv("DCT_TRACKING_DIR", str(tmp_path / f"mlruns_{tag}"))
    monkeypatch.setenv("DCT_HEARTBEAT_DIR", str(tmp_path / f"hb_{tag}"))
    monkeypatch.setenv("DCT_EPOCHS", "2")
    monkeypatch.setenv("DCT_BATCH_SIZE", "16")
    monkeypatch.delenv("DCT_RUN_ID", raising=False)
    result = Trainer(RunConfig.from_env()).fit()
    events = [
        json.loads(line)
        for line in open(tmp_path / f"events_{tag}" / "events.jsonl")
    ]
    windows = [e for e in events if e.get("event") == "compile.window"]
    return result, windows


def test_trainer_warm_rerun_is_bitwise_identical_and_labelled(
    tmp_path, monkeypatch
):
    """Two identical runs sharing one AOT dir: run A misses (and
    publishes), run B hits — with the SAME loss trajectory bit for bit
    and byte-identical deploy checkpoints."""
    processed = _processed_dir(tmp_path)
    monkeypatch.setenv("DCT_COMPILE_CACHE", "on")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    monkeypatch.setenv("DCT_COMPILE_CACHE_AOT_DIR", str(tmp_path / "aot"))
    res_a, win_a = _fit_once(tmp_path, "a", monkeypatch, processed)
    res_b, win_b = _fit_once(tmp_path, "b", monkeypatch, processed)
    assert [w["cache"] for w in win_a] == ["miss"]
    assert [w["cache"] for w in win_b] == ["hit"]
    assert res_a.history == res_b.history  # floats compare exactly
    bytes_a = open(res_a.best_model_path, "rb").read()
    bytes_b = open(res_b.best_model_path, "rb").read()
    assert bytes_a == bytes_b


def test_trainer_corrupt_artifact_degrades_to_identical_compile(
    tmp_path, monkeypatch
):
    """A torn/garbage artifact between runs: run B takes the loud-miss
    path and still reproduces run A bit for bit."""
    processed = _processed_dir(tmp_path)
    monkeypatch.setenv("DCT_COMPILE_CACHE", "on")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    monkeypatch.setenv("DCT_COMPILE_CACHE_AOT_DIR", str(tmp_path / "aot"))
    res_a, _ = _fit_once(tmp_path, "a", monkeypatch, processed)
    for name in os.listdir(tmp_path / "aot"):
        with open(tmp_path / "aot" / name, "r+b") as f:
            f.seek(0)
            f.write(b"garbage!")
    res_b, win_b = _fit_once(tmp_path, "b", monkeypatch, processed)
    assert [w["cache"] for w in win_b] == ["miss"]
    assert res_a.history == res_b.history
    assert (
        open(res_a.best_model_path, "rb").read()
        == open(res_b.best_model_path, "rb").read()
    )


def test_trainer_cache_off_matches_cache_on_bitwise(tmp_path, monkeypatch):
    """The cache must be invisible to the math: a cache-hit run equals
    a no-cache-at-all run bit for bit."""
    processed = _processed_dir(tmp_path)
    monkeypatch.setenv("DCT_COMPILE_CACHE", "off")
    res_off, win_off = _fit_once(tmp_path, "off", monkeypatch, processed)
    monkeypatch.setenv("DCT_COMPILE_CACHE", "on")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    monkeypatch.setenv("DCT_COMPILE_CACHE_AOT_DIR", str(tmp_path / "aot"))
    _fit_once(tmp_path, "warmup", monkeypatch, processed)
    res_hit, win_hit = _fit_once(tmp_path, "hit", monkeypatch, processed)
    assert [w["cache"] for w in win_off] == ["disabled"]
    assert [w["cache"] for w in win_hit] == ["hit"]
    assert res_off.history == res_hit.history
    assert (
        open(res_off.best_model_path, "rb").read()
        == open(res_hit.best_model_path, "rb").read()
    )


# ======================================================================
# serving: package-carried scorer


def test_warm_package_scorer_publishes_and_serves_hits(
    tmp_path, monkeypatch
):
    from dct_tpu.compilecache.aot import _example_batch, warm_package_scorer
    from dct_tpu.serving.batching import _build_jax_scorer
    from dct_tpu.serving.score_gen import generate_score_package

    processed = _processed_dir(tmp_path)
    monkeypatch.setenv("DCT_COMPILE_CACHE", "off")
    res, _ = _fit_once(tmp_path, "pkg", monkeypatch, processed)
    pkg = str(tmp_path / "package")
    generate_score_package(res.best_model_path, pkg)
    assert not os.path.isdir(os.path.join(pkg, "aot"))  # cache off

    done = warm_package_scorer(pkg, sizes=[1, 3])  # 3 pads to 4
    assert done == [1, 4]
    arts = os.listdir(os.path.join(pkg, "aot"))
    assert len(arts) == 2 and all(a.endswith(".aotx") for a in arts)

    # A "fresh worker" with the cache armed loads the packaged
    # executables and answers exactly like the jit path.
    npz = np.load(os.path.join(pkg, "model.npz"))
    weights = {k: npz[k] for k in npz.files}
    meta = json.load(open(os.path.join(pkg, "model_meta.json")))
    x = np.asarray(
        np.random.default_rng(3).normal(size=(3, int(meta["input_dim"]))),
        np.float32,
    )
    monkeypatch.setenv("DCT_COMPILE_CACHE", "on")
    warm_meta = dict(meta, _aot_dir=os.path.join(pkg, "aot"))
    probs_warm = _build_jax_scorer(weights, warm_meta)(x)
    monkeypatch.setenv("DCT_COMPILE_CACHE", "off")
    probs_cold = _build_jax_scorer(weights, dict(meta))(x)
    np.testing.assert_array_equal(probs_warm, probs_cold)


def test_scorer_identity_includes_weights_digest(tmp_path, monkeypatch):
    """The jitted scorer bakes the weights in as constants, so two
    packages with IDENTICAL meta but different weights must never
    share an artifact — the second build misses and serves its own
    model's probabilities."""
    from dct_tpu.serving.batching import _build_jax_scorer

    meta = {
        "model": "weather_mlp", "input_dim": 4, "hidden_dim": 8,
        "num_classes": 2, "dropout": 0.0,
        "_aot_dir": str(tmp_path / "aot"),
    }
    rng = np.random.default_rng(0)

    def mk_weights(seed):
        r = np.random.default_rng(seed)
        return {
            "w0": r.normal(size=(4, 8)).astype(np.float32),
            "b0": np.zeros(8, np.float32),
            "w1": r.normal(size=(8, 2)).astype(np.float32),
            "b1": np.zeros(2, np.float32),
        }

    x = rng.normal(size=(2, 4)).astype(np.float32)
    monkeypatch.setenv("DCT_COMPILE_CACHE", "on")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    w_a, w_b = mk_weights(1), mk_weights(2)
    probs_a = _build_jax_scorer(w_a, dict(meta))(x)
    probs_b = _build_jax_scorer(w_b, dict(meta))(x)
    # Different weights -> different artifacts on disk, and the second
    # scorer's output matches ITS weights' jit reference, not model A.
    arts = os.listdir(tmp_path / "aot")
    assert len(arts) == 2
    monkeypatch.setenv("DCT_COMPILE_CACHE", "off")
    ref_b = _build_jax_scorer(w_b, {
        k: v for k, v in meta.items() if k != "_aot_dir"
    })(x)
    np.testing.assert_array_equal(probs_b, ref_b)
    assert not np.array_equal(probs_a, probs_b)


# ======================================================================
# e2e: supervised SIGKILL-relaunch, warm vs cold (the acceptance)


def test_e2e_supervised_relaunch_warm_vs_cold(tmp_path):
    """Through the REAL supervisor relaunch path: with a pre-warmed
    cache the healed attempt executes zero fresh XLA compiles (every
    compile.window is cache=hit, compile seconds a fraction of the cold
    control's) and the run books a smaller startup_recovery debt than
    the cold control (the crashing attempt itself started warm, so the
    supervisor hands less lost wall clock to the relaunch)."""
    from dct_tpu.compilecache import spinup

    spinup.prepare_processed(str(tmp_path), rows=400)
    model_env = {
        "DCT_MODEL": "weather_transformer",
        "DCT_N_LAYERS": "2", "DCT_D_MODEL": "64", "DCT_N_HEADS": "4",
        "DCT_D_FF": "256", "DCT_SEQ_LEN": "16",
        "DCT_PREFETCH_SPANS": "0",
    }
    cold = spinup.measure_relaunch(
        str(tmp_path), cache_on=False, model_env=model_env
    )
    warm = spinup.measure_relaunch(
        str(tmp_path), cache_on=True, prewarm=True, model_env=model_env
    )
    assert cold["returncode"] == 0, cold["stderr_tail"]
    assert warm["returncode"] == 0, warm["stderr_tail"]
    # Cold control: real compiles, no cache in the loop.
    assert cold["relaunch_cache"] == ["disabled"]
    assert cold["relaunch_compile_s"] > 0.5
    # Warm: zero fresh XLA compiles on the healed attempt — proven by
    # the cache labels — and near-zero compile-window seconds (what
    # remains is the trace + deserialize + first dispatch).
    assert warm["relaunch_cache"] == ["hit"]
    assert warm["relaunch_compile_s"] < 0.5 * cold["relaunch_compile_s"]
    # The healed run reaches its first step sooner...
    assert (
        warm["sigkill_to_first_step_s"] < cold["sigkill_to_first_step_s"]
    )
    # ...and books a smaller startup_recovery debt than the cold
    # control (the crashed attempt's wall, which the supervisor hands
    # to the relaunch as debt, no longer contains an XLA compile).
    assert warm["startup_recovery_s"] < cold["startup_recovery_s"]

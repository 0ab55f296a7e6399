"""Roofline introspection plane + flight recorder (ISSUE 14).

Covers the acceptance surface end to end:

- cost_analysis round trip for EVERY registry family's real train-step
  program, and for each MPMD stage program individually;
- the goodput-ledger join: per-program dispatch stats, MFU math, the
  compute-vs-memory-bound classification, compile.window cost stamping;
- exposition round trip: dct_program_* gauges for all four families on
  ONE aggregated /metrics scrape;
- AOT artifact header provenance: a warm load reports the same analytic
  cost the compiling run captured;
- flight recorder: file-trigger fire-once-per-mtime semantics, deadline
  stop, SIGUSR2, busy refusal, the serving /debug/profile endpoint, and
  the trigger-capture e2e — a mid-run capture produces a TensorBoard-
  loadable plugins/profile dir while the loss trajectory stays bitwise
  identical to an untriggered run;
- MPMD transfer byte/latency histograms on the metrics plane;
- the trajectory sentinel's program_mfu / transfer_wait_frac series.
"""

from __future__ import annotations

import glob
import json
import os
import socket
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dct_tpu.compilecache.aot import ExecutableStore
from dct_tpu.config import ModelConfig, RunConfig
from dct_tpu.observability import roofline as rf
from dct_tpu.observability.capture import (
    CaptureBusy,
    FlightRecorder,
    capture_profile,
)
from dct_tpu.observability.goodput import GoodputLedger, compile_report
from dct_tpu.observability.metrics import MetricsRegistry

FAMILY_CONFIGS = {
    "weather_mlp": ModelConfig(name="weather_mlp", hidden_dim=16),
    "weather_gru": ModelConfig(
        name="weather_gru", hidden_dim=16, n_layers=1, seq_len=8,
    ),
    "weather_transformer": ModelConfig(
        name="weather_transformer", d_model=16, n_heads=2, n_layers=1,
        d_ff=32, seq_len=8,
    ),
    "weather_moe": ModelConfig(
        name="weather_moe", d_model=16, n_heads=2, n_layers=1, d_ff=32,
        seq_len=8, n_experts=2,
    ),
}
INPUT_DIM = 5


def _family_program(name: str, cfg: ModelConfig):
    """(CachedProgram over the family's REAL train step, example args):
    the exact program shape the trainer dispatches, disabled-store
    wrapped so the lowered-analysis path (the default) is exercised."""
    from dct_tpu.models.registry import get_model, is_sequence_model
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import make_train_step

    sequence = is_sequence_model(name)
    example_shape = (1, cfg.seq_len, INPUT_DIM) if sequence else None
    model = get_model(cfg, input_dim=INPUT_DIM, compute_dtype=jnp.float32)
    state = create_train_state(
        model, input_dim=INPUT_DIM, lr=1e-3, seed=0,
        example_shape=example_shape,
    )
    batch = 4
    shape = (batch, cfg.seq_len, INPUT_DIM) if sequence else (
        batch, INPUT_DIM
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 2, batch), jnp.int32)
    w = jnp.ones((batch,), jnp.float32)
    store = ExecutableStore(None, enabled=False)
    prog = store.wrap(
        make_train_step(donate=False), program=f"train_{name}"
    )
    return store, prog, (state, x, y, w)


@pytest.mark.parametrize("family", sorted(FAMILY_CONFIGS))
def test_cost_roundtrip_every_family(family):
    """Every registry family's real train-step program reports analytic
    FLOPs and bytes accessed through the CachedProgram capture path."""
    store, prog, args = _family_program(family, FAMILY_CONFIGS[family])
    state2, _metrics = prog(*args)
    jax.block_until_ready(state2.params)
    cost = store.costs[f"train_{family}"]
    assert cost["flops"] > 0
    assert cost["bytes_accessed"] > 0
    assert cost["source"] == "lowered"


def test_enabled_store_captures_memory_analysis(tmp_path):
    """The miss path analyzes the COMPILED executable: HBM fields join
    the record, and a warm process reads the same numbers back off the
    artifact header without re-deriving them."""
    events = []
    store = ExecutableStore(
        str(tmp_path), identity={"family": "t"}, enabled=True,
        emit=lambda c, e, **f: events.append((c, e, f)),
    )

    @jax.jit
    def f(x):
        return (x @ x.T).sum()

    x = jnp.ones((16, 8))
    prog = store.wrap(f, program="p")
    prog(x)
    cost = store.costs["p"]
    assert cost["source"] == "compiled"
    assert cost["flops"] > 0
    assert cost["hbm_peak_bytes"] > 0
    assert ("roofline", "roofline.program") in [
        (c, e) for c, e, _f in events
    ]
    # Warm process: header provenance, no fresh analysis needed.
    warm = ExecutableStore(
        str(tmp_path), identity={"family": "t"}, enabled=True,
    )
    wprog = warm.wrap(f, program="p")
    wprog(x)
    assert warm.states["p"] == "hit"
    assert warm.costs["p"]["source"] == "header"
    assert warm.costs["p"]["flops"] == cost["flops"]
    assert warm.costs["p"]["hbm_peak_bytes"] == cost["hbm_peak_bytes"]


def test_roofline_disabled_gates_warm_load_too(tmp_path, monkeypatch):
    """DCT_ROOFLINE=0 means NO roofline telemetry, warm or cold: a hit
    off an artifact whose header carries stamped provenance must not
    resurrect the series the operator turned off."""

    @jax.jit
    def f(x):
        return (x * 2).sum()

    x = jnp.ones(8)
    store = ExecutableStore(str(tmp_path), identity={"family": "t"},
                            enabled=True)
    store.wrap(f, program="p")(x)  # cold: stamps header provenance
    assert "p" in store.costs
    monkeypatch.setenv("DCT_ROOFLINE", "0")
    warm = ExecutableStore(str(tmp_path), identity={"family": "t"},
                           enabled=True)
    warm.wrap(f, program="p")(x)
    assert warm.states["p"] == "hit"
    assert "p" not in warm.costs


def test_planned_profiler_yields_to_active_capture(tmp_path):
    """A flight capture active when the planned one-epoch profiler's
    target epoch arrives must SKIP the planned trace (one jax.profiler
    session per process), never crash the fit — and the planned window
    must work again once the capture released the session."""
    from dct_tpu.utils.profiling import Profiler

    events = []
    rec, trig = _recorder(tmp_path, events, capture_s=5.0)
    with open(trig, "w") as f:
        f.write("5")
    rec.poll(epoch=0)
    assert events[-1][0] == "profile.capture_start"
    prof = Profiler(str(tmp_path / "planned"), enabled=True, epoch=1)
    prof.maybe_start(1)  # must not raise; planned window yields
    assert not prof._active
    rec.close()  # capture released the session
    prof.maybe_start(1)
    assert prof._active
    prof.maybe_stop(1)
    assert not prof._active
    # The session gate is free again for on-demand captures.
    capture_profile(str(tmp_path / "after"), 0.01)


def test_trigger_defers_while_session_busy(tmp_path):
    """An operator touch landing while the planned Profiler holds the
    session is DEFERRED — one capture_error note, silent retries, and
    the capture starts at the first span boundary after the session
    frees (never silently dropped)."""
    from dct_tpu.utils.profiling import Profiler

    events = []
    rec, trig = _recorder(tmp_path, events)
    prof = Profiler(str(tmp_path / "planned"), enabled=True, epoch=0)
    prof.maybe_start(0)  # holds the session for "the epoch"
    with open(trig, "w") as f:
        f.write("0.05")
    rec.poll(epoch=0)
    rec.poll(epoch=1)  # retry is silent: one error note per trigger
    names = [e for e, _f in events]
    assert names.count("profile.capture_error") == 1
    assert "deferred" in events[0][1]["error"]
    prof.maybe_stop(0)  # session freed
    rec.poll(epoch=2)
    assert events[-1][0] == "profile.capture_start"
    rec.close()
    assert [e for e, _f in events][-1] == "profile.capture_end"


def test_roofline_disabled_by_env(monkeypatch):
    monkeypatch.setenv("DCT_ROOFLINE", "0")
    store = ExecutableStore(None, enabled=False)

    @jax.jit
    def f(x):
        return x + 1

    prog = store.wrap(f, program="off")
    prog(jnp.ones(4))
    assert "off" not in store.costs


def test_mpmd_stage_programs_report_cost():
    """Each MPMD stage's fwd/bwd/update programs report analytic cost
    individually — exercised through a real in-process runner step."""
    from dct_tpu.parallel import mpmd
    from dct_tpu.train import mpmd_trainer as mt

    n_stages, m = 2, 4
    cfg = ModelConfig(
        name="weather_transformer_pp", d_model=16, n_heads=2,
        n_layers=2, d_ff=32, seq_len=8, n_stages=n_stages, dropout=0.0,
    )
    run_cfg = RunConfig()
    run_cfg.model = cfg
    spec = type(run_cfg.mpmd)(
        stages=",".join(["1"] * n_stages), microbatches=m,
    ).to_spec(n_devices=jax.device_count())
    meshes = mpmd.carve_stage_meshes(spec.device_counts, model=1)
    full = mt.build_full_state(run_cfg, INPUT_DIM, compute_dtype=jnp.float32)
    stage_states = [
        mt.shard_stage_state(
            mpmd.split_state(full, k, n_stages), meshes[k]
        )
        for k in range(n_stages)
    ]
    fns = mt.build_stage_fns(cfg, INPUT_DIM, compute_dtype=jnp.float32)
    stores = [ExecutableStore(None, enabled=False) for _ in range(n_stages)]
    progs = [
        mpmd.make_stage_programs(k, n_stages, fns, store=stores[k])
        for k in range(n_stages)
    ]
    runner = mpmd.MpmdRunner(spec, stage_states, progs, meshes)
    rng = np.random.default_rng(0)
    b = m * 2
    x = rng.standard_normal((b, cfg.seq_len, INPUT_DIM)).astype(np.float32)
    y = rng.integers(0, 2, b).astype(np.int32)
    w = np.ones(b, np.float32)
    runner.train_step(x, y, w)
    for k, store in enumerate(stores):
        for name in ("fwd", "bwd", "update"):
            cost = store.costs.get(f"mpmd_{name}_s{k}")
            assert cost and cost["flops"] > 0, (k, name, store.costs)


def test_ledger_dispatch_stats_and_amend():
    t = [0.0]

    def clock():
        return t[0]

    ledger = GoodputLedger(clock=clock)
    ledger.start()
    # First dispatch = compile: excluded from roofline stats.
    cat = ledger.add_dispatch("train_step", "k", 3.0)
    assert cat == "compile"
    assert "k" not in ledger.dispatch_stats
    for _ in range(2):
        cat = ledger.add_dispatch("train_step", "k", 1.0)
    assert cat == "train_step"
    assert ledger.dispatch_stats["k"] == [2, 2.0]
    ledger.amend_dispatch_window("k", 0.5)
    ledger.amend_dispatch_window("k", -9.0)  # never shrinks
    assert ledger.dispatch_stats["k"] == [2, 2.5]
    with ledger.dispatch("train_step", key="k"):
        t[0] += 2.0
    assert ledger.dispatch_stats["k"] == [3, 4.5]


def test_program_report_join_and_classification(monkeypatch):
    monkeypatch.setenv("DCT_PEAK_TFLOPS", "0.001")  # 1e9 FLOPs/s
    monkeypatch.setenv("DCT_HBM_GBPS", "1")         # 1e9 B/s; ridge = 1
    costs = {
        "hot": {"flops": 1e8, "bytes_accessed": 1e7,
                "hbm_peak_bytes": 42, "source": "compiled"},
        "membound": {"flops": 1e6, "bytes_accessed": 1e7,
                     "source": "lowered"},
        "analytic_only": {"flops": 5.0, "bytes_accessed": 2.0,
                          "source": "lowered"},
    }
    stats = {"hot": [5, 1.0], "membound": [1, 1.0]}
    rep = {
        r["program"]: r
        for r in rf.program_report(
            costs, stats, n_chips=1, family="f", config_hash="c",
            mesh="m",
        )
    }
    hot = rep["hot"]
    # 1e8 x 5 / 1.0s / 1e9 peak = 0.5
    assert hot["mfu"] == pytest.approx(0.5)
    assert hot["arithmetic_intensity"] == pytest.approx(10.0)
    assert hot["bound"] == "compute"
    assert hot["hbm_peak_bytes"] == 42
    assert rep["membound"]["bound"] == "memory"
    assert "mfu" not in rep["analytic_only"]
    assert rep["analytic_only"]["bound"] == "compute"


def test_compile_report_carries_cost():
    windows = [("k", 2.0), ("k", 0.1)]
    rep = compile_report(
        windows, family="f",
        costs={"k": {"flops": 7.0, "bytes_accessed": 3.0,
                     "hbm_peak_bytes": 11, "source": "compiled"}},
    )
    assert rep[0]["flops"] == 7.0
    assert rep[0]["bytes_accessed"] == 3.0
    assert rep[0]["hbm_peak_bytes"] == 11


def test_exposition_roundtrip_all_families(tmp_path, monkeypatch):
    """dct_program_* gauge families for all four registry families on
    ONE aggregated scrape: per-family final snapshots merge into a body
    carrying flops + a live MFU gauge per family."""
    from dct_tpu.observability import aggregate
    from dct_tpu.observability.dump import build_train_registry

    monkeypatch.setenv("DCT_PEAK_TFLOPS", "0.001")
    monkeypatch.setenv("DCT_HBM_GBPS", "1")
    mdir = str(tmp_path / "metrics")
    for i, family in enumerate(sorted(FAMILY_CONFIGS)):
        rep = rf.program_report(
            {f"train_{family}": {
                "flops": 1e6 * (i + 1), "bytes_accessed": 1e5,
                "hbm_peak_bytes": 1000 + i, "source": "compiled",
            }},
            {f"train_{family}": [3, 0.5]},
            n_chips=1, family=family, mesh="data1",
        )
        reg = build_train_registry(
            {"categories": {}, "goodput_fraction": 0.5,
             "wall_seconds": 1.0, "epochs": 1},
            run_id=f"r{i}", roofline=rep,
        )
        aggregate.write_snapshot(
            reg.snapshot(proc=f"train-{family}", final=True), mdir
        )
    text, _merged = aggregate.aggregate_text(mdir)
    for family in FAMILY_CONFIGS:
        assert f'dct_program_flops{{family="{family}"' in text
        assert f'dct_program_mfu{{bound="compute",family="{family}"' in text
        assert f'dct_program_hbm_peak_bytes{{family="{family}"' in text


# ----------------------------------------------------------------------
# Flight recorder.


def _recorder(tmp_path, events, **kw):
    trig = str(tmp_path / "trigger")
    kw.setdefault("trigger_path", trig)
    kw.setdefault("capture_s", 0.05)
    rec = FlightRecorder(
        str(tmp_path / "traces"), rank=0,
        emit=lambda c, e, **f: events.append((e, f)), **kw,
    )
    return rec, trig


def test_file_trigger_capture_and_deadline_stop(tmp_path):
    events = []
    rec, trig = _recorder(tmp_path, events)
    rec.poll(epoch=0)  # no trigger yet
    assert events == []
    with open(trig, "w") as f:
        f.write("0.05")
    rec.poll(epoch=1)
    assert events[-1][0] == "profile.capture_start"
    assert events[-1][1]["trigger"] == "file"
    rec.poll(epoch=2)  # deadline not yet passed is clock-dependent;
    time.sleep(0.08)
    rec.poll(epoch=3)
    names = [e for e, _f in events]
    assert names.count("profile.capture_start") == 1
    assert names.count("profile.capture_end") == 1
    cap_dir = events[-1][1]["dir"]
    assert glob.glob(os.path.join(cap_dir, "plugins", "profile", "*"))
    # Same mtime never refires.
    rec.poll(epoch=4)
    assert [e for e, _f in events].count("profile.capture_start") == 1
    # A new touch fires again.
    time.sleep(0.01)
    os.utime(trig)
    rec.poll(epoch=5)
    assert [e for e, _f in events].count("profile.capture_start") == 2
    rec.close()
    assert [e for e, _f in events].count("profile.capture_end") == 2


def test_sigusr2_trigger(tmp_path):
    import signal

    events = []
    rec, _trig = _recorder(tmp_path, events, trigger_path="")
    rec.install_signal()
    try:
        os.kill(os.getpid(), signal.SIGUSR2)
        time.sleep(0.02)
        rec.poll(epoch=0)
        assert events[-1][0] == "profile.capture_start"
        assert events[-1][1]["trigger"] == "signal"
    finally:
        rec.close()
    assert [e for e, _f in events][-1] == "profile.capture_end"


def test_concurrent_capture_refused(tmp_path):
    events = []
    rec, trig = _recorder(tmp_path, events)
    with open(trig, "w") as f:
        f.write("5")
    rec.poll(epoch=0)
    assert events[-1][0] == "profile.capture_start"
    with pytest.raises(CaptureBusy):
        capture_profile(str(tmp_path / "other"), 0.01)
    rec.close()


def test_trigger_capture_e2e_bitwise(tmp_path, processed_dir):
    """The acceptance pin: an on-demand capture during a real run
    produces a TensorBoard-loadable plugins/profile dir AND the loss
    trajectory is bitwise identical to an untriggered run."""
    from dct_tpu.tracking.client import LocalTracking
    from dct_tpu.train.trainer import Trainer

    def run(tag: str, trigger: bool):
        root = tmp_path / tag
        cfg = RunConfig()
        cfg.data.processed_dir = processed_dir
        cfg.data.models_dir = str(root / "models")
        cfg.train.epochs = 4
        cfg.train.batch_size = 16
        cfg.obs.events_dir = str(root / "events")
        cfg.obs.heartbeat_dir = str(root / "hb")
        cfg.obs.spans_dir = str(root / "spans")
        cfg.profile.trace_dir = str(root / "traces")
        cfg.profile.trigger_path = (
            str(root / "trigger") if trigger else ""
        )
        cfg.profile.capture_s = 0.05
        cfg.profile.sigusr2 = False
        if trigger:
            os.makedirs(root, exist_ok=True)
            with open(root / "trigger", "w") as f:
                f.write("0.05")
        tracker = LocalTracking(root=str(root / "runs"), experiment="t")
        res = Trainer(cfg, tracker=tracker).fit()
        return res, str(root)

    plain, _ = run("plain", trigger=False)
    traced, troot = run("traced", trigger=True)
    # Loadable trace from the mid-run capture.
    profile_dirs = glob.glob(
        os.path.join(troot, "traces", "capture-*", "plugins",
                     "profile", "*")
    )
    assert profile_dirs, "trigger produced no plugins/profile dir"
    ev = [
        json.loads(line)
        for line in open(os.path.join(troot, "events", "events.jsonl"))
    ]
    names = [e["event"] for e in ev]
    assert "profile.capture_start" in names
    assert "profile.capture_end" in names
    # Capture never perturbs training: trajectories bitwise equal.
    assert [h["train_loss"] for h in traced.history] == [
        h["train_loss"] for h in plain.history
    ]
    assert [h["val_loss"] for h in traced.history] == [
        h["val_loss"] for h in plain.history
    ]
    # The run-end roofline join landed too (live MFU needs a peak —
    # absent on the CPU table — but analytic flops always report).
    roof = [e for e in ev if e["event"] == "roofline.report"]
    assert roof and roof[0]["flops"] > 0


def test_serving_debug_profile_endpoint(tmp_path, monkeypatch):
    import urllib.error
    import urllib.request

    from dct_tpu.serving.server import make_server_from_weights

    monkeypatch.setenv("DCT_TRACE_DIR", str(tmp_path / "traces"))
    rng = np.random.default_rng(0)
    weights = {
        "w1": rng.standard_normal((5, 8)).astype(np.float32),
        "b1": np.zeros(8, np.float32),
        "w2": rng.standard_normal((8, 2)).astype(np.float32),
        "b2": np.zeros(2, np.float32),
    }
    meta = {"model": "weather_mlp", "input_dim": 5, "hidden": 8,
            "num_classes": 2}
    srv = make_server_from_weights(weights, meta)
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        r = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/profile?seconds=0.05"
        )
        body = json.loads(r.read())
        assert r.status == 200
        assert glob.glob(
            os.path.join(body["trace_dir"], "plugins", "profile", "*")
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/profile?seconds=abc"
            )
        assert ei.value.code == 400
    finally:
        srv.shutdown()
        srv.server_close()


# ----------------------------------------------------------------------
# MPMD transfer accounting.


def test_transfer_histograms_record_bytes_and_latency():
    from dct_tpu.parallel import mpmd_transfer as mt

    reg = MetricsRegistry()
    mt.arm_transfer_metrics(reg)
    try:
        a, b = socket.socketpair()
        ca, cb = mt.SocketChannel(a), mt.SocketChannel(b)
        payload = np.arange(1024, dtype=np.float32)
        ca.send(payload)
        got = cb.recv(timeout=5.0)
        np.testing.assert_array_equal(got, payload)
        cb.send(got * 2)
        ca.recv(timeout=5.0)
        text = reg.render()
        assert (
            'dct_mpmd_transfer_bytes_total{direction="send"} 8192'
            in text
        )
        assert (
            'dct_mpmd_transfer_bytes_total{direction="recv"} 8192'
            in text
        )
        assert 'dct_mpmd_transfer_frames_total{direction="send"} 2' in text
        assert 'dct_mpmd_transfer_seconds_bucket' in text
        ca.close()
        cb.close()
    finally:
        mt.disarm_transfer_metrics()
    # Disarmed: transfers keep flowing, nothing records.
    c, d = socket.socketpair()
    mt.SocketChannel(c).send(np.ones(4))
    mt.SocketChannel(d).recv(timeout=5.0)
    assert reg.render().count('direction="send"} 2') >= 1


# ----------------------------------------------------------------------
# Trajectory sentinel.


def test_inspector_roofline_section(tmp_path):
    from dct_tpu.observability.inspect import build_report

    events = [
        {"ts": 1.0, "run_id": "r", "component": "roofline",
         "event": "roofline.report", "program": "scan_k1",
         "flops": 1e6, "bytes_accessed": 1e5, "hbm_peak_bytes": 10,
         "arithmetic_intensity": 10.0, "mfu": 0.31, "bound": "compute"},
        {"ts": 2.0, "run_id": "r", "component": "profile",
         "event": "profile.capture_start", "dir": "/d", "seconds": 1},
        {"ts": 3.0, "run_id": "r", "component": "profile",
         "event": "profile.capture_end", "dir": "/d", "seconds": 1.0},
    ]
    report = build_report(events, [], [], "r", None)
    assert "Roofline" in report
    assert "scan_k1" in report
    assert "MFU=0.31" in report
    assert "compute-bound" in report
    assert "flight recorder: 1 capture(s), 1 completed" in report

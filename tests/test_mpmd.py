"""MPMD pipeline-parallel trainer (ISSUE 13): spec grammar, 1F1B/GPipe
schedules, bubble math, the SPMD<->MPMD state pivots, oracle parity,
per-stage AOT identity, cross-topology resume, and the transfer plane.

The SPMD pipeline oracle is the sequential stack (the PP family's
documented oracle — tests/test_pipeline*.py prove GPipe == sequential);
the MPMD pin is the LOSS trajectory within 1e-5 (measured ~6e-8 over 20
steps: microbatch accumulation reorders float reductions, so bitwise is
not promised — docs/PARALLELISM.md §MPMD tolerance policy). Pivot paths
are pure data movement and pin BITWISE.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dct_tpu.config import ModelConfig, MpmdConfig, RunConfig
from dct_tpu.parallel import mpmd
from dct_tpu.parallel import mpmd_transfer
from dct_tpu.train import mpmd_trainer as mt

SMALL = dict(
    name="weather_transformer_pp", dropout=0.0, seq_len=8, d_model=16,
    n_heads=2, n_layers=2, d_ff=32, n_stages=2,
)
INPUT_DIM = 5


def _small_cfg(tmp_path=None, **model_over):
    cfg = RunConfig()
    cfg.model = ModelConfig(**{**SMALL, **model_over})
    cfg.train.bf16_compute = False
    cfg.train.batch_size = 8
    cfg.mpmd = MpmdConfig(stages="1,1", microbatches=4)
    if tmp_path is not None:
        cfg.data.models_dir = str(tmp_path / "models")
    return cfg


def _full_state(cfg):
    return mt.build_full_state(cfg, INPUT_DIM, compute_dtype=jnp.float32)


def _runner(cfg, full=None):
    spec = cfg.mpmd.to_spec(n_devices=jax.device_count())
    meshes = mpmd.carve_stage_meshes(spec.device_counts, model=1)
    full = full if full is not None else _full_state(cfg)
    states = [
        mt.shard_stage_state(
            mpmd.split_state(full, k, spec.n_stages), meshes[k]
        )
        for k in range(spec.n_stages)
    ]
    fns = mt.build_stage_fns(cfg.model, INPUT_DIM, compute_dtype=jnp.float32)
    progs = [
        mpmd.make_stage_programs(k, spec.n_stages, fns)
        for k in range(spec.n_stages)
    ]
    return mpmd.MpmdRunner(spec, states, progs, meshes)


def _batches(n, b=8, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (
            rng.standard_normal(
                (b, SMALL["seq_len"], INPUT_DIM)
            ).astype(np.float32),
            rng.integers(0, 2, b).astype(np.int32),
            np.ones(b, np.float32),
        )
        for _ in range(n)
    ]


# ----------------------------------------------------------------------
# Spec grammar: loud failures at parse time (satellite 1).


def test_stage_spec_parses_count_and_explicit_counts():
    assert mpmd.parse_stage_spec("2", n_devices=8) == (4, 4)
    assert mpmd.parse_stage_spec("1,1") == (1, 1)
    assert mpmd.parse_stage_spec(" 2 , 1 , 1 ") == (2, 1, 1)


@pytest.mark.parametrize(
    "text,match",
    [
        ("", "empty"),
        ("two", "not an integer"),
        ("1", ">= 2 stages"),
        ("0,1", "must be >= 1"),
        ("3", "does not divide"),  # with n_devices=8
        ("4,4,4", "asks for 12"),  # with n_devices=8
    ],
)
def test_stage_spec_malformed_is_loud(text, match):
    with pytest.raises(mpmd.MpmdSpecError, match=match):
        mpmd.parse_stage_spec(text, n_devices=8)


def test_spec_env_values_validate_loudly():
    with pytest.raises(mpmd.MpmdSpecError, match="DCT_MPMD_SCHEDULE"):
        MpmdConfig(stages="1,1", schedule="zigzag").to_spec()
    with pytest.raises(mpmd.MpmdSpecError, match="MICROBATCHES"):
        MpmdConfig(stages="1,1,1", microbatches=2).to_spec()
    with pytest.raises(mpmd.MpmdSpecError, match="TRANSFER_TIMEOUT"):
        MpmdConfig(stages="1,1", transfer_timeout_s=0).to_spec()
    spec = MpmdConfig(stages="1,1").to_spec()
    assert spec.n_microbatches == 4  # default 2x stages


def test_trainer_mode_refusals():
    cfg = _small_cfg()
    cfg.model.dropout = 0.2
    with pytest.raises(mpmd.MpmdSpecError, match="DCT_DROPOUT"):
        mt._validate_cfg(cfg)
    cfg = _small_cfg()
    cfg.train.grad_clip_norm = 1.0
    with pytest.raises(mpmd.MpmdSpecError, match="GRAD_CLIP"):
        mt._validate_cfg(cfg)
    cfg = _small_cfg()
    cfg.model.name = "weather_mlp"
    with pytest.raises(mpmd.MpmdSpecError, match="pipeline-parallel"):
        mt._validate_cfg(cfg)


def test_untileable_stage_map_is_loud():
    with pytest.raises(mpmd.MpmdSpecError, match="does not tile"):
        mpmd.stage_layers(2, 3)
    # A 2-stage checkpointed tree refuses a 4-stage split.
    cfg = _small_cfg()
    full = _full_state(cfg)
    with pytest.raises(mpmd.MpmdSpecError, match="untileable"):
        mpmd.split_params(full.params, 0, 4)


# ----------------------------------------------------------------------
# Schedules + bubble math (satellite 2's analytic half).


@pytest.mark.parametrize("p,m", [(2, 4), (2, 8), (4, 8)])
def test_1f1b_schedule_properties(p, m):
    ops = mpmd.build_schedule(p, m, "1f1b")
    assert len(ops) == p
    for i, stage_ops in enumerate(ops):
        fwds = [o for o in stage_ops if o.kind == "fwd"]
        bwds = [o for o in stage_ops if o.kind == "bwd"]
        assert [o.mb for o in fwds] == list(range(m))
        assert [o.mb for o in bwds] == list(range(m))
        # fwd(mb) precedes bwd(mb); warmup fills are P-1-i deep.
        pos = {(o.kind, o.mb): j for j, o in enumerate(stage_ops)}
        for mb in range(m):
            assert pos[("fwd", mb)] < pos[("bwd", mb)]
        fills = [o for o in stage_ops if o.phase == "fill"]
        assert len(fills) == min(p - 1 - i, m)
    # The LAST stage has no fill: it alternates f/b from its first op.
    assert all(o.phase != "fill" for o in ops[p - 1])
    # In-flight activations never exceed P - i (1F1B's memory bound).
    for i, stage_ops in enumerate(ops):
        live = peak = 0
        for o in stage_ops:
            live += 1 if o.kind == "fwd" else -1
            peak = max(peak, live)
        assert peak <= p - i


def test_gpipe_schedule_is_all_fwd_then_all_bwd():
    ops = mpmd.build_schedule(2, 4, "gpipe")
    kinds = [o.kind for o in ops[0]]
    assert kinds == ["fwd"] * 4 + ["bwd"] * 4


def test_analytic_bubble_values():
    assert mpmd.analytic_bubble(2, 8) == pytest.approx(1 / 9)
    assert mpmd.analytic_bubble(4, 4) == pytest.approx(3 / 7)


def test_measured_bubble_recovers_analytic_on_ideal_walls():
    # t(M) = a*(M + P - 1): the ideal pipeline's wall.
    p, a = 4, 0.01
    for m in (4, 8):
        t1, t2 = a * (m + p - 1), a * (2 * m + p - 1)
        assert mpmd.measured_bubble(t1, t2, m, 2 * m) == pytest.approx(
            mpmd.analytic_bubble(p, m), abs=1e-9
        )
    with pytest.raises(ValueError):
        mpmd.measured_bubble(1.0, 2.0, 8, 8)


def _gpipe_walls_over_ledger(p: int, m: int):
    """Run the real GPipe program at ``m`` and ``2 m`` microbatches of a
    fixed size under a goodput ledger: one compile dispatch and three
    timed ones each. Returns ``(ledger, t_small, t_large)``, the best
    timed wall of each. Chunky stage compute so scheduling noise stays
    small beside it."""
    from dct_tpu.observability.goodput import GoodputLedger
    from dct_tpu.parallel.pipeline import (
        gpipe_tick_apply,
        stack_stage_params,
    )

    d = 256
    rng = np.random.default_rng(0)
    stacked = stack_stage_params([
        {"w": jnp.asarray(rng.standard_normal((d, d)) * 0.1, jnp.float32)}
        for _ in range(p)
    ])

    def stage_fn(params, x):
        h = x
        for _ in range(4):
            h = jnp.tanh(h @ params["w"])
        return h

    mb_rows = 256
    ledger = GoodputLedger()
    ledger.start()

    def timed(n_mb: int) -> float:
        x = jnp.asarray(
            rng.standard_normal((mb_rows * n_mb, d)), jnp.float32
        )
        f = jax.jit(
            lambda pp, xx: gpipe_tick_apply(
                stage_fn, pp, xx, n_microbatches=n_mb
            )
        )
        with ledger.dispatch("train_step", key=f"gpipe_m{n_mb}"):
            jax.block_until_ready(f(stacked, x))  # compile window
        best = None
        for _ in range(3):
            t0 = ledger.clock()
            with ledger.dispatch("train_step", key=f"gpipe_m{n_mb}"):
                jax.block_until_ready(f(stacked, x))
            dt = ledger.clock() - t0
            best = dt if best is None or dt < best else best
        return best

    return ledger, timed(m), timed(2 * m)


def test_gpipe_dispatches_bill_the_ledger():
    """Satellite 2, the half that does not depend on how fast the
    machine is: each program's first dispatch bills to ``compile``, the
    timed ones to ``train_step`` — the ledger carries the windows the
    bubble measurement uses — and the slope method stays a fraction
    whatever walls it is given."""
    p, m = 4, 4
    ledger, t1, t2 = _gpipe_walls_over_ledger(p, m)
    assert ledger.seconds["compile"] > 0
    assert ledger.seconds["train_step"] >= t1 + t2
    assert set(ledger.dispatch_stats) == {"gpipe_m4", "gpipe_m8"}
    assert 0.0 <= mpmd.measured_bubble(t1, t2, m, 2 * m) <= 1.0
    # On the ideal pipeline's walls it is the documented fraction.
    assert mpmd.measured_bubble(
        m + p - 1, 2 * m + p - 1, m, 2 * m
    ) == pytest.approx(mpmd.analytic_bubble(p, m))


@pytest.mark.slow
def test_gpipe_measured_vs_analytic_bubble_over_ledger():
    """The documented ``(P-1)/(M+P-1)`` fraction against a MEASUREMENT
    of the real GPipe program: step wall is affine in M at fixed
    microbatch size, and the intercept fraction (slope method) must
    recover the analytic bubble. A ratio of two wall-clock timings, so
    not for a loaded machine: slow-marked (ROADMAP D12), out of tier-1's
    six-worker run where it failed in the runs of PRs 25 and 28."""
    p, m = 4, 4
    _, t1, t2 = _gpipe_walls_over_ledger(p, m)
    measured = mpmd.measured_bubble(t1, t2, m, 2 * m)
    analytic = mpmd.analytic_bubble(p, m)  # 0.429
    assert measured == pytest.approx(analytic, abs=0.15)


# ----------------------------------------------------------------------
# State pivots: SPMD stacked <-> per-stage, bitwise.


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree_util.tree_leaves_with_path(b)
    assert len(la) == len(lb)
    for (pa, va), (_pb, vb) in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb), err_msg=str(pa)
        )


@pytest.mark.parametrize("optimizer", ["adam", "sgd"])
def test_split_merge_roundtrip_bitwise(optimizer):
    cfg = _small_cfg()
    cfg.train.optimizer = optimizer
    cfg.train.momentum = 0.9 if optimizer == "sgd" else 0.0
    full = _full_state(cfg)
    stages = [mpmd.split_state(full, k, 2) for k in range(2)]
    # Stage 0 carries the embedding, the last stage the head.
    assert "in_proj" in stages[0].params["params"]
    assert "head" in stages[1].params["params"]
    assert "in_proj" not in stages[1].params["params"]
    merged = mpmd.merge_stage_states(stages, template=full)
    _assert_trees_equal(full.params, merged.params)
    _assert_trees_equal(full.opt_state, merged.opt_state)


# ----------------------------------------------------------------------
# Oracle parity: the SPMD pipeline oracle's loss trajectory.


def test_runner_matches_spmd_oracle_loss_trajectory():
    from dct_tpu.train.steps import _eval_body, _train_body

    cfg = _small_cfg()
    full = _full_state(cfg)
    runner = _runner(cfg, full)
    batches = _batches(6)
    oracle = full
    step = jax.jit(_train_body)
    for i, (x, y, w) in enumerate(batches):
        oracle, loss_o, _ = step(oracle, x, y, w)
        loss_m, _wall = runner.train_step(x, y, w)
        assert abs(float(loss_o) - loss_m) < 1e-5, f"step {i}"
    # Eval sums agree too (forward-only microbatch pipeline vs the
    # oracle's eval body on the SAME post-training states).
    x, y, w = batches[0]
    sums_m = runner.eval_pass(x, y, w)
    merged = mpmd.merge_stage_states(runner.states, template=full)
    host = merged.replace(
        params=jax.tree.map(jnp.asarray, merged.params)
    )
    sums_o = jax.jit(_eval_body)(host, x, y, w)
    for a, b in zip(sums_m, sums_o):
        assert abs(float(a) - float(b)) < 1e-4
    # Per-stage step counters advanced together.
    assert all(
        int(jax.device_get(s.step)) == len(batches)
        for s in runner.states
    )


def test_runner_gpipe_schedule_same_math():
    """The gpipe op order on the MPMD substrate computes the identical
    update (schedules reorder execution, not math)."""
    cfg = _small_cfg()
    full = _full_state(cfg)
    r1 = _runner(cfg, full)
    cfg2 = _small_cfg()
    cfg2.mpmd.schedule = "gpipe"
    r2 = _runner(cfg2, full)
    for x, y, w in _batches(3):
        l1, _ = r1.train_step(x, y, w)
        l2, _ = r2.train_step(x, y, w)
        assert l1 == pytest.approx(l2, abs=1e-7)


def test_step_report_attributes_phases():
    cfg = _small_cfg()
    runner = _runner(cfg)
    x, y, w = _batches(1)[0]
    _loss, wall = runner.train_step(x, y, w)
    rep = runner.step_bubble(wall)
    assert rep["schedule"] == "1f1b"
    assert 0.0 <= rep["step_bubble"] <= 1.0
    assert 0.0 <= rep["steady_bubble"] <= 1.0
    assert rep["analytic_bubble"] == pytest.approx(
        mpmd.analytic_bubble(2, 4)
    )
    stages = rep["stages"]
    assert len(stages) == 2
    # Stage 0 warms up (fill > 0); the LAST stage has no fill by
    # construction; everyone has steady work; busy decomposes into the
    # three phases.
    assert stages[0]["fill_s"] > 0
    assert stages[1]["fill_s"] == 0
    for s in stages:
        assert s["steady_s"] > 0
        assert s["busy_s"] >= (
            s["fill_s"] + s["steady_s"] + s["drain_s"]
        ) - 1e-9


def test_transfer_timeout_is_loud():
    ch = mpmd.QueueChannel()
    with pytest.raises(mpmd.MpmdTransferTimeout):
        ch.recv(timeout=0.05)


# ----------------------------------------------------------------------
# Cross-topology resume (satellite 3): MPMD-saved per-stage checkpoints
# restored by the SPMD trainer (and vice versa), bitwise; untileable
# stage maps refuse loudly.


def _save_mpmd_checkpoint(cfg, runner, epochs_completed=1):
    for k in range(runner.spec.n_stages):
        mt.stage_checkpointer(cfg.data.models_dir, k).save(
            runner.states[k],
            {
                "epochs_completed": epochs_completed,
                "target_epochs": epochs_completed,
                "family": cfg.model.name,
                "stage": k,
            },
        )
    mt.write_manifest(cfg.data.models_dir, {
        "version": 1,
        "n_stages": runner.spec.n_stages,
        "device_counts": list(runner.spec.device_counts),
        "schedule": runner.spec.schedule,
        "n_microbatches": runner.spec.n_microbatches,
        "family": cfg.model.name,
        "n_layers": cfg.model.n_layers,
        "epochs_completed": epochs_completed,
    })


def test_mpmd_checkpoint_adopted_by_spmd_bitwise(tmp_path):
    from dct_tpu.checkpoint.manager import TrainStateCheckpointer

    cfg = _small_cfg(tmp_path)
    full = _full_state(cfg)
    runner = _runner(cfg, full)
    for x, y, w in _batches(2):
        runner.train_step(x, y, w)
    _save_mpmd_checkpoint(cfg, runner)
    in_memory = mpmd.merge_stage_states(runner.states, template=full)

    meta = mt.adopt_mpmd_checkpoint(cfg.data.models_dir, full)
    assert meta["epochs_completed"] == 1
    spmd = TrainStateCheckpointer(
        os.path.join(cfg.data.models_dir, "train_state", "p0")
    )
    restored = spmd.restore(full)
    _assert_trees_equal(in_memory.params, restored.params)
    _assert_trees_equal(in_memory.opt_state, restored.opt_state)
    assert int(np.asarray(restored.step)) == 2


def test_spmd_checkpoint_splits_into_mpmd_bitwise(tmp_path):
    from dct_tpu.checkpoint.manager import TrainStateCheckpointer
    from dct_tpu.train.steps import _train_body

    cfg = _small_cfg(tmp_path)
    full = _full_state(cfg)
    step = jax.jit(_train_body)
    for x, y, w in _batches(2):
        full, _loss, _ = step(full, x, y, w)
    spmd = TrainStateCheckpointer(
        os.path.join(cfg.data.models_dir, "train_state", "p0")
    )
    spmd.save(full, {"epochs_completed": 1, "target_epochs": 1})

    template = _full_state(cfg)
    restored, meta = mt._restore_from_spmd(cfg.data.models_dir, template)
    assert meta["epochs_completed"] == 1
    for k in range(2):
        _assert_trees_equal(
            mpmd.split_state(restored, k, 2).params,
            mpmd.split_state(full, k, 2).params,
        )


def test_adopt_refuses_untileable_stage_map(tmp_path):
    cfg = _small_cfg(tmp_path)
    runner = _runner(cfg)
    x, y, w = _batches(1)[0]
    runner.train_step(x, y, w)
    _save_mpmd_checkpoint(cfg, runner)
    # Doctor the manifest to a stage count the template cannot tile.
    man = mt.read_manifest(cfg.data.models_dir)
    man["n_stages"] = 4
    mt.write_manifest(cfg.data.models_dir, man)
    with pytest.raises(mpmd.MpmdSpecError, match="untileable"):
        mt.adopt_mpmd_checkpoint(
            cfg.data.models_dir, _full_state(cfg)
        )


def test_mpmd_trainer_fit_resume_and_pivot(tmp_path, monkeypatch):
    """End-to-end MpmdTrainer.fit over a real processed dataset: fresh
    fit -> per-stage resume extends the trajectory -> the step_report
    events land -> a fresh SPMD-side adoption resumes the same
    trajectory (mpmd.pivot on the log)."""
    from dct_tpu.data.synthetic import generate_weather_csv
    from dct_tpu.etl.preprocess import preprocess_csv_to_parquet

    ev_dir = tmp_path / "events"
    monkeypatch.setenv("DCT_EVENTS_DIR", str(ev_dir))
    raw = str(tmp_path / "weather.csv")
    generate_weather_csv(raw, rows=300, seed=7)
    proc = str(tmp_path / "processed")
    preprocess_csv_to_parquet(raw, proc)

    from dct_tpu.observability import events as _events
    from dct_tpu.observability.buffered import flush_all_appenders

    cfg = _small_cfg(tmp_path)
    cfg.data.processed_dir = proc
    cfg.obs.events_dir = str(ev_dir)
    cfg.obs.metrics_dir = str(tmp_path / "metrics")
    cfg.train.epochs = 2
    res = mt.MpmdTrainer(cfg).fit()
    # The default EventLog batches appends (DCT_TELEMETRY_FLUSH_S);
    # make the records durable before reading them back.
    _events.get_default().flush()
    flush_all_appenders()
    assert len(res.train_losses) == 2
    assert res.epochs_completed == 2
    assert 0.0 <= res.bubble["steady_bubble"] <= 1.0
    assert mt.mpmd_checkpoint_present(cfg.data.models_dir)
    # The metrics plane got a final snapshot with the bubble gauges.
    snaps = list((tmp_path / "metrics").glob("*.metrics.json"))
    assert snaps
    snap = json.loads(snaps[0].read_text())
    blob = json.dumps(snap)
    assert "dct_mpmd_bubble_fraction" in blob
    assert "dct_mpmd_stage_phase_seconds" in blob

    cfg.train.resume = True
    cfg.train.epochs = 1
    res2 = mt.MpmdTrainer(cfg).fit()
    assert res2.epochs_completed == 3
    # The trajectory extended: the resumed epoch improves on the first
    # fit's start.
    assert res2.train_losses[-1] < res.train_losses[0]

    _events.get_default().flush()
    events = [
        json.loads(line)
        for line in open(ev_dir / "events.jsonl")
    ]
    reports = [e for e in events if e["event"] == "mpmd.step_report"]
    assert len(reports) == 3
    assert all("stages" in r for r in reports)

    # The SPMD trainer adopts the per-stage files on resume.
    template = _full_state(cfg)
    mt.adopt_mpmd_checkpoint(cfg.data.models_dir, template)
    _events.get_default().flush()
    events = [
        json.loads(line)
        for line in open(ev_dir / "events.jsonl")
    ]
    pivots = [e for e in events if e["event"] == "mpmd.pivot"]
    assert any(p.get("direction") == "mpmd_to_spmd" for p in pivots)

    # And the inspector renders the MPMD section from the same log.
    from dct_tpu.observability.inspect import build_report

    report = build_report(events, [], [], None, None)
    assert "MPMD pipeline" in report
    assert "steady=" in report


def test_resume_refuses_optimizer_change_and_torn_set(
    tmp_path, processed_dir
):
    """The Trainer's cross-optimizer resume refusal applies to the MPMD
    paths (opt_state trees can be structurally isomorphic across
    configs), and a manifest whose stage files are incomplete is a TORN
    set — loud, never a silent fresh start over surviving progress."""
    import shutil

    cfg = _small_cfg(tmp_path)
    cfg.data.processed_dir = processed_dir
    cfg.train.epochs = 1
    mt.MpmdTrainer(cfg).fit()

    cfg2 = _small_cfg(tmp_path)
    cfg2.data.processed_dir = processed_dir
    cfg2.train.resume = True
    cfg2.train.optimizer = "sgd"
    cfg2.train.momentum = 0.9
    with pytest.raises(RuntimeError, match="Resume refused"):
        mt.MpmdTrainer(cfg2).fit()

    shutil.rmtree(
        os.path.join(
            mt.mpmd_state_root(cfg.data.models_dir), "stage1"
        )
    )
    cfg.train.resume = True
    with pytest.raises(FileNotFoundError, match="torn"):
        mt.MpmdTrainer(cfg).fit()


def test_per_stage_aot_identity_and_warm_hit(tmp_path, monkeypatch):
    """Per-stage programs key into the AOT store with stage id + slice
    topology in the identity: a cold build misses (publishing per-stage
    artifacts with DISTINCT names), a warm rebuild hits every stage."""
    monkeypatch.setenv("DCT_COMPILE_CACHE", "auto")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    from dct_tpu import compilecache as _cc

    cfg = _small_cfg(tmp_path)
    spec = cfg.mpmd.to_spec(n_devices=jax.device_count())
    meshes = mpmd.carve_stage_meshes(spec.device_counts, model=1)
    full = _full_state(cfg)
    fns = mt.build_stage_fns(cfg.model, INPUT_DIM, compute_dtype=jnp.float32)

    def build_runner():
        stores = [
            _cc.store_from_env(
                str(tmp_path / "aot"), family=cfg.model.name,
                config_hash="deadbeef", mesh="data1_model1",
                extra={"mpmd_stage": k, "mpmd_slice": "1x1"},
            )
            for k in range(2)
        ]
        states = [
            mt.shard_stage_state(
                mpmd.split_state(full, k, 2), meshes[k]
            )
            for k in range(2)
        ]
        progs = [
            mpmd.make_stage_programs(k, 2, fns, store=stores[k])
            for k in range(2)
        ]
        return mpmd.MpmdRunner(spec, states, progs, meshes), stores

    x, y, w = _batches(1)[0]
    r1, stores1 = build_runner()
    r1.train_step(x, y, w)
    assert all(
        v == "miss" for st in stores1 for v in st.states.values()
    )
    # Stage identities partition the artifact namespace.
    names = os.listdir(tmp_path / "aot")
    assert any("mpmd_fwd_s0" in n for n in names)
    assert any("mpmd_fwd_s1" in n for n in names)
    assert stores1[0]._identity_key() != stores1[1]._identity_key()

    r2, stores2 = build_runner()
    r2.train_step(x, y, w)
    assert all(
        v == "hit" for st in stores2 for v in st.states.values()
    ), {k: v for st in stores2 for k, v in st.states.items()}


# ----------------------------------------------------------------------
# Transfer plane.


def test_socket_transfer_roundtrip_and_timeout():
    import socket as _socket
    import threading

    a, b = _socket.socketpair()
    ca, cb = (
        mpmd_transfer.SocketChannel(a),
        mpmd_transfer.SocketChannel(b),
    )
    arr = np.arange(24, dtype=np.float32).reshape(2, 3, 4)

    def send():
        ca.send(arr)

    t = threading.Thread(target=send)
    t.start()
    got = cb.recv(timeout=5.0)
    t.join()
    np.testing.assert_array_equal(got, arr)
    assert got.dtype == np.float32
    # An empty link times out loudly, never hangs.
    with pytest.raises(mpmd.MpmdTransferTimeout):
        cb.recv(timeout=0.1)
    ca.close()
    cb.close()


def test_stage_links_establish_and_carry(tmp_path):
    """A 2-stage link ring over loopback: activations down, gradients
    back up, in either start order."""
    import threading

    port_base = 29710
    results = {}

    def stage(k):
        links = mpmd_transfer.connect_stage_links(
            k, 2, port_base=port_base, timeout=20.0
        )
        try:
            if k == 0:
                links["act_out"].send(np.full((2, 2), 7.0, np.float32))
                results["grad"] = links["grad_in"].recv(10.0)
            else:
                act = links["act_in"].recv(10.0)
                links["grad_out"].send(act * 2.0)
        finally:
            mpmd_transfer.close_links(links)

    threads = [
        threading.Thread(target=stage, args=(k,)) for k in (1, 0)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    np.testing.assert_array_equal(
        results["grad"], np.full((2, 2), 14.0, np.float32)
    )


@pytest.mark.slow
def test_two_process_worker_matches_in_process_bitwise(tmp_path):
    """The multi-process deployment (one process per stage, socket
    transfers) computes the IDENTICAL loss trajectory as the in-process
    thread-per-stage trainer — same schedule, same programs, different
    transport."""
    import subprocess
    import sys

    from dct_tpu.data.synthetic import generate_weather_csv
    from dct_tpu.etl.preprocess import preprocess_csv_to_parquet

    raw = str(tmp_path / "weather.csv")
    generate_weather_csv(raw, rows=300, seed=7)
    proc = str(tmp_path / "processed")
    preprocess_csv_to_parquet(raw, proc)

    # In-process reference.
    cfg = _small_cfg(tmp_path)
    cfg.data.processed_dir = proc
    cfg.data.models_dir = str(tmp_path / "models_inproc")
    cfg.train.epochs = 2
    res = mt.MpmdTrainer(cfg).fit()

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        DCT_PROCESSED_DIR=proc,
        DCT_MODELS_DIR=str(tmp_path / "models_proc"),
        DCT_EVENTS_DIR=str(tmp_path / "events_proc"),
        DCT_HEARTBEAT_DIR=str(tmp_path / "hb"),
        DCT_MODEL="weather_transformer_pp", DCT_DROPOUT="0",
        DCT_SEQ_LEN="8", DCT_D_MODEL="16", DCT_N_HEADS="2",
        DCT_N_LAYERS="2", DCT_D_FF="32", DCT_N_STAGES="2",
        DCT_BF16_COMPUTE="0", DCT_EPOCHS="2", DCT_BATCH_SIZE="8",
        DCT_MPMD_STAGES="1,1", DCT_MPMD_MICROBATCHES="4",
        DCT_MPMD_PORT_BASE="29720",
        DCT_MPMD_TRANSFER_TIMEOUT_S="60",
    )
    env.pop("XLA_FLAGS", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "dct_tpu.train.mpmd_worker"],
            env=dict(env, DCT_MPMD_STAGE_ID=str(k)), cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for k in range(2)
    ]
    errs = []
    for p in procs:
        _out, err = p.communicate(timeout=240)
        errs.append(err)
    assert [p.returncode for p in procs] == [0, 0], errs
    events = [
        json.loads(line)
        for line in open(tmp_path / "events_proc" / "events.jsonl")
    ]
    losses = [
        e["train_loss"] for e in events
        if e["event"] == "mpmd.step_report"
    ]
    assert losses == pytest.approx(res.train_losses, abs=0.0)

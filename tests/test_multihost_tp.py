"""Tensor parallelism SPANNING processes: the checkpoint/loader path that
single-host rigs cannot exercise.

Two real jax.distributed CPU processes, one device each, mesh
(data=1, model=2): transformer params shard across the two hosts, the
batch replicates across them (process_data_block gives both the same
block), and the coordinator's checkpoint write must assemble the
cross-process params with an allgather. Metrics must match a
single-process run of the same config (parallelism is layout, not math).
"""

import glob
import json
import os
import sys

import numpy as np
import pytest

from dct_tpu.config import MeshConfig
from dct_tpu.launch.launcher import LocalProcessLauncher
from dct_tpu.parallel.mesh import make_mesh, process_data_block


def test_process_data_block_single_process():
    mesh = make_mesh(MeshConfig(data=2, model=2, seq=2))
    # One process owns everything -> one block.
    assert process_data_block(mesh) == (1, 0)


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def base_training_env(processed_dir, tmp_path, models_sub: str,
                      runs_sub: str, env_overrides: dict) -> dict:
    """The shared small-model CPU env for spanning-processes launches;
    ``env_overrides`` carries the DCT_* config distinguishing the
    parallelism under test."""
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "DCT_PROCESSED_DIR": processed_dir,
        "DCT_MODELS_DIR": str(tmp_path / models_sub),
        "DCT_TRACKING_DIR": str(tmp_path / runs_sub),
        "DCT_SEQ_LEN": "8",
        "DCT_D_MODEL": "16",
        "DCT_N_HEADS": "2",
        "DCT_D_FF": "32",
        "DCT_EPOCHS": "1",
        "DCT_BATCH_SIZE": "16",
        "DCT_BF16_COMPUTE": "0",
        "DCT_MESH_DATA": "1",
        "DCT_RESUME": "0",
        **env_overrides,
    }


def launch_training(processed_dir, tmp_path, *, world_size: int, port: int,
                    models_sub: str, runs_sub: str, env_overrides: dict):
    """Launch ``world_size`` real jax.distributed CPU processes (one
    device each) running jobs/train_tpu.py, and return the merged final
    metrics of the newest tracking run. Shared by every
    spanning-processes test."""
    env = base_training_env(
        processed_dir, tmp_path, models_sub, runs_sub, env_overrides
    )
    launcher = LocalProcessLauncher(
        coordinator_port=port, stagger_seconds=1.0, timeout=300
    )
    results = launcher.launch(
        [sys.executable, os.path.join(_REPO, "jobs", "train_tpu.py")],
        world_size=world_size,
        env=env,
    )
    assert LocalProcessLauncher.all_succeeded(results), results
    runs = sorted(
        glob.glob(
            str(tmp_path / runs_sub / "weather_forecasting" / "*" / "metrics.jsonl")
        ),
        key=os.path.getmtime,
    )
    assert runs, "no tracking run written"
    last = {}
    with open(runs[-1]) as f:
        for line in f:
            last.update(json.loads(line))
    return last


@pytest.mark.slow
def test_tp_across_processes_trains_and_checkpoints(processed_dir, tmp_path):
    def run(world_size, mesh_model, models_sub, runs_sub, *, epochs=1,
            resume=False):
        # One device per process: the model axis must span PROCESSES.
        return launch_training(
            processed_dir, tmp_path, world_size=world_size, port=29533,
            models_sub=models_sub, runs_sub=runs_sub,
            env_overrides={
                "DCT_MODEL": "weather_transformer",
                "DCT_N_LAYERS": "1",
                "DCT_MESH_MODEL": str(mesh_model),
                "DCT_EPOCHS": str(epochs),
                "DCT_RESUME": "1" if resume else "0",
            },
        )

    m_tp = run(2, 2, "m_tp", "r_tp")
    m_ref = run(1, 1, "m_ref", "r_ref")

    # Same global batch (data axis 1 in both runs), same seeds: TP across
    # hosts must follow the single-process trajectory to fp tolerance.
    assert abs(m_tp["val_loss"] - m_ref["val_loss"]) < 1e-3, (m_tp, m_ref)

    # Coordinator assembled the cross-host params into a deployable ckpt.
    best = glob.glob(str(tmp_path / "m_tp" / "weather-best-*.ckpt"))
    assert best
    from dct_tpu.checkpoint.manager import load_checkpoint

    params, meta = load_checkpoint(best[0])
    assert meta["model"] == "weather_transformer"
    # The qkv kernel must be the FULL [d_model, 3*d_model] matrix, not one
    # process's model-axis shard.
    qkv = params["params"]["block_0"]["attn"]["qkv_proj"]["kernel"]
    assert qkv.shape == (16, 48)

    # Resume on the cross-process topology: each rank reassembles its
    # shard-saved train state (params + Adam moments) onto its devices and
    # continues for the second epoch.
    m_resume = run(2, 2, "m_tp", "r_tp", epochs=2, resume=True)
    assert "val_loss" in m_resume
    # Two tracking runs now: the original and the resumed epoch.
    runs = glob.glob(
        str(tmp_path / "r_tp" / "weather_forecasting" / "*" / "metrics.jsonl")
    )
    assert len(runs) == 2


@pytest.mark.slow
def test_ep_all_to_all_across_processes(processed_dir, tmp_path):
    """Expert parallelism SPANNING processes: the sorted dispatch engine's
    lax.all_to_all crosses a real process boundary (2 jax.distributed CPU
    procs, one device each, experts split over the model axis), and the
    loss trajectory matches the single-process sorted engine (ample
    capacity -> no drops -> parallelism is layout, not math)."""
    def run(world_size, mesh_model, models_sub, runs_sub):
        return launch_training(
            processed_dir, tmp_path, world_size=world_size, port=29534,
            models_sub=models_sub, runs_sub=runs_sub,
            env_overrides={
                "DCT_MODEL": "weather_moe",
                "DCT_N_LAYERS": "1",
                "DCT_N_EXPERTS": "4",
                "DCT_MOE_DISPATCH": "sorted",
                "DCT_CAPACITY_FACTOR": "8.0",
                "DCT_MESH_MODEL": str(mesh_model),
            },
        )

    m_ep = run(2, 2, "m_ep", "r_ep")
    m_ref = run(1, 1, "m_ep_ref", "r_ep_ref")
    assert abs(m_ep["val_loss"] - m_ref["val_loss"]) < 1e-3, (m_ep, m_ref)


@pytest.mark.slow
def test_striped_causal_ring_across_processes(processed_dir, tmp_path):
    """Striped (zigzag) causal ring attention SPANNING processes: 2
    jax.distributed CPU procs (one device each), mesh seq=2, causal
    family with DCT_FLASH=interpret — so the striped flash ring (static
    sequence permutation, per-step lax.cond visibility cases, ppermute KV
    hops) crosses a real process boundary. Loss must match the
    single-process run (parallelism is layout, not math)."""
    def run(world_size, seq_par, models_sub, runs_sub):
        return launch_training(
            processed_dir, tmp_path, world_size=world_size, port=29536,
            models_sub=models_sub, runs_sub=runs_sub,
            env_overrides={
                "DCT_MODEL": "weather_transformer_causal",
                "DCT_N_LAYERS": "1",
                "DCT_FLASH": "interpret",
                "DCT_MESH_SEQ": str(seq_par),
                "DCT_MESH_MODEL": "1",
            },
        )

    m_sp = run(2, 2, "m_sp", "r_sp")
    m_ref = run(1, 1, "m_sp_ref", "r_sp_ref")
    assert abs(m_sp["val_loss"] - m_ref["val_loss"]) < 1e-3, (m_sp, m_ref)


@pytest.mark.slow
def test_a2a_sp_across_processes(processed_dir, tmp_path):
    """The all-to-all (Ulysses) SP engine SPANNING processes: mesh seq=2
    over 2 jax.distributed CPU procs — the head<->seq lax.all_to_all
    exchange crosses a real process boundary, causal family. Loss must
    match the single-process run."""
    def run(world_size, seq_par, models_sub, runs_sub):
        return launch_training(
            processed_dir, tmp_path, world_size=world_size, port=29543,
            models_sub=models_sub, runs_sub=runs_sub,
            env_overrides={
                "DCT_MODEL": "weather_transformer_causal",
                "DCT_N_LAYERS": "1",
                "DCT_SP_ENGINE": "a2a",
                "DCT_MESH_SEQ": str(seq_par),
                "DCT_MESH_MODEL": "1",
            },
        )

    m_sp = run(2, 2, "m_a2a", "r_a2a")
    m_ref = run(1, 1, "m_a2a_ref", "r_a2a_ref")
    assert abs(m_sp["val_loss"] - m_ref["val_loss"]) < 1e-3, (m_sp, m_ref)


@pytest.mark.slow
def test_windowed_gqa_rope_ring_across_processes(processed_dir, tmp_path):
    """The round-4 attention stack COMPOSED across a real process
    boundary: sliding window (truncated ring hops) x grouped KV shards
    (GQA — the rotated ring payload stays at n_kv_heads) x rotary
    embeddings, causal family over mesh seq=2 spanning 2 jax.distributed
    CPU procs on the default (ring) engine. Loss must match the
    single-process run (all three features are layout/structure, not
    batch-dependent math)."""
    def run(world_size, seq_par, models_sub, runs_sub):
        return launch_training(
            processed_dir, tmp_path, world_size=world_size, port=29545,
            models_sub=models_sub, runs_sub=runs_sub,
            env_overrides={
                "DCT_MODEL": "weather_transformer_causal",
                "DCT_N_LAYERS": "1",
                "DCT_N_HEADS": "4",
                "DCT_N_KV_HEADS": "2",
                "DCT_POS_EMBED": "rope",
                "DCT_ATTN_WINDOW": "3",
                "DCT_MESH_SEQ": str(seq_par),
                "DCT_MESH_MODEL": "1",
            },
        )

    m_sp = run(2, 2, "m_wgr", "r_wgr")
    m_ref = run(1, 1, "m_wgr_ref", "r_wgr_ref")
    assert abs(m_sp["val_loss"] - m_ref["val_loss"]) < 1e-3, (m_sp, m_ref)


@pytest.mark.slow
def test_zero1_across_processes(processed_dir, tmp_path):
    """ZeRO-1 weight-update sharding SPANNING processes: the data axis
    covers 2 jax.distributed CPU procs, Adam moments shard P('data') —
    XLA's reduce-scatter/all-gather pair crosses a real process boundary
    — and the trajectory matches the unsharded single-process run (the
    optimizer partitioning is layout, not math). Resume then reassembles
    each rank's moment shards."""

    def run(world_size, shard_opt, models_sub, runs_sub, *, epochs=1,
            resume=False):
        return launch_training(
            processed_dir, tmp_path, world_size=world_size, port=29537,
            models_sub=models_sub, runs_sub=runs_sub,
            env_overrides={
                "DCT_MODEL": "weather_mlp",
                "DCT_MESH_DATA": "-1",
                "DCT_SHARD_OPT_STATE": "1" if shard_opt else "0",
                "DCT_EPOCHS": str(epochs),
                "DCT_RESUME": "1" if resume else "0",
                # batch_size is per data shard: keep the GLOBAL batch (16)
                # equal across world sizes so trajectories compare.
                "DCT_BATCH_SIZE": str(16 // world_size),
            },
        )

    m_z = run(2, True, "m_z", "r_z")
    m_ref = run(1, False, "m_z_ref", "r_z_ref")
    assert abs(m_z["val_loss"] - m_ref["val_loss"]) < 1e-3, (m_z, m_ref)

    # Resume on the sharded topology: each rank restores its own moment
    # shards (offset-keyed) and extends the run with finite metrics (a
    # structurally-restored-but-corrupt state would train to nan).
    m_resume = run(2, True, "m_z", "r_z", epochs=1, resume=True)
    assert np.isfinite(m_resume["val_loss"]), m_resume
    # Continuing from a trained state must not be worse than the first
    # epoch's result by much (a wrong-moment restore diverges sharply).
    assert m_resume["val_loss"] < m_z["val_loss"] + 0.1, (m_resume, m_z)


@pytest.mark.slow
def test_fsdp_across_processes(processed_dir, tmp_path):
    """FSDP/ZeRO-3 SPANNING processes: params AND Adam moments shard
    P('data') across 2 jax.distributed CPU procs — each rank stores half
    of every 64-wide weight, XLA all-gathers on use across the process
    boundary — with the trajectory matching the unsharded single-process
    run, then a resume on the sharded topology."""

    def run(world_size, fsdp, models_sub, runs_sub, *, epochs=1,
            resume=False):
        return launch_training(
            processed_dir, tmp_path, world_size=world_size, port=29541,
            models_sub=models_sub, runs_sub=runs_sub,
            env_overrides={
                "DCT_MODEL": "weather_mlp",
                "DCT_MESH_DATA": "-1",
                "DCT_SHARD_PARAMS": "1" if fsdp else "0",
                "DCT_EPOCHS": str(epochs),
                "DCT_RESUME": "1" if resume else "0",
                # Same GLOBAL batch (16) across world sizes.
                "DCT_BATCH_SIZE": str(16 // world_size),
            },
        )

    m_f = run(2, True, "m_f", "r_f")
    m_ref = run(1, False, "m_f_ref", "r_f_ref")
    assert abs(m_f["val_loss"] - m_ref["val_loss"]) < 1e-3, (m_f, m_ref)

    # Resume restores each rank's param/moment shards in the declared
    # layout and keeps training finite and non-divergent.
    m_resume = run(2, True, "m_f", "r_f", epochs=1, resume=True)
    assert np.isfinite(m_resume["val_loss"]), m_resume
    assert m_resume["val_loss"] < m_f["val_loss"] + 0.1, (m_resume, m_f)


@pytest.mark.slow
def test_tp_zero1_composed_across_processes(processed_dir, tmp_path):
    """TP x ZeRO-1 composed over 4 real processes (mesh data=2 x
    model=2): transformer params shard over ``model`` ACROSS hosts while
    the replicated leaves' Adam moments shard over ``data`` across the
    other host pair — both rules at once, trajectory matching the
    unsharded single-process run."""

    def run(world_size, mesh_data, mesh_model, shard_opt, models_sub,
            runs_sub):
        return launch_training(
            processed_dir, tmp_path, world_size=world_size, port=29539,
            models_sub=models_sub, runs_sub=runs_sub,
            env_overrides={
                "DCT_MODEL": "weather_transformer",
                "DCT_N_LAYERS": "1",
                "DCT_MESH_DATA": str(mesh_data),
                "DCT_MESH_MODEL": str(mesh_model),
                "DCT_SHARD_OPT_STATE": "1" if shard_opt else "0",
                # Same GLOBAL batch (16) at any data-axis width.
                "DCT_BATCH_SIZE": str(16 // mesh_data),
            },
        )

    m_tz = run(4, 2, 2, True, "m_tz", "r_tz")
    m_ref = run(1, 1, 1, False, "m_tz_ref", "r_tz_ref")
    assert abs(m_tz["val_loss"] - m_ref["val_loss"]) < 1e-3, (m_tz, m_ref)


@pytest.mark.slow
def test_pp_tp_composed_across_processes(processed_dir, tmp_path):
    """PP x TP composed over 4 real processes (mesh pipe=2 x model=2):
    GPipe ppermute hops cross one process boundary while the stages'
    megatron-split kernels all-reduce across the other — trajectory
    matching the single-process sequential stack."""

    def run(world_size, pipe, model, models_sub, runs_sub):
        return launch_training(
            processed_dir, tmp_path, world_size=world_size, port=29540,
            models_sub=models_sub, runs_sub=runs_sub,
            env_overrides={
                "DCT_MODEL": "weather_transformer_pp",
                "DCT_N_LAYERS": "2",
                "DCT_N_STAGES": "2",
                "DCT_MESH_PIPE": str(pipe),
                "DCT_MESH_MODEL": str(model),
            },
        )

    m_pt = run(4, 2, 2, "m_pt", "r_pt")
    m_ref = run(1, 1, 1, "m_pt_ref", "r_pt_ref")
    assert abs(m_pt["val_loss"] - m_ref["val_loss"]) < 1e-3, (m_pt, m_ref)


@pytest.mark.slow
def test_sigkill_rank_then_resume(processed_dir, tmp_path):
    """Crash recovery end to end: SIGKILL one rank MID-TRAINING (after at
    least one epoch's resume state landed), assert the fail-fast launcher
    reaps the survivor and reports failure, then a resume launch
    continues from the rotated state instead of restarting from scratch."""
    import json as _json
    import signal
    import subprocess
    import threading
    import time

    env = base_training_env(
        processed_dir, tmp_path, "m_kill", "r_kill",
        {
            # Long enough that the kill lands mid-run, short enough that
            # the resume (which finishes to this interrupted target)
            # stays fast.
            "DCT_EPOCHS": "50",
            "DCT_BATCH_SIZE": "8",
            "DCT_MESH_DATA": "-1",
            "DCT_RESUME": "1",
        },
    )
    launcher = LocalProcessLauncher(
        coordinator_port=29538, stagger_seconds=1.0, timeout=300
    )
    results = []
    # train_tpu.py reads config from env only, so a marker argv scopes
    # pgrep to THIS launch (never another test's or machine tenant's
    # ranks). No leading dashes: pgrep would parse them as options.
    marker = "sigkill_resume_test_marker"

    def run():
        results.extend(
            launcher.launch(
                [sys.executable, os.path.join(_REPO, "jobs", "train_tpu.py"),
                 marker],
                world_size=2,
                env=env,
            )
        )

    t = threading.Thread(target=run)
    t.start()
    # Wait until rank 0's first resume state is PUBLISHED (not just a
    # .next in progress) so the kill lands mid-training with a
    # restorable checkpoint on disk.
    state_npz = (
        tmp_path / "m_kill" / "train_state" / "p0" / "state" / "state.npz"
    )
    deadline = time.time() + 240
    while time.time() < deadline and not state_npz.exists():
        time.sleep(0.5)
    assert state_npz.exists(), "no resume state appeared before deadline"
    pids = subprocess.run(
        ["pgrep", "-f", marker], capture_output=True, text=True
    ).stdout.split()
    assert pids, "no training rank processes found to kill"
    os.kill(int(pids[0]), signal.SIGKILL)
    t.join(timeout=240)
    assert not t.is_alive(), "launcher did not return after rank kill"
    assert not LocalProcessLauncher.all_succeeded(results), results
    # Fail-fast must have reaped the survivor too.
    leftover = subprocess.run(
        ["pgrep", "-f", marker], capture_output=True, text=True
    ).stdout.split()
    assert not leftover, f"surviving ranks not reaped: {leftover}"

    completed = _json.load(
        open(tmp_path / "m_kill" / "train_state" / "p0" / "state" / "meta.json")
    )["epochs_completed"]
    assert completed >= 1

    # Resume: finish a small extension from the rotated state.
    m = launch_training(
        processed_dir, tmp_path, world_size=2, port=29538,
        models_sub="m_kill", runs_sub="r_kill",
        env_overrides={"DCT_EPOCHS": "2", "DCT_RESUME": "1",
                       "DCT_MESH_DATA": "-1", "DCT_BATCH_SIZE": "8"},
    )
    assert np.isfinite(m["val_loss"]), m


@pytest.mark.slow
def test_pp_ppermute_across_processes(processed_dir, tmp_path):
    """Pipeline parallelism SPANNING processes: stages sharded P('pipe')
    across 2 jax.distributed CPU procs (one device each); the GPipe
    ppermute hops cross a real process boundary and the loss trajectory
    matches the single-process sequential stack."""
    def run(world_size, pipe, models_sub, runs_sub):
        return launch_training(
            processed_dir, tmp_path, world_size=world_size, port=29535,
            models_sub=models_sub, runs_sub=runs_sub,
            env_overrides={
                "DCT_MODEL": "weather_transformer_pp",
                "DCT_N_LAYERS": "2",
                "DCT_N_STAGES": "2",
                "DCT_MESH_PIPE": str(pipe),
                "DCT_MESH_MODEL": "1",
            },
        )

    m_pp = run(2, 2, "m_pp", "r_pp")
    m_ref = run(1, 1, "m_pp_ref", "r_pp_ref")
    assert abs(m_pp["val_loss"] - m_ref["val_loss"]) < 1e-3, (m_pp, m_ref)

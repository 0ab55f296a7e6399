"""Launcher tests: generated launch-script semantics and the real
multi-process SPMD rig (the reference's two-container test bed, SURVEY §4,
replaced by two local jax.distributed processes)."""

import os
import sys

import pytest

from dct_tpu.launch.launcher import (
    LocalProcessLauncher,
    build_healthcheck_script,
    build_spmd_launch_script,
    build_zombie_cleanup_script,
)

HOSTS = ["tpu-vm-0", "tpu-vm-1"]


def test_launch_script_env_contract():
    script = build_spmd_launch_script(HOSTS, "python3 jobs/train_tpu.py")
    # Coordinator is host 0 on every rank; ranks numbered in order.
    assert script.count("MASTER_ADDR=tpu-vm-0") == 2
    assert "NODE_RANK=0" in script and "NODE_RANK=1" in script
    assert script.count("WORLD_SIZE=2") == 2
    assert "MASTER_PORT=29500" in script
    # Staggered start after rank 0 only.
    assert script.count("sleep 5") == 1
    # Fail-fast join + exit-code conjunction over both ranks.
    assert 'wait "$PID0"' in script and 'wait "$PID1"' in script
    assert "terminating remaining ranks" in script
    assert '[ "$RC0" -eq 0 ] && [ "$RC1" -eq 0 ]' in script
    assert "exit 1" in script


def test_launch_script_docker_exec_template():
    script = build_spmd_launch_script(
        ["pytorch-master", "pytorch-worker"],
        "python3 train.py",
        exec_template="docker exec {host} {cmd}",
    )
    assert "docker exec pytorch-master" in script
    assert "docker exec pytorch-worker" in script


def test_zombie_cleanup_script():
    script = build_zombie_cleanup_script(HOSTS, pattern="train_tpu.py")
    assert script.count("pkill -9 -f") == 2
    assert "|| true" in script
    assert "sleep 2" in script


def test_healthcheck_script():
    script = build_healthcheck_script(HOSTS)
    assert script.count("import jax") == 2


def test_healthcheck_fails_on_bad_host():
    """A failed host check must fail the whole task — bash returns the
    LAST command's status, so without set -e the trailing success banner
    would mask the failure."""
    import subprocess

    script = build_healthcheck_script(
        ["h0", "h1"], exec_template="bash -c {cmd}", check_command="false"
    )
    proc = subprocess.run(["bash", "-c", script], capture_output=True, text=True)
    assert proc.returncode != 0
    assert "All hosts healthy" not in proc.stdout


def test_ssh_reparse_quoting(tmp_path):
    """ssh joins its command argv with spaces and the remote shell re-parses
    the string — flattening exactly one quoting level. Simulate that with a
    fake ssh and assert the payload ACTUALLY runs on both 'hosts' (a
    quoting bug here makes the launch a silent no-op that still exits 0)."""
    import subprocess

    marker = tmp_path / "ran"
    fake_ssh = tmp_path / "fake_ssh"
    fake_ssh.write_text('#!/bin/bash\nshift\nexec bash -c "$*"\n')
    fake_ssh.chmod(0o755)
    script = build_spmd_launch_script(
        ["h0", "h1"],
        f"sh -c 'echo rank=$NODE_RANK >> {marker}'",
        exec_template=f"{fake_ssh} {{host}} {{cmd}}",
        stagger_seconds=0,
    )
    proc = subprocess.run(["bash", "-c", script], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    content = marker.read_text()
    assert "rank=0" in content and "rank=1" in content


def test_single_host_no_stagger():
    script = build_spmd_launch_script(["only-host"], "python3 t.py")
    assert "sleep 5" not in script  # no stagger (poll-loop sleeps remain)
    assert "WORLD_SIZE=1" in script


def test_launch_script_executes_locally(tmp_path):
    """Run the generated script through bash with a local exec template."""
    import subprocess

    marker = tmp_path / "ranks"
    script = build_spmd_launch_script(
        ["h0", "h1"],
        f"sh -c 'echo rank=$NODE_RANK world=$WORLD_SIZE >> {marker}'",
        exec_template="bash -c {cmd}",  # run locally, no ssh
        stagger_seconds=0,
    )
    proc = subprocess.run(["bash", "-c", script], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    content = marker.read_text()
    assert "rank=0 world=2" in content and "rank=1 world=2" in content
    assert "All 2 ranks finished successfully" in proc.stdout


def test_launch_script_fails_if_any_rank_fails():
    import subprocess

    script = build_spmd_launch_script(
        ["h0", "h1"],
        "sh -c 'exit $NODE_RANK'",  # rank 1 fails
        exec_template="bash -c {cmd}",
        stagger_seconds=0,
    )
    proc = subprocess.run(["bash", "-c", script], capture_output=True, text=True)
    assert proc.returncode == 1
    assert "Training failed" in proc.stdout


def test_launch_script_fail_fast_kills_survivors():
    """A dead rank must fail the launch in seconds, not leave the healthy
    rank blocked until the task timeout."""
    import subprocess
    import time as _time

    script = build_spmd_launch_script(
        ["h0", "h1"],
        # Rank 0 would run for 100s; rank 1 dies immediately.
        "sh -c 'if [ $NODE_RANK -eq 1 ]; then exit 3; else sleep 100; fi'",
        exec_template="bash -c {cmd}",
        stagger_seconds=0,
        fail_fast_poll_seconds=1,
    )
    t0 = _time.monotonic()
    proc = subprocess.run(["bash", "-c", script], capture_output=True, text=True)
    elapsed = _time.monotonic() - t0
    assert proc.returncode == 1
    assert "fail-fast" in proc.stdout
    assert elapsed < 30, f"fail-fast took {elapsed:.1f}s"


def test_local_launcher_fail_fast(tmp_path):
    """LocalProcessLauncher: first nonzero exit kills the surviving rank."""
    import time as _time

    launcher = LocalProcessLauncher(
        stagger_seconds=0.0, timeout=60.0, poll_seconds=0.1
    )
    t0 = _time.monotonic()
    results = launcher.launch(
        [
            sys.executable,
            "-c",
            "import os, sys, time\n"
            "rank = int(os.environ['NODE_RANK'])\n"
            "sys.exit(5) if rank == 1 else time.sleep(60)\n",
        ],
        world_size=2,
    )
    elapsed = _time.monotonic() - t0
    assert elapsed < 30, f"fail-fast took {elapsed:.1f}s"
    assert not LocalProcessLauncher.all_succeeded(results)
    assert results[1].returncode == 5
    assert results[0].returncode != 0  # killed, not left running


@pytest.mark.slow
def test_two_process_spmd_training(processed_dir, tmp_path):
    """THE distributed rig: two real jax.distributed processes (CPU
    backend) running the identical jobs/train_tpu.py, metrics must match a
    single-process run on the same data (DDP == big-batch equivalence,
    which the reference asserts only implicitly)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(world_size, models_sub, runs_sub, per_proc_batch):
        env = {
            # Neutralize the ambient TPU plugin for subprocesses.
            "JAX_PLATFORMS": "cpu",
            "DCT_PROCESSED_DIR": processed_dir,
            "DCT_MODELS_DIR": str(tmp_path / models_sub),
            "DCT_TRACKING_DIR": str(tmp_path / runs_sub),
            "DCT_EPOCHS": "2",
            "DCT_BATCH_SIZE": str(per_proc_batch),
            "DCT_BF16_COMPUTE": "0",
        }
        launcher = LocalProcessLauncher(stagger_seconds=1.0, timeout=300)
        results = launcher.launch(
            [sys.executable, os.path.join(repo, "jobs", "train_tpu.py")],
            world_size=world_size,
            env=env,
        )
        assert LocalProcessLauncher.all_succeeded(results), results
        import json
        import glob

        runs = glob.glob(str(tmp_path / runs_sub / "weather_forecasting" / "*" / "metrics.jsonl"))
        assert len(runs) == 1  # coordinator-only tracking
        last = {}
        with open(runs[0]) as f:
            for line in f:
                last.update(json.loads(line))
        return last

    # world 2 x batch 4/rank == world 1 x batch 8: same global batch.
    m2 = run(2, "m2", "r2", 4)
    m1 = run(1, "m1", "r1", 8)
    # Same global batches in the same row order; only the cross-device
    # reduction tree differs (1 device vs 2), so tolerances are fp-level.
    assert abs(m2["val_loss"] - m1["val_loss"]) < 1e-3, (m2, m1)
    assert abs(m2["val_acc"] - m1["val_acc"]) < 0.02, (m2, m1)

    # Rank-0-only side effects: exactly one best checkpoint dir.
    import glob as g

    assert g.glob(str(tmp_path / "m2" / "weather-best-*.ckpt"))

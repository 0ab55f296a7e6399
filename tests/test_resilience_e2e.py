"""ISSUE 3 acceptance rig — self-healing training cycles, end to end:

1. a launched world_size=2 CPU run with ``crash@rank1:epoch1`` injected
   completes after one supervised relaunch; the healed cycle's epoch
   count matches a no-fault run's, ``events.jsonl`` shows
   ``restart.relaunch``, and the lost wall clock is booked as
   ``startup_recovery`` badput in the healed run's goodput summary;
2. a SIGTERM mid-epoch produces a ``PREEMPTED`` (75) exit with a
   durable resume checkpoint, and the resume loses at most one epoch;
3. (slow / chaos CI) a rank that hangs mid-epoch is stall-killed by the
   supervising launcher and the relaunch completes the run.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from dct_tpu.launch.launcher import LocalProcessLauncher
from dct_tpu.resilience.supervisor import EXIT_PREEMPTED

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAIN = os.path.join(REPO, "jobs", "train_tpu.py")


def _env(processed_dir, tmp, **extra):
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "DCT_RUN_ID": "",
        "DCT_SPAN_ID": "",
        "DCT_PROCESSED_DIR": processed_dir,
        "DCT_MODELS_DIR": str(tmp / "models"),
        "DCT_TRACKING_DIR": str(tmp / "runs"),
        "DCT_EVENTS_DIR": str(tmp / "events"),
        "DCT_HEARTBEAT_DIR": str(tmp / "heartbeats"),
        "DCT_EPOCHS": "2",
        "DCT_BATCH_SIZE": "8",
        "DCT_BF16_COMPUTE": "0",
        "DCT_RESUME": "0",
    }
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _events(tmp):
    path = tmp / "events" / "events.jsonl"
    if not path.exists():
        return []
    return [json.loads(line) for line in open(path)]


def _epochs_completed(tmp, rank=0):
    from dct_tpu.checkpoint.manager import TrainStateCheckpointer

    return int(
        TrainStateCheckpointer(
            str(tmp / "models" / "train_state" / f"p{rank}")
        ).load_meta().get("epochs_completed", -1)
    )


def test_crash_resume_supervised_world2(processed_dir, tmp_path):
    """THE acceptance run: rank 1 crashes at epoch 1; the supervisor
    relaunches the whole world once and the cycle still lands exactly
    where a no-fault run does."""
    # -- no-fault control run (same config, its own sandbox) ----------
    ctrl = tmp_path / "ctrl"
    ctrl.mkdir()
    launcher = LocalProcessLauncher(
        coordinator_port=29551, stagger_seconds=1.0, timeout=300.0,
        heartbeat_dir=str(ctrl / "heartbeats"), preempt_grace_s=8.0,
    )
    res = launcher.supervise(
        [sys.executable, TRAIN], world_size=2,
        env=_env(processed_dir, ctrl), max_restarts=2, backoff_s=2.0,
        jitter=0.0,
    )
    assert res.success and res.restarts == 0, res
    ctrl_epochs = _epochs_completed(ctrl)
    assert ctrl_epochs == 2

    # -- fault run: crash rank 1 at the start of epoch 1 --------------
    tmp = tmp_path / "fault"
    tmp.mkdir()
    launcher = LocalProcessLauncher(
        coordinator_port=29553, stagger_seconds=1.0, timeout=300.0,
        heartbeat_dir=str(tmp / "heartbeats"), preempt_grace_s=8.0,
    )
    res = launcher.supervise(
        [sys.executable, TRAIN], world_size=2,
        env=_env(processed_dir, tmp, DCT_FAULT_SPEC="crash@rank1:epoch1"),
        max_restarts=2, backoff_s=2.0, jitter=0.0,
    )
    assert res.success, res
    assert res.restarts == 1
    assert res.attempts[0].classification == "crash"
    assert res.attempts[-1].classification == "success"

    # Healed to the SAME place as the no-fault run.
    assert _epochs_completed(tmp) == ctrl_epochs

    recs = _events(tmp)
    names = [r["event"] for r in recs]
    # The injection, the death, the relaunch, the recovery — on record,
    # all under ONE run-correlation ID.
    assert "fault.injected" in names
    fault = next(r for r in recs if r["event"] == "fault.injected")
    assert fault["action"] == "crash" and fault["injected_rank"] == 1
    assert "restart.relaunch" in names
    relaunch = next(r for r in recs if r["event"] == "restart.relaunch")
    assert relaunch["classification"] == "crash"
    assert relaunch["lost_wall_s"] > 0
    assert len({r["run_id"] for r in recs}) == 1

    # The relaunched attempt RESUMED (epoch 1 only, not epoch 0 again):
    # per rank, every epoch ran exactly once across the healed cycle.
    ends = [r for r in recs if r["event"] == "epoch_end"]
    for rank in (0, 1):
        assert sorted(
            r["epoch"] for r in ends if r["rank"] == rank
        ) == [0, 1]

    # The lost window is booked as startup_recovery badput in the healed
    # run's goodput summary (debt passed via DCT_STARTUP_RECOVERY_DEBT_S
    # plus the relaunched attempt's own startup).
    summaries = [r for r in recs if r["event"] == "goodput_summary"]
    assert summaries
    final = summaries[-1]
    assert (
        final["categories"]["startup_recovery"] >= relaunch["lost_wall_s"]
    )


def test_sigterm_mid_epoch_preempts_then_resume_loses_at_most_one_epoch(
    processed_dir, tmp_path
):
    """Graceful preemption: SIGTERM lands mid-epoch (made deterministic
    by a slow_epoch fault), the trainer finishes the in-flight epoch,
    saves a durable resume checkpoint, and exits 75; the resumed run
    completes the budget without redoing any finished epoch."""
    tmp = tmp_path
    env = dict(os.environ)
    env.update(
        _env(
            processed_dir, tmp,
            DCT_EPOCHS="3",
            DCT_FAULT_SPEC="slow_epoch@rank0:epoch1",
            DCT_FAULT_SLEEP_S="8",
            DCT_RUN_ID="dct-preempt-run1",
        )
    )
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, TRAIN], env=env, start_new_session=True
    )
    try:
        # Wait for epoch 0 to finish; the trainer then sleeps 8 s at the
        # start of epoch 1 — SIGTERM lands mid-epoch, deterministically.
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if any(
                r["event"] == "epoch_end" and r["epoch"] == 0
                for r in _events(tmp)
            ):
                break
            if proc.poll() is not None:
                pytest.fail(f"run1 exited early rc={proc.returncode}")
            time.sleep(0.1)
        else:
            pytest.fail("epoch 0 never completed")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == EXIT_PREEMPTED

    recs = _events(tmp)
    names = [r["event"] for r in recs]
    assert "preempt.signal_received" in names
    assert "preempt.checkpoint_saved" in names
    assert "fit_preempted" in names
    assert "fit_failed" not in names
    saved = next(
        r for r in recs if r["event"] == "preempt.checkpoint_saved"
    )["epochs_completed"]
    assert saved >= 1  # the in-flight epoch was finished, not discarded
    assert _epochs_completed(tmp) == saved
    # The cooperative exit closed its tracking run (no phantom RUNNING
    # run left behind per preemption).
    import glob

    metas = glob.glob(str(tmp / "runs" / "*" / "*" / "meta.json"))
    assert metas
    assert {json.load(open(m))["status"] for m in metas} == {"KILLED"}

    # -- resume: loses no finished epoch, completes the budget --------
    env2 = dict(env)
    env2.update(
        DCT_RESUME="1", DCT_FAULT_SPEC="", DCT_RUN_ID="dct-preempt-run2"
    )
    rc2 = subprocess.run(
        [sys.executable, TRAIN], env=env2, timeout=300
    ).returncode
    assert rc2 == 0
    assert _epochs_completed(tmp) == 3
    run2 = [r for r in _events(tmp) if r["run_id"] == "dct-preempt-run2"]
    resumed_epochs = sorted(
        r["epoch"] for r in run2 if r["event"] == "epoch_end"
    )
    # At most one epoch of progress lost: the resume picks up exactly
    # where the preempted run's checkpoint left off.
    assert resumed_epochs == list(range(saved, 3))


@pytest.mark.slow
def test_hang_is_stall_killed_and_relaunch_completes(processed_dir, tmp_path):
    """A rank that goes PID-alive-but-wedged (hang fault on the eager
    path) stops beating; the supervising launcher stall-kills the world
    and the relaunch completes the budget."""
    tmp = tmp_path
    launcher = LocalProcessLauncher(
        coordinator_port=29557, stagger_seconds=0.0, timeout=240.0,
        heartbeat_dir=str(tmp / "heartbeats"),
        heartbeat_stall_seconds=25.0, heartbeat_scan_seconds=2.0,
        preempt_grace_s=3.0, stall_kill=True,
    )
    res = launcher.supervise(
        [sys.executable, TRAIN], world_size=1,
        env=_env(
            processed_dir, tmp,
            DCT_FAULT_SPEC="hang@rank0:step3",
            DCT_USE_SCAN="0",
            DCT_HEARTBEAT_INTERVAL="0.2",
        ),
        max_restarts=2, backoff_s=1.0, jitter=0.0,
    )
    assert res.success, res
    assert res.attempts[0].classification == "hang"
    names = [r["event"] for r in _events(tmp)]
    assert "restart.stall_kill" in names
    assert "restart.relaunch" in names
    assert _epochs_completed(tmp) == 2

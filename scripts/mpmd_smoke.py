#!/usr/bin/env python3
"""MPMD pipeline smoke (the ``mpmd-pipeline`` CI job / ISSUE 13).

A short but REAL 2-stage multi-process MPMD session on CPU — one
process per stage, each its own single-process jax world, activations
and gradients crossing the explicit TCP transfer plane — under the
PR 3 supervised launcher:

1. **cold train**: ``python -m dct_tpu.resilience.supervise
   --world-size 2 -- python -m dct_tpu.train.mpmd_worker`` trains 2
   epochs with the compile cache armed; both stages checkpoint
   (``train_state_mpmd/stage<k>/`` + manifest) and publish their AOT
   artifacts; exit 0;
2. **warm AOT relaunch**: resume 1 more epoch — EVERY stage program
   must load ``cache=hit`` (``compile.cache_hit`` events for both
   stages' fwd/bwd/update programs), and the train loss must extend
   the same trajectory;
3. **clean SIGTERM drain**: start a long run, SIGTERM the supervisor
   mid-flight — the workers finish the in-flight epoch, save, exit 75;
   the supervisor classifies "preempted" and exits ``EXIT_PREEMPTED``
   with ``mpmd.stage_done preempted=true`` on the event log.

Exit 0 on success; 1 with a diagnostic (stderr tails + event-log tail)
on any gate failing.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

WAIT_S = float(os.environ.get("DCT_MPMD_SMOKE_WAIT_S", "420"))
EXIT_PREEMPTED = 75


def _events(path: str, name: str) -> list[dict]:
    out = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except ValueError:
                    continue
                if r.get("event") == name:
                    out.append(r)
    except OSError:
        pass
    return out


def _fail(msg: str, ev_path: str, *tails: str) -> int:
    print(f"[mpmd_smoke] FAIL: {msg}", file=sys.stderr)
    for t in tails:
        print(t[-2000:], file=sys.stderr)
    try:
        with open(ev_path) as f:
            lines = f.readlines()
        print("".join(lines[-30:]), file=sys.stderr)
    except OSError:
        pass
    return 1


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="mpmd_smoke_")
    from dct_tpu.data.synthetic import generate_weather_csv
    from dct_tpu.etl.preprocess import preprocess_csv_to_parquet

    raw = os.path.join(tmp, "weather.csv")
    generate_weather_csv(raw, rows=400, seed=7)
    proc = os.path.join(tmp, "processed")
    preprocess_csv_to_parquet(raw, proc)

    ev_dir = os.path.join(tmp, "events")
    ev_path = os.path.join(ev_dir, "events.jsonl")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        DCT_PROCESSED_DIR=proc,
        DCT_MODELS_DIR=os.path.join(tmp, "models"),
        DCT_EVENTS_DIR=ev_dir,
        DCT_HEARTBEAT_DIR=os.path.join(tmp, "hb"),
        DCT_MODEL="weather_transformer_pp",
        DCT_DROPOUT="0",
        DCT_SEQ_LEN="8", DCT_D_MODEL="16", DCT_N_HEADS="2",
        DCT_N_LAYERS="2", DCT_D_FF="32", DCT_N_STAGES="2",
        DCT_BF16_COMPUTE="0", DCT_BATCH_SIZE="8",
        DCT_MPMD_STAGES="1,1", DCT_MPMD_MICROBATCHES="4",
        DCT_MPMD_PORT_BASE=os.environ.get("DCT_MPMD_PORT_BASE", "29650"),
        DCT_MPMD_TRANSFER_TIMEOUT_S="90",
        DCT_COMPILE_CACHE="auto",
        JAX_COMPILATION_CACHE_DIR=os.path.join(tmp, "xla_cache"),
        DCT_WORLD_SIZE="2",
        DCT_RUN_ID="mpmd-smoke",
    )
    env.pop("XLA_FLAGS", None)
    cmd = [
        sys.executable, "-m", "dct_tpu.resilience.supervise", "--",
        sys.executable, "-m", "dct_tpu.train.mpmd_worker",
    ]

    # -- phase 1: cold supervised train -------------------------------
    p1 = subprocess.run(
        cmd, env=dict(env, DCT_EPOCHS="2"), cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=WAIT_S,
    )
    if p1.returncode != 0:
        return _fail(f"cold train rc={p1.returncode}", ev_path, p1.stderr)
    manifest = os.path.join(
        tmp, "models", "train_state_mpmd", "manifest.json"
    )
    if not os.path.exists(manifest):
        return _fail("no MPMD manifest after cold train", ev_path)
    for k in range(2):
        if not os.path.exists(os.path.join(
            tmp, "models", "train_state_mpmd", f"stage{k}", "p0",
            "state", "state.npz",
        )):
            return _fail(f"stage {k} checkpoint missing", ev_path)
    cold_reports = _events(ev_path, "mpmd.step_report")
    if len(cold_reports) < 2:
        return _fail("cold train logged < 2 step reports", ev_path)

    # -- phase 2: warm AOT relaunch -----------------------------------
    p2 = subprocess.run(
        cmd, env=dict(env, DCT_EPOCHS="1", DCT_RESUME="1"),
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=WAIT_S,
    )
    if p2.returncode != 0:
        return _fail(f"warm relaunch rc={p2.returncode}", ev_path, p2.stderr)
    hits = {
        r.get("program")
        for r in _events(ev_path, "compile.cache_hit")
    }
    want = {
        "mpmd_fwd_s0", "mpmd_bwd_s0", "mpmd_update_s0",
        "mpmd_fwd_s1", "mpmd_bwd_s1", "mpmd_update_s1",
    }
    missing = want - hits
    if missing:
        return _fail(
            f"warm relaunch missed AOT hits for {sorted(missing)} "
            f"(hits: {sorted(hits)})", ev_path, p2.stderr,
        )
    warm_reports = _events(ev_path, "mpmd.step_report")
    losses = [
        r.get("train_loss") for r in warm_reports
        if r.get("train_loss") is not None
    ]
    if len(losses) < 3 or not losses[-1] < losses[0]:
        return _fail(
            f"warm relaunch did not extend the trajectory: {losses}",
            ev_path,
        )

    # -- phase 3: clean SIGTERM drain ---------------------------------
    p3 = subprocess.Popen(
        cmd, env=dict(env, DCT_EPOCHS="200", DCT_RESUME="1"),
        cwd=REPO_ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )
    # Wait until training is demonstrably underway (a new step report).
    n0 = len(_events(ev_path, "mpmd.step_report"))
    deadline = time.monotonic() + WAIT_S / 2
    while time.monotonic() < deadline:
        if len(_events(ev_path, "mpmd.step_report")) > n0:
            break
        if p3.poll() is not None:
            out, err = p3.communicate()
            return _fail(
                f"long run died early rc={p3.returncode}", ev_path, err
            )
        time.sleep(0.5)
    else:
        p3.kill()
        return _fail("long run never reached a step report", ev_path)
    p3.send_signal(signal.SIGTERM)
    try:
        out, err = p3.communicate(timeout=WAIT_S / 2)
    except subprocess.TimeoutExpired:
        p3.kill()
        return _fail("drain hung past the wait budget", ev_path)
    if p3.returncode != EXIT_PREEMPTED:
        return _fail(
            f"drain rc={p3.returncode} (expected {EXIT_PREEMPTED})",
            ev_path, err,
        )
    drained = [
        r for r in _events(ev_path, "mpmd.stage_done")
        if r.get("preempted")
    ]
    if not drained:
        return _fail("no preempted mpmd.stage_done on the log", ev_path)

    print(
        "[mpmd_smoke] OK: cold train + warm relaunch "
        f"(AOT hits: {len(hits)} programs) + clean SIGTERM drain "
        f"({len(drained)} stage(s) preempted)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Sharded always-on loop smoke (the ``sharded-loop`` CI job / ISSUE 11).

A short but REAL sharded continuous-training session on CPU: two
jax.distributed processes (one virtual device each), mesh
``data=1/model=2`` — the transformer family's params (and Adam
moments) shard ACROSS the two ranks under the partition rules — with
training in ``supervised`` mode (every round under the PR 3 supervisor,
compile cache armed so relaunches resume warm):

1. start ``jobs/loop.py`` as a subprocess over a seeded staging CSV,
   with the sharded mesh/family knobs in the env (the loop forwards
   them into every child rank);
2. append one generation of rows while it runs — the ingest watcher
   must publish it through the incremental-ETL DELTA path;
3. wait for >= 1 mid-run promotion (the evaluator packaging the
   cross-process-gathered best checkpoint and walking gate + rollout);
4. SIGTERM the loop and require a CLEAN drain: exit code 0 and a
   ``loop.stop`` record on the event log.

Exit 0 on success; 1 with a diagnostic (and the loop's stdout tail +
event-log tail) on any gate failing.
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

PROMOTIONS_WANTED = 1
WAIT_S = float(os.environ.get("DCT_LOOP_SMOKE_WAIT_S", "420"))


def _events(path: str, *names: str) -> list[dict]:
    out = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except ValueError:
                    continue
                if r.get("event") in names:
                    out.append(r)
    except OSError:
        pass
    return out


def main() -> int:
    from dct_tpu.data.synthetic import generate_weather_csv

    work = tempfile.mkdtemp(prefix="sharded_loop_smoke_")
    raw = os.path.join(work, "raw", "weather.csv")
    generate_weather_csv(raw, rows=400, seed=7)
    events_path = os.path.join(work, "events", "events.jsonl")

    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        # One device per rank: the model axis must span PROCESSES.
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        DCT_RAW_CSV=raw,
        DCT_PROCESSED_DIR=os.path.join(work, "processed"),
        DCT_MODELS_DIR=os.path.join(work, "models"),
        DCT_EVENTS_DIR=os.path.join(work, "events"),
        DCT_HEARTBEAT_DIR=os.path.join(work, "hb"),
        DCT_TRACKING_DIR=os.path.join(work, "mlruns"),
        DCT_LOOP_PACKAGES_DIR=os.path.join(work, "pkgs"),
        # The contract under test: SHARDED rounds under the PR 3
        # supervisor — a 2-rank world with the transformer family's
        # tensor-parallel axis spanning the processes.
        DCT_LOOP_TRAIN_MODE="supervised",
        DCT_WORLD_SIZE="2",
        DCT_MESH_DATA="1",
        DCT_MESH_MODEL="2",
        DCT_MODEL="weather_transformer",
        DCT_SEQ_LEN="8",
        DCT_D_MODEL="16",
        DCT_N_HEADS="2",
        DCT_N_LAYERS="1",
        DCT_D_FF="32",
        DCT_BATCH_SIZE="16",
        DCT_BF16_COMPUTE="0",
        DCT_LOOP_EPOCHS_PER_ROUND="1",
        DCT_LOOP_SOAK_S="0.1",
        DCT_LOOP_POLL_S="0.3",
        DCT_LOOP_EVAL_POLL_S="0.3",
        DCT_LOOP_MAX_WALL_S=str(int(WAIT_S)),
        # Warm relaunches: the steady-state loop configuration (PR 9).
        DCT_COMPILE_CACHE="on",
        JAX_COMPILATION_CACHE_DIR=os.path.join(work, "xla_cache"),
    )

    # Child output goes to a FILE, not a pipe: supervised rounds log per
    # round and nobody drains a pipe during the wait loop — ~64KB of
    # buffered output would block the loop process mid-session.
    loop_log = os.path.join(work, "loop.log")
    log_f = open(loop_log, "w")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO_ROOT, "jobs", "loop.py")],
        env=env, cwd=REPO_ROOT,
        stdout=log_f, stderr=subprocess.STDOUT,
    )

    appended = 0
    failures: list[str] = []
    try:
        deadline = time.time() + WAIT_S
        while time.time() < deadline:
            if proc.poll() is not None:
                failures.append(
                    f"loop exited early with code {proc.returncode}"
                )
                break
            promos = _events(events_path, "loop.promoted")
            # Grow the staging data once the bootstrap round promoted.
            if appended < 1 and len(promos) >= 1:
                from dct_tpu.data.synthetic import append_weather_rows

                append_weather_rows(raw, rows=150, seed=100)
                appended += 1
                print("[smoke] appended generation", flush=True)
            if len(promos) >= PROMOTIONS_WANTED and appended >= 1:
                deltas = [
                    r for r in _events(events_path, "ingest.processed")
                    if r.get("mode") == "delta"
                ]
                if deltas:
                    break
            time.sleep(1.0)
        else:
            failures.append(
                f"timed out after {WAIT_S:.0f}s waiting for "
                f"{PROMOTIONS_WANTED} promotion(s) + a delta ingest"
            )

        if proc.poll() is None:
            print("[smoke] SIGTERM -> drain", flush=True)
            proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=180)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            failures.append("loop did not drain within 180s of SIGTERM")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        log_f.close()
    try:
        with open(loop_log) as f:
            out = f.read()
    except OSError:
        out = ""

    if proc.returncode != 0 and not failures:
        failures.append(f"drain exit code {proc.returncode} != 0")
    promos = _events(events_path, "loop.promoted")
    if len(promos) < PROMOTIONS_WANTED:
        failures.append(
            f"{len(promos)} promotion(s) < {PROMOTIONS_WANTED}"
        )
    deltas = [
        r for r in _events(events_path, "ingest.processed")
        if r.get("mode") == "delta"
    ]
    if not deltas:
        failures.append("no incremental (delta) ETL generation observed")
    stops = _events(events_path, "loop.stop")
    if not stops:
        failures.append("no loop.stop record — the drain was not clean")

    # The promoted package must hold the DENSE gathered model: the qkv
    # kernel's full [d_model, 3*d_model], not one rank's model-axis
    # shard (the gather-on-publish acceptance made observable).
    if promos and not failures:
        try:
            import glob as _glob

            import numpy as _np

            pkgs = sorted(_glob.glob(os.path.join(work, "pkgs", "pkg-*")))
            npz = _np.load(os.path.join(pkgs[-1], "model.npz"))
            qkv = [k for k in npz.files if k.endswith("qkv_proj/kernel")]
            if not qkv or npz[qkv[0]].shape != (16, 48):
                failures.append(
                    f"promoted package qkv kernel shape "
                    f"{npz[qkv[0]].shape if qkv else None} != (16, 48) — "
                    "a model-axis shard leaked into the package"
                )
        except Exception as e:  # noqa: BLE001 — name it in the verdict
            failures.append(f"package density check failed: {e}")

    print(
        f"[smoke] promotions={len(promos)} delta_ingests={len(deltas)} "
        f"stop={stops[-1].get('reason') if stops else None} "
        f"rc={proc.returncode}",
        flush=True,
    )
    if failures:
        print("[smoke] FAIL:", "; ".join(failures), flush=True)
        print("---- loop stdout tail ----")
        print((out or "")[-3000:])
        print("---- event log tail ----")
        try:
            with open(events_path) as f:
                print("".join(f.readlines()[-25:]))
        except OSError:
            pass
        return 1
    print(
        "[smoke] PASS: ingest -> sharded 2-process rounds -> mid-run "
        "promotion (dense gathered package) -> clean SIGTERM drain",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

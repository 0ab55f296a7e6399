#!/usr/bin/env python3
"""Always-on loop smoke (the ``continuous-loop`` CI job / ISSUE 10).

A short but REAL always-on session on CPU, with training in
``supervised`` mode (every round under the PR 3 supervisor, compile
cache armed so relaunches resume warm):

1. start ``jobs/loop.py`` as a subprocess over a seeded staging CSV;
2. append two generations of rows while it runs — the ingest watcher
   must publish them through the incremental-ETL DELTA path;
3. wait for >= 2 mid-run promotions (the evaluator walking fresh best
   checkpoints through gate + rollout against the live champion);
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

PROMOTIONS_WANTED = 2
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


def _append_generation(raw: str, seed: int) -> None:
    from dct_tpu.data.synthetic import append_weather_rows

    append_weather_rows(raw, rows=150, seed=seed)
    print(f"[smoke] appended generation (seed={seed})", flush=True)


def main() -> int:
    from dct_tpu.data.synthetic import generate_weather_csv

    work = tempfile.mkdtemp(prefix="loop_smoke_")
    raw = os.path.join(work, "raw", "weather.csv")
    generate_weather_csv(raw, rows=400, seed=7)
    events_path = os.path.join(work, "events", "events.jsonl")

    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        DCT_RAW_CSV=raw,
        DCT_PROCESSED_DIR=os.path.join(work, "processed"),
        DCT_MODELS_DIR=os.path.join(work, "models"),
        DCT_EVENTS_DIR=os.path.join(work, "events"),
        DCT_HEARTBEAT_DIR=os.path.join(work, "hb"),
        DCT_TRACKING_DIR=os.path.join(work, "mlruns"),
        DCT_LOOP_PACKAGES_DIR=os.path.join(work, "pkgs"),
        # The contract under test: rounds under the PR 3 supervisor.
        DCT_LOOP_TRAIN_MODE="supervised",
        DCT_LOOP_EPOCHS_PER_ROUND="1",
        DCT_LOOP_SOAK_S="0.1",
        DCT_LOOP_POLL_S="0.3",
        DCT_LOOP_EVAL_POLL_S="0.3",
        DCT_LOOP_MAX_WALL_S=str(int(WAIT_S)),
        # Warm relaunches: the steady-state loop configuration (PR 9).
        DCT_COMPILE_CACHE="on",
        JAX_COMPILATION_CACHE_DIR=os.path.join(work, "xla_cache"),
        # Keep supervised rounds snappy on the CI box.
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
            # Grow the staging data AFTER the bootstrap promotion, one
            # generation per observed promotion milestone.
            if appended < 2 and len(promos) >= appended + 1:
                _append_generation(raw, seed=100 + appended)
                appended += 1
            if len(promos) >= PROMOTIONS_WANTED and appended >= 2:
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
                f"{PROMOTIONS_WANTED} promotions + a delta ingest"
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
    print("[smoke] PASS: ingest -> incremental ETL -> >=2 mid-run "
          "promotions -> clean SIGTERM drain", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

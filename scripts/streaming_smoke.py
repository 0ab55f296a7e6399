#!/usr/bin/env python3
"""Always-on STREAM-FED loop smoke (the ``streaming-smoke`` CI job /
ISSUE 19).

The continuous-loop smoke proves the CSV-polling cycle; this one proves
the streaming ingest data plane end to end against a live producer:

1. start ``jobs/loop.py`` as a subprocess with ``DCT_INGEST_MODE=stream``
   over an EMPTY event-log root — the loop must idle cheaply until the
   producer appears;
2. produce a bootstrap generation of weather events into the
   partitioned log from THIS process (a real cross-process producer:
   tmp+rename segment seals, watermark sidecars, offset commits are the
   only coordination), then one more generation per observed promotion
   — each must flow through the exactly-once stream ETL's DELTA path;
3. wait for >= 2 mid-run promotions whose ``loop.promoted`` records
   carry finite ``freshness_s`` measured from EVENT ARRIVAL time (the
   arrival->served number the plane exists to bound);
4. require the producer to finish un-shed (consumer lag stayed inside
   the budget without backpressure ever degrading to drops);
5. SIGTERM the loop and require a CLEAN drain: exit code 0, a
   ``loop.stop`` record, and a final committed consumer offset equal to
   everything produced (nothing stranded in the log).

Exit 0 on success; 1 with a diagnostic (loop stdout tail + event-log
tail) on any gate failing.
"""

from __future__ import annotations

import csv
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
WAIT_S = float(os.environ.get("DCT_STREAM_SMOKE_WAIT_S", "420"))
TOPIC = "events"
GROUP = "etl"


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


def _weather_records(rows: int, seed: int) -> list[dict]:
    """Synthetic weather rows as stream payloads (same generator the
    CSV smokes seed from, so the model actually learns)."""
    from dct_tpu.data.synthetic import generate_weather_csv

    with tempfile.TemporaryDirectory() as td:
        path = generate_weather_csv(
            os.path.join(td, "w.csv"), rows=rows, seed=seed
        )
        with open(path) as f:
            return [dict(r) for r in csv.DictReader(f)]


def _produce(stream_dir: str, rows: int, seed: int) -> int:
    """One producer session: open, append, seal on close. Returns the
    number of records durably appended (un-shed)."""
    from dct_tpu.stream.log import PartitionedEventLog, StreamProducer

    log = PartitionedEventLog(stream_dir, TOPIC, partitions=1)
    prod = StreamProducer(
        log, groups=(GROUP,), backpressure="block",
        lag_budget=4096, block_timeout_s=60.0,
    )
    for rec in _weather_records(rows, seed):
        prod.produce(rec)
    prod.close()
    print(
        f"[smoke] produced {prod.produced} events "
        f"(seed={seed}, shed={prod.shed})",
        flush=True,
    )
    return prod.produced if prod.shed == 0 else -prod.shed


def main() -> int:
    work = tempfile.mkdtemp(prefix="stream_smoke_")
    stream_dir = os.path.join(work, "stream")
    events_path = os.path.join(work, "events", "events.jsonl")

    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        # The contract under test: the loop fed by the event log alone.
        DCT_INGEST_MODE="stream",
        DCT_STREAM_DIR=stream_dir,
        DCT_STREAM_TOPIC=TOPIC,
        DCT_STREAM_GROUP=GROUP,
        DCT_STREAM_POLL_S="0.1",
        DCT_STREAM_SEGMENT_RECORDS="256",
        DCT_PROCESSED_DIR=os.path.join(work, "processed"),
        DCT_MODELS_DIR=os.path.join(work, "models"),
        DCT_EVENTS_DIR=os.path.join(work, "events"),
        DCT_HEARTBEAT_DIR=os.path.join(work, "hb"),
        DCT_TRACKING_DIR=os.path.join(work, "mlruns"),
        DCT_LOOP_PACKAGES_DIR=os.path.join(work, "pkgs"),
        DCT_LOOP_TRAIN_MODE="inline",
        DCT_LOOP_EPOCHS_PER_ROUND="1",
        DCT_LOOP_SOAK_S="0.1",
        DCT_LOOP_POLL_S="0.3",
        DCT_LOOP_EVAL_POLL_S="0.3",
        DCT_LOOP_MAX_WALL_S=str(int(WAIT_S)),
    )

    # Child output to a FILE, not a pipe (see continuous_loop_smoke.py).
    loop_log = os.path.join(work, "loop.log")
    log_f = open(loop_log, "w")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO_ROOT, "jobs", "loop.py")],
        env=env, cwd=REPO_ROOT,
        stdout=log_f, stderr=subprocess.STDOUT,
    )

    produced_total = 0
    generations = 0
    shed = 0
    failures: list[str] = []
    try:
        # Bootstrap generation AFTER the loop starts: stream mode must
        # come up against a not-yet-existent topic and stay healthy.
        time.sleep(2.0)
        n = _produce(stream_dir, 400, seed=7)
        if n < 0:
            shed += -n
        else:
            produced_total += n
        generations = 1

        deadline = time.time() + WAIT_S
        while time.time() < deadline:
            if proc.poll() is not None:
                failures.append(
                    f"loop exited early with code {proc.returncode}"
                )
                break
            promos = _events(events_path, "loop.promoted")
            # Grow the stream one generation per promotion milestone —
            # these MUST land via the delta (mode "stream") ETL path.
            if generations < 3 and len(promos) >= generations:
                n = _produce(stream_dir, 150, seed=100 + generations)
                if n < 0:
                    shed += -n
                else:
                    produced_total += n
                generations += 1
            if len(promos) >= PROMOTIONS_WANTED and generations >= 3:
                deltas = [
                    r for r in _events(events_path, "ingest.processed")
                    if r.get("source") == "stream"
                    and r.get("mode") == "stream"
                ]
                if deltas:
                    break
            time.sleep(1.0)
        else:
            failures.append(
                f"timed out after {WAIT_S:.0f}s waiting for "
                f"{PROMOTIONS_WANTED} promotions + a stream-delta ingest"
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
        failures.append(f"{len(promos)} promotion(s) < {PROMOTIONS_WANTED}")
    fresh = [p.get("freshness_s") for p in promos]
    if promos and not all(
        isinstance(f, (int, float)) and f >= 0 for f in fresh
    ):
        failures.append(
            f"promotion freshness not measured from arrival ts: {fresh}"
        )
    stream_ingests = [
        r for r in _events(events_path, "ingest.processed")
        if r.get("source") == "stream"
    ]
    deltas = [r for r in stream_ingests if r.get("mode") == "stream"]
    if not stream_ingests:
        failures.append("no stream-fed ETL generation observed")
    elif not deltas:
        failures.append(
            "no exactly-once DELTA (mode=stream) generation observed"
        )
    if shed:
        failures.append(
            f"producer shed {shed} events — lag left the bounded budget"
        )
    stops = _events(events_path, "loop.stop")
    if not stops:
        failures.append("no loop.stop record — the drain was not clean")

    # Nothing stranded: the drained loop's last commit covers the log.
    from dct_tpu.stream.consumer import committed_offsets

    offsets_dir = os.path.join(stream_dir, TOPIC, "offsets")
    committed = sum(committed_offsets(offsets_dir, GROUP, 1))
    if committed != produced_total:
        failures.append(
            f"committed offsets {committed} != produced {produced_total} "
            "— events stranded in the log after drain"
        )

    print(
        f"[smoke] promotions={len(promos)} freshness_s={fresh} "
        f"stream_ingests={len(stream_ingests)} deltas={len(deltas)} "
        f"produced={produced_total} committed={committed} "
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
        "[smoke] PASS: live producer -> exactly-once stream ETL -> "
        ">=2 arrival-fresh promotions -> clean drain, nothing stranded",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

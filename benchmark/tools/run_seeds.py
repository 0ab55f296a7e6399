#!/usr/bin/env python3
"""Run one cell on several seeds, one process a run, and keep what each said.

    chiprun -- python benchmark/tools/run_seeds.py --workload <cell> \\
        --seeds 2147490011 2147490029 --out chiprun_out/<name>.jsonl \\
        [--seconds <run_seconds>] [--trace 0] [--root build/co]

A set of a cell's runs belongs in one call of the chip tool (the first run
compiles, the others find every program in the cache). Each run is the
benchmark's own command in a process of its own, started from ``--root`` (this
checkout, or another one unpacked inside it). This file imports no JAX, so
the chip is the child's. A line of ``--out`` holds a run's seed, exit code and
wall seconds, its result line, the notes ``run.py`` prints on standard error,
and the ``moe_*`` counters of every epoch the trainer logged to its local
tracker (the epoch that closes the window is in no ``epoch_end`` event, but
it is there; the files are where driver ``fit`` puts the run's tracker,
``build/benchmark/<cell>/mlruns``). Standard output gets one line a run: what
one looks at first, the per-layer metrics too where the run was traced.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _last_json(text: str, prefix: str = "") -> dict | None:
    for line in reversed(text.strip().splitlines()):
        if line.startswith(prefix + "{"):
            return json.loads(line[len(prefix):])
    return None


def _epoch_counters(root: str, cell: str) -> list[dict]:
    """The ``moe_*`` keys of each epoch's metrics, in epoch order."""
    rows = []
    for path in glob.glob(os.path.join(
            root, "build", "benchmark", cell, "mlruns", "*", "*",
            "metrics.jsonl")):
        with open(path) as f:
            rows += [json.loads(line) for line in f if line.strip()]
    rows = [r for r in rows if "val_loss" in r]
    return [
        {"step": r["step"], **{
            k: v for k, v in r.items()
            if k.startswith("moe_") and not k[-1].isdigit()}}
        for r in sorted(rows, key=lambda r: r["step"])]


def run_one(root: str, command: list, cell: str, seed: int, seconds: int,
            trace: int) -> dict:
    argv = [*command, "--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    began = time.perf_counter()
    r = subprocess.run(argv, cwd=root, capture_output=True, text=True)
    return {
        "workload": cell, "seed": seed, "trace": trace, "root": root,
        "rc": r.returncode, "wall_s": time.perf_counter() - began,
        "result": _last_json(r.stdout),
        "notes": _last_json(r.stderr, "[benchmark] "),
        "epoch_counters": _epoch_counters(root, cell),
        "stderr_tail": "" if r.returncode == 0 else r.stderr[-3000:],
    }


def summary(rec: dict) -> dict:
    """What one looks at first of a run: its line on standard output."""
    result, notes = rec["result"] or {}, rec["notes"] or {}
    e2e = notes.get("end_to_end_in_this_run", {})
    out = {
        "seed": rec["seed"], "rc": rec["rc"],
        "correct": result.get("correct"), "wall_s": round(rec["wall_s"], 1),
        "fit_tokens_per_s": e2e.get("fit_tokens_per_s"),
        "setup_s": e2e.get("setup_s"),
        "epoch_seconds": e2e.get("epoch_seconds"),
        "memory_peak_bytes": result.get("device", {}).get(
            "memory_peak_bytes"),
        "why_not_correct": notes.get("why_not_correct") or None,
    }
    if rec["trace"]:
        out["metrics"] = {
            k: v["value"] for k, v in result.get("metrics", {}).items()}
    for i, c in enumerate(rec["epoch_counters"]):
        if c.get("moe_rows"):
            bound = c.get("moe_rows_bound")
            out[f"epoch{i}"] = {
                "rows": c["moe_rows"],
                "max_over_mean": c.get("moe_rows_max_over_mean"),
                "bound_over_routed": bound and bound / c["moe_rows"],
                "bias_abs_max": c.get("moe_bias_abs_max"),
                "overflowed": c.get("moe_rows_overflowed"),
            }
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=int)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    table = []
    for seed in args.seeds:
        rec = run_one(root, manifest["command"], args.workload, seed,
                      seconds, args.trace)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        table.append(summary(rec))
        print(json.dumps(table[-1]), flush=True)
    return 0 if all(
        t["rc"] == 0 and t["correct"] for t in table) else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, sets up, warms up, measures for
``--seconds`` and prints one JSON object as the last line of its standard
output: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
traced, ``breakdown``. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics. It exits 2 and
prints no result where JAX finds no TPU or another number of chips than the
cell asks for. Everything it writes lands under ``build/benchmark/`` and the compile
cache the program's resolver names (``JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.jax_cache``), both inside the checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's record."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - start


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_record(jax) -> dict:
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


def run_cell(args, *, require_tpu: bool = True):
    """(exit code, result line or None, notes). ``require_tpu=False`` is
    for the CPU rehearsal in ``benchmark/tests``: its result says
    ``"platform": "cpu"`` and is never printed by this file."""
    from benchmark import manifest as mf

    if not os.path.isdir(os.path.join(ROOT, "dct_tpu")):
        print("benchmark: the program (dct_tpu/) is not in this checkout",
              file=sys.stderr)
        return 2, None, None
    manifest = mf.load_manifest()
    cell, config, traffic = mf.load_cell(manifest, args.workload)

    import jax

    device = device_record(jax)
    if require_tpu and (
        device["platform"] != "tpu" or device["count"] != cell["chips"]
    ):
        print(
            f"benchmark: cell {cell['name']} needs {cell['chips']} TPU "
            f"chip(s); JAX found {device['count']} x {device['platform']} "
            f"({device['kind']}); nothing was run", file=sys.stderr)
        return 2, None, None

    from dct_tpu.compilecache import resolve_cache_dir

    cache_dir = resolve_cache_dir({**os.environ, "DCT_COMPILE_CACHE": "on"})
    driver = mf.load_module(
        os.path.join(mf.BENCH_DIR, "drivers", traffic["driver"] + ".py"),
        "bench_driver_" + traffic["driver"],
    )
    work = os.path.join(ROOT, "build", "benchmark", cell["name"])
    art = driver.run(
        cell, config, traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), work=work, cache_dir=cache_dir,
        age_fn=process_age_s,
    )
    art.update(cell=cell, config=config, traffic=traffic, device=device,
               manifest=manifest)
    verdict = driver.verdict(art)
    e2e = driver.end_to_end(art)
    art["end_to_end"] = e2e
    device["memory_peak_bytes"] = art["memory_peak_bytes"]
    metrics: dict = {}
    out = {
        "correct": verdict["correct"], "attempted": verdict["attempted"],
        "failed": verdict["failed"], "metrics": metrics, "device": device,
    }
    if args.trace:
        from benchmark.reduce import trace as tr

        xplane = tr.find_xplane(art["trace_dir"])
        reduced = tr.load(xplane) if xplane else None
        art["trace"] = reduced
        if reduced is not None and reduced.devices:
            device["busy_s"] = tr.busy_mean_s(reduced)
            device["window_s"] = reduced.window_s
            out["breakdown"] = {
                "device_ops": tr.top_ops(reduced),
                "idle_gaps": tr.attribute_gaps(reduced),
            }
        for m in mf.metrics_of(manifest, "per_layer", cell["name"]):
            value = mf.load_layer_metric(m["name"]).read(art)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in mf.metrics_of(manifest, "end_to_end", cell["name"]):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {
                    "value": e2e[m["name"]], "unit": m["unit"]}
    # What the line does not carry, for whoever reads the run by hand.
    notes = {
        "why_not_correct": verdict["why"], "reference": art["reference"],
        "mesh": art["mesh"], "attention": art["attention_path"],
        "epochs": e2e.get("epochs"), "stopped": art["stopped"],
        "setup_marks_s": art.get("setup_marks"),
        "end_to_end_in_this_run": {
            k: v for k, v in e2e.items() if v is not None},
    }
    return 0, out, notes


def main(argv=None) -> int:
    rc, out, notes = run_cell(parse(argv))
    if out is None:
        return rc
    print("[benchmark] " + json.dumps(notes), file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

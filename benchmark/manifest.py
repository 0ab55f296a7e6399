"""BENCHMARK.json and the data files it names: loading and the lint.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name the manifest
gives. A later PR adds a file and an entry and edits nothing here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(path: str = MANIFEST) -> dict:
    return load_json(path)


def traffic_path(mix: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", f"{mix}.json")


def layer_metric_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "layer_metrics", f"{name}.py")


def load_cell(manifest: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic) of the cell named ``workload``."""
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"unknown workload {workload!r}; known: {sorted(cells)}"
        )
    cell = cells[workload]
    cfg_entry = next(
        c for c in manifest["configs"] if c["name"] == cell["config"]
    )
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(traffic_path(cell["traffic"]))
    return cell, config, traffic


def metrics_of(manifest: dict, kind: str, workload: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: an
    entry without ``workloads`` belongs to every cell."""
    return [
        m for m in manifest[kind]
        if "workloads" not in m or workload in m["workloads"]
    ]


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_layer_metric(name: str):
    """The reader module of one per-layer metric: ``LAYER``, ``UNIT``,
    ``MOVES``, ``SOURCE`` and ``read(artefacts) -> float | None``."""
    return load_module(
        layer_metric_path(name), "layer_metric_" + re.sub(r"\W", "_", name)
    )


def load_flops(config: dict):
    """The operation counts of the configuration's family, from the file
    its ``flops`` key names; nothing where it names none."""
    kind = config.get("flops")
    if not kind:
        return None
    return load_module(
        os.path.join(BENCH_DIR, "flops", f"{kind}.py"), "bench_flops_" + kind)


def lint(manifest: dict) -> list[str]:
    """Every breach of the contract that can be seen without a run."""
    errs: list[str] = []

    def name_ok(what: str, value) -> None:
        if not isinstance(value, str) or not NAME_RE.match(value):
            errs.append(f"{what}: bad name {value!r}")

    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != keys:
        errs.append(f"top-level keys {sorted(manifest)} != {sorted(keys)}")
        return errs
    if not 1 <= int(manifest["run_seconds"]) <= 51:
        errs.append("run_seconds outside 1..51")
    for p in manifest["paths"]:
        if not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/") \
                or ".." in p.split("/"):
            errs.append(f"bad path {p!r}")
    config_names = set()
    files = set()
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errs.append(f"config keys {sorted(c)}")
            continue
        name_ok("config", c["name"])
        for k in ("why", "source"):
            if not 1 <= len(c[k]) <= 200 or "\n" in c[k] or "\t" in c[k]:
                errs.append(f"config {c['name']}: {k} is {len(c[k])} chars")
        config_names.add(c["name"])
        if c["file"] in files:
            errs.append(f"config file {c['file']} used twice")
        files.add(c["file"])
        if not any(c["file"].startswith(p + "/") for p in manifest["paths"]):
            errs.append(f"config file {c['file']} outside paths")
        if not os.path.exists(os.path.join(ROOT, c["file"])):
            errs.append(f"config file {c['file']} missing")
        if len(c["reduced"]) > 16:
            errs.append(f"config {c['name']}: more than 16 reduced keys")
        for k in c["reduced"]:
            name_ok(f"config {c['name']} reduced", k)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        errs.append("no setup_s among end_to_end")
    for m in manifest["end_to_end"]:
        if not set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}:
            errs.append(f"end_to_end keys {sorted(m)}")
        name_ok("end_to_end", m["name"])
        if not UNIT_RE.match(m["unit"]):
            errs.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            errs.append(f"{m['name']}: better={m['better']!r}")
        if not 0.01 <= m["bound"] <= 0.1:
            errs.append(f"{m['name']}: bound {m['bound']} outside 1%..10%")
        if m["source"] not in ("host_clock", "device_trace"):
            errs.append(f"{m['name']}: end-to-end source {m['source']!r}")
    for m in manifest["per_layer"]:
        if not set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}:
            errs.append(f"per_layer keys {sorted(m)}")
        name_ok("per_layer", m["name"])
        if not UNIT_RE.match(m["unit"]):
            errs.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            errs.append(f"{m['name']}: better={m['better']!r}")
        if m["source"] not in SOURCES:
            errs.append(f"{m['name']}: source {m['source']!r}")
        if m["moves"] not in e2e:
            errs.append(f"{m['name']}: moves unknown {m['moves']!r}")
        if not os.path.exists(layer_metric_path(m["name"])):
            errs.append(f"{m['name']}: no reader file")
    all_names = [m["name"] for m in manifest["end_to_end"]] + [
        m["name"] for m in manifest["per_layer"]]
    if len(set(all_names)) != len(all_names):
        errs.append("two metrics share a name")
    cells = manifest["workloads"]
    if not 2 <= len(cells) <= 24:
        errs.append(f"{len(cells)} cells, want 2..24")
    seen_pairs = set()
    used_configs = set()
    for c in cells:
        if set(c) != {"name", "config", "traffic", "chips", "why"}:
            errs.append(f"cell keys {sorted(c)}")
            continue
        for k in ("name", "config", "traffic"):
            name_ok(f"cell {k}", c[k])
        if c["config"] not in config_names:
            errs.append(f"cell {c['name']}: unknown config {c['config']}")
        used_configs.add(c["config"])
        if (c["config"], c["traffic"]) in seen_pairs:
            errs.append(f"cell {c['name']}: pair appears twice")
        seen_pairs.add((c["config"], c["traffic"]))
        if c["chips"] not in (1, 4):
            errs.append(f"cell {c['name']}: chips {c['chips']}")
        if not 1 <= len(c["why"]) <= 200 or "\n" in c["why"]:
            errs.append(f"cell {c['name']}: why is {len(c['why'])} chars")
        if not os.path.exists(traffic_path(c["traffic"])):
            errs.append(f"cell {c['name']}: no traffic file")
        reported_e2e = [m["name"] for m in metrics_of(
            manifest, "end_to_end", c["name"])]
        if "setup_s" not in reported_e2e or len(reported_e2e) < 2:
            errs.append(f"cell {c['name']}: end-to-end {reported_e2e}")
        layer = metrics_of(manifest, "per_layer", c["name"])
        if not layer:
            errs.append(f"cell {c['name']}: no per-layer metric")
        for m in layer:
            if m["moves"] not in reported_e2e:
                errs.append(
                    f"cell {c['name']}: {m['name']} moves {m['moves']}, "
                    "which the cell does not report")
    if used_configs != config_names:
        errs.append(f"unused configs {sorted(config_names - used_configs)}")
    four = sum(1 for c in cells if c["chips"] == 4)
    if four > max(1, len(cells) // 4):
        errs.append(f"{four} of {len(cells)} cells ask for four chips")
    cmd = manifest["command"]
    if len(cmd) > 32 or any(
        w.startswith("/") or ".." in w.split("/") for w in cmd
    ):
        errs.append(f"bad command {cmd}")
    if len(json.dumps(manifest)) > 64 * 1024:
        errs.append("manifest over 64 KiB")
    return errs

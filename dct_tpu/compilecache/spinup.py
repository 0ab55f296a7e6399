"""Cold/warm spin-up measurement: SIGKILL→first-step and first-score.

The restart/spin-up debt the compile cache erases, measured through the
REAL paths:

- :func:`measure_relaunch` runs ``python -m dct_tpu.resilience.supervise``
  over ``jobs/train_tpu.py`` with a ``crash@rank0:step1`` fault plan —
  attempt 1 compiles, is hard-killed at its first span boundary (before
  any resume snapshot), and the supervisor relaunches. The event log
  then yields **time-from-SIGKILL-to-first-step** (``fault.injected``
  ts → the healed attempt's first ``epoch_end`` ts), the healed
  attempt's ``compile.window`` seconds + cache labels, and its
  ``startup_recovery`` badput.
- :func:`measure_first_score` times an endpoint worker's
  **time-to-first-score** (scorer build → first probabilities) over a
  deployed package's jitted jax scorer, in a fresh subprocess per
  measurement so in-process jit caches cannot flatter the warm number.

Used by two consumers with one implementation: the ``compile-cache``
CI smoke (scripts/compile_cache_smoke.py) and the e2e tests.

Run this module as a CLI for the subprocess halves::

    python -m dct_tpu.compilecache.spinup first-score <package_dir>
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

#: Env keys a measurement must control; everything else passes through.
_CLEARED = (
    "DCT_RESUME", "DCT_STARTUP_RECOVERY_DEBT_S", "DCT_RUN_ID",
    "DCT_SPAN_ID", "DCT_FAULT_SPEC", "DCT_METRICS_DIR",
)


def prepare_processed(workdir: str, *, rows: int = 600, seed: int = 0) -> str:
    """Synthetic weather CSV -> processed parquet dir (the trainer's
    input contract), under ``workdir``."""
    from dct_tpu.data.synthetic import generate_weather_csv
    from dct_tpu.etl.preprocess import preprocess_csv_to_parquet

    csv = os.path.join(workdir, "raw", "weather.csv")
    processed = os.path.join(workdir, "processed")
    if not os.path.isdir(processed):
        generate_weather_csv(csv, rows=rows, seed=seed)
        preprocess_csv_to_parquet(csv, processed)
    return processed


def _measure_env(
    workdir: str, tag: str, *, cache_on: bool, model_env: dict | None,
) -> dict:
    env = dict(os.environ)
    for k in _CLEARED:
        env.pop(k, None)
    env.update(
        JAX_PLATFORMS=env.get("JAX_PLATFORMS", "cpu"),
        DCT_PROCESSED_DIR=os.path.join(workdir, "processed"),
        DCT_MODELS_DIR=os.path.join(workdir, f"models_{tag}"),
        DCT_EVENTS_DIR=os.path.join(workdir, f"events_{tag}"),
        DCT_HEARTBEAT_DIR=os.path.join(workdir, f"hb_{tag}"),
        DCT_TRACKING_DIR=os.path.join(workdir, f"mlruns_{tag}"),
        DCT_COMPILE_CACHE="on" if cache_on else "off",
        DCT_COMPILE_CACHE_AOT_DIR=os.path.join(workdir, "aot"),
        DCT_EPOCHS="1",
        DCT_BATCH_SIZE="32",
        DCT_USE_SCAN="1",
        # Telemetry write-through: the event timestamps ARE the
        # measurement, and the crash path must not owe them a flush.
        DCT_TELEMETRY_FLUSH_S="0",
    )
    # JAX reads this variable itself, whatever DCT_COMPILE_CACHE says: the
    # cold control must not see one at all.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_on:
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(workdir, "xla_cache")
    env.update(model_env or {})
    return env


def _read_events(events_dir: str) -> list[dict]:
    path = os.path.join(events_dir, "events.jsonl")
    records = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    records.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return records


def measure_relaunch(
    workdir: str,
    *,
    cache_on: bool,
    prewarm: bool = False,
    model_env: dict | None = None,
    backoff_s: float = 0.2,
    timeout: float = 600.0,
    world_size: int = 1,
) -> dict:
    """One supervised crash-and-relaunch cycle; returns the restart
    metrics dict (see module docstring). ``prewarm`` runs a plain
    1-epoch training first (separate models dir, SAME cache dirs) so
    even the crashing attempt starts warm — the configuration the
    steady-state continuous-training loop lives in. ``world_size > 1``
    supervises a real multi-process world (pass the mesh/device knobs
    via ``model_env``) — the sharded-relaunch proof path: per-rank AOT
    artifacts must warm the healed attempt exactly like DP ones."""
    tag = ("warm" if cache_on else "cold") + ("_pw" if prewarm else "")
    env = _measure_env(workdir, tag, cache_on=cache_on, model_env=model_env)
    train = [sys.executable, os.path.join(REPO_ROOT, "jobs", "train_tpu.py")]
    if prewarm:
        pre_env = dict(env)
        pre_env.update(
            DCT_MODELS_DIR=os.path.join(workdir, f"models_{tag}_prewarm"),
            DCT_EVENTS_DIR=os.path.join(workdir, f"events_{tag}_prewarm"),
            DCT_HEARTBEAT_DIR=os.path.join(workdir, f"hb_{tag}_prewarm"),
            DCT_TRACKING_DIR=os.path.join(workdir, f"mlruns_{tag}_prewarm"),
        )
        subprocess.run(
            train, env=pre_env, cwd=REPO_ROOT, capture_output=True,
            timeout=timeout,
        )
    env["DCT_FAULT_SPEC"] = "crash@rank0:step1"
    t0 = time.monotonic()
    proc = subprocess.run(
        [
            sys.executable, "-m", "dct_tpu.resilience.supervise",
            "--world-size", str(world_size), "--max-restarts", "1",
            "--backoff", str(backoff_s), "--jitter", "0",
            "--", *train,
        ],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True,
        timeout=timeout,
    )
    wall = time.monotonic() - t0

    ev = _read_events(env["DCT_EVENTS_DIR"])
    t_kill = next(
        (r["ts"] for r in ev if r.get("event") == "fault.injected"), None
    )
    first_step = next(
        (
            r["ts"] for r in ev
            if r.get("event") == "epoch_end"
            and t_kill is not None and r["ts"] > t_kill
        ),
        None,
    )
    # The crashed attempt dies before its end-of-fit compile report, so
    # every compile.window on the log belongs to the healed attempt.
    windows = [r for r in ev if r.get("event") == "compile.window"]
    goodput = next(
        (r for r in ev if r.get("event") == "goodput_summary"), None
    )
    return {
        "returncode": proc.returncode,
        "wall_s": round(wall, 3),
        "sigkill_to_first_step_s": (
            round(first_step - t_kill, 3)
            if t_kill is not None and first_step is not None else None
        ),
        "relaunch_compile_s": round(
            sum(float(r.get("seconds") or 0.0) for r in windows), 3
        ),
        "relaunch_cache": sorted(
            {str(r.get("cache", "disabled")) for r in windows}
        ),
        "startup_recovery_s": (
            round(
                float(
                    goodput.get("categories", {}).get(
                        "startup_recovery", 0.0
                    )
                ),
                3,
            )
            if goodput else None
        ),
        "stderr_tail": proc.stderr[-500:] if proc.returncode else "",
    }


#: The endpoint worker's warm-up batch ladder: a single-row probe plus
#: the default max-batch flush — the two programs a fresh worker
#: compiles (or loads) before it is serving-ready under real traffic.
FIRST_SCORE_SIZES = (1, 64)


def measure_first_score(
    package_dir: str, *, cache_on: bool,
    sizes: tuple = FIRST_SCORE_SIZES, timeout: float = 300.0,
) -> float | None:
    """Time-to-first-score of a fresh endpoint worker over the deployed
    package's jax scorer, in a subprocess: scorer build +
    compile-or-load + one scored request per batch size in the worker's
    warm-up ladder. Returns seconds, or None on failure."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS=env.get("JAX_PLATFORMS", "cpu"),
        DCT_COMPILE_CACHE="on" if cache_on else "off",
    )
    # A persistent XLA cache would hide the compile on the "cold"
    # control (JAX reads the variable whatever DCT_COMPILE_CACHE says);
    # the warm side gets an empty one of its own beside the package, so
    # the measurement isolates the package's aot/ dir.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_on:
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            os.path.dirname(os.path.abspath(package_dir)),
            "xla_cache_first_score",
        )
    proc = subprocess.run(
        [
            sys.executable, "-m", "dct_tpu.compilecache.spinup",
            "first-score", package_dir,
            ",".join(str(s) for s in sizes),
        ],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True,
        timeout=timeout,
    )
    if proc.returncode != 0:
        sys.stderr.write(
            f"[spinup] first-score failed: {proc.stderr[-500:]}\n"
        )
        return None
    try:
        return float(json.loads(proc.stdout.splitlines()[-1])["first_score_s"])
    except (ValueError, KeyError, IndexError):
        return None


def _first_score_main(package_dir: str, sizes: tuple) -> int:
    """Subprocess half of :func:`measure_first_score`: load the
    package, build the jitted scorer (AOT store over ``<pkg>/aot`` —
    honored or bypassed per ``DCT_COMPILE_CACHE``), score one request
    per warm-up batch size, report the wall. ``force_store`` is NOT
    set: the measurement obeys exactly the env contract a real
    endpoint worker would."""
    import numpy as np

    from dct_tpu.compilecache.aot import _example_batch
    from dct_tpu.serving.batching import _build_jax_scorer
    from dct_tpu.serving.runtime import assemble_weights

    npz = np.load(os.path.join(package_dir, "model.npz"))
    weights = assemble_weights({k: npz[k] for k in npz.files})
    with open(os.path.join(package_dir, "model_meta.json")) as f:
        meta = json.load(f)
    meta["_aot_dir"] = os.path.join(package_dir, "aot")
    t0 = time.perf_counter()
    score = _build_jax_scorer(weights, meta)
    shape = None
    for n in sizes:
        shape = list(np.asarray(score(_example_batch(meta, n))).shape)
    first = time.perf_counter() - t0
    print(json.dumps({
        "first_score_s": round(first, 4),
        "sizes": list(sizes),
        "probs_shape": shape,
    }))
    return 0


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "first-score" and len(argv) in (2, 3):
        sizes = tuple(
            int(t) for t in (
                argv[2] if len(argv) == 3 else "1"
            ).split(",") if t.strip().isdigit()
        ) or (1,)
        return _first_score_main(argv[1], sizes)
    print(
        "usage: python -m dct_tpu.compilecache.spinup "
        "first-score <package_dir> [sizes]",
        file=sys.stderr,
    )
    return 2


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Cold→warm restart smoke for the compile cache (tier1.yml job).

Runs the REAL supervised relaunch path twice on CPU — compile cache
off (cold control) then armed (warm) — over the crash drill of
``dct_tpu/compilecache/spinup.py``, and gates:

1. the healed warm attempt resolved its fused program from the AOT
   store (``compile.window`` cache label == ``hit``);
2. warm relaunch compile-window seconds < half the cold control's
   (the XLA compile is gone; what remains is trace + deserialize);
3. warm time-from-SIGKILL-to-first-step < cold.

Then the endpoint half: a package built with the packaging-time scorer
warm-up must spin up a worker faster than the cold control.

Exit 0 = all gates hold; nonzero with the evidence printed otherwise.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

MODEL_ENV = {
    "DCT_MODEL": "weather_transformer",
    "DCT_N_LAYERS": "4",
    "DCT_D_MODEL": "96",
    "DCT_N_HEADS": "4",
    "DCT_D_FF": "384",
    "DCT_SEQ_LEN": "16",
    "DCT_PREFETCH_SPANS": "0",
}


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from dct_tpu.compilecache import spinup
    from dct_tpu.serving.score_gen import generate_score_package

    failures: list[str] = []
    with tempfile.TemporaryDirectory() as work:
        spinup.prepare_processed(work, rows=600)
        cold = spinup.measure_relaunch(
            work, cache_on=False, model_env=MODEL_ENV
        )
        warm = spinup.measure_relaunch(
            work, cache_on=True, model_env=MODEL_ENV
        )
        print("cold:", json.dumps(cold))
        print("warm:", json.dumps(warm))
        for tag, res in (("cold", cold), ("warm", warm)):
            if res["returncode"] != 0:
                failures.append(
                    f"{tag} supervised run exited "
                    f"{res['returncode']}: {res['stderr_tail']}"
                )
            if res["sigkill_to_first_step_s"] is None:
                failures.append(f"{tag} run left no relaunch timeline")
        if not failures:
            if warm["relaunch_cache"] != ["hit"]:
                failures.append(
                    "warm relaunch compile windows not all cache=hit: "
                    f"{warm['relaunch_cache']}"
                )
            if not (
                warm["relaunch_compile_s"]
                < 0.5 * cold["relaunch_compile_s"]
            ):
                failures.append(
                    "warm compile seconds not < half cold: "
                    f"{warm['relaunch_compile_s']} vs "
                    f"{cold['relaunch_compile_s']}"
                )
            if not (
                warm["sigkill_to_first_step_s"]
                < cold["sigkill_to_first_step_s"]
            ):
                failures.append(
                    "warm SIGKILL->first-step not < cold: "
                    f"{warm['sigkill_to_first_step_s']} vs "
                    f"{cold['sigkill_to_first_step_s']}"
                )

        ckpts = sorted(
            f
            for f in os.listdir(os.path.join(work, "models_warm"))
            if f.endswith(".ckpt")
        ) if os.path.isdir(os.path.join(work, "models_warm")) else []
        if ckpts:
            pkg = os.path.join(work, "package")
            os.environ["DCT_COMPILE_CACHE"] = "on"
            os.environ["DCT_COMPILE_CACHE_WARM_SIZES"] = ",".join(
                str(s) for s in spinup.FIRST_SCORE_SIZES
            )
            generate_score_package(
                os.path.join(work, "models_warm", ckpts[0]), pkg
            )
            cold_s = spinup.measure_first_score(pkg, cache_on=False)
            warm_s = spinup.measure_first_score(pkg, cache_on=True)
            print(f"first-score cold={cold_s} warm={warm_s}")
            if cold_s is None or warm_s is None:
                failures.append("first-score measurement failed")
            elif not warm_s < cold_s:
                failures.append(
                    f"warm first-score not < cold: {warm_s} vs {cold_s}"
                )
        else:
            failures.append("warm run produced no checkpoint to package")

    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print("compile-cache smoke: all gates hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())

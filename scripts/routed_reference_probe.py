#!/usr/bin/env python3
"""The routed driver's two-part reference check alone, on the chip, at a
cell's published widths and its own traffic, against two references.

    chiprun -- python scripts/routed_reference_probe.py \\
        --workload moonlight_16b_ep8.fit_seq8192 --seeds 2147480011 7

For each seed: the data, the trainer and its seeded parameters exactly as
``benchmark/drivers/fit_routed.py`` makes them, then its
``_reference_check`` (teacher-forced logits and loss, then the choice
against near-ties) twice: against the cell's plain float32 reference, which
has to come out ``ok``, and against the same reference given parameters and
inputs rounded to float8 (e4m3), the nearest precision below the bf16 the
configuration computes in, which has to FAIL at least one limit: a
comparison that passes both cannot tell a wrong precision from a right one.
Prints one JSON line a seed with both verdicts and every distance beside
its limit. No ``Trainer.fit`` runs; a seed takes about two minutes.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def float8_reference(ref):
    """``ref`` with ``forward`` fed float8-rounded parameters and inputs."""
    import jax
    import ml_dtypes
    import numpy as np

    def rounded(a):
        a = np.asarray(a)
        if a.dtype.kind != "f":
            return a
        return a.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)

    def forward(params, x, config, routing=None):
        return ref.forward(
            jax.tree.map(rounded, params), rounded(x), config,
            routing=routing)

    out = types.SimpleNamespace(**vars(ref))
    out.forward = forward
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[2147480011])
    args = ap.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu":
        print("routed_reference_probe compares bf16 on a TPU; JAX selected "
              f"{jax.devices()[0].platform!r}", file=sys.stderr)
        return 2

    from benchmark import manifest as mf
    from benchmark.drivers import fit_routed as driver
    from dct_tpu.config import RunConfig
    from dct_tpu.tracking import get_tracker
    from dct_tpu.train.trainer import Trainer

    _cell, config, traffic = mf.load_cell(mf.load_manifest(), args.workload)
    plan = driver.Plan(config, traffic, len(jax.devices()))
    load_module = mf.load_module
    ok = True
    for seed in args.seeds:
        work = os.path.join(ROOT, "build", "reference_probe")
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work)
        env = plan.env(work, driver._etl(work, plan.rows, seed), work)
        env["DCT_SEED"] = seed
        line = {"workload": args.workload, "seed": seed, "limits": {
            "logit_rel_err": driver.LOGIT_TOL, "loss_rel_err": driver.LOSS_TOL,
            "routing_widest_disagreeing_margin": driver.TIE_WIDTH,
            "routing_disagree_share": driver.MAX_DISAGREE}}
        with driver.env_overlay(env):
            cfg = RunConfig.from_env()
            trainer = Trainer(cfg, tracker=get_tracker(
                tracking_uri=cfg.tracking.tracking_uri,
                experiment=cfg.tracking.experiment))
            for name, wrap in (("float32", lambda m: m),
                               ("float8", float8_reference)):
                mf.load_module = lambda p, n, w=wrap: w(load_module(p, n))
                try:
                    line[name] = driver._reference_check(
                        cfg, trainer, plan, config)
                finally:
                    mf.load_module = load_module
        ok &= line["float32"]["ok"] and not line["float8"]["ok"]
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

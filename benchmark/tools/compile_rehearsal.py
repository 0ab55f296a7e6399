#!/usr/bin/env python3
"""Compile a fit cell's epoch program for a described v5e:2x2, without a chip.

    JAX_PLATFORMS=cpu python benchmark/tools/compile_rehearsal.py \
        [--workload sc2_3b.fit_seq4096_e40] [--layers 4 3] [--chips 1 4]

Lowers ``make_epoch_train_eval_step`` exactly as the pipelined trainer
builds it (state not donated, stacks donated, grad norms on) on shapes only,
for one described chip and for mesh ``data=4``, and prints the TPU
compiler's ``memory_analysis()``, the number of Mosaic custom calls and the
collectives it put in. Nothing runs: this says what fits and what is
refused, never how fast. It counts one program, not what else the trainer
keeps resident (the previous span's state while its checkpoint is written).

The program asks ``jax.default_backend()`` whether to use the Mosaic kernel
and sees the CPU here, so this script steers that one answer; nothing else
of the program is touched.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def rehearse(workload: str, layers: int, chips: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from benchmark import manifest as mf
    from benchmark.drivers.fit import Plan, env_overlay
    from dct_tpu.config import RunConfig
    from dct_tpu.models.registry import get_model
    from dct_tpu.ops import attention
    from dct_tpu.parallel.mesh import AXES, stacked_batch_sharding
    from dct_tpu.parallel.sharding_rules import state_shardings
    from dct_tpu.train.state import create_train_state
    from dct_tpu.train.steps import make_epoch_train_eval_step

    attention.flash_interpret_mode = lambda: False  # Mosaic, not interpret
    manifest = mf.load_manifest()
    _cell, config, traffic = mf.load_cell(manifest, workload)
    traffic = {**traffic, "mesh": {"data": chips}}
    plan = Plan(config, traffic, chips)
    env = plan.env("/nonexistent", "/nonexistent", "/nonexistent")
    env["DCT_N_LAYERS"] = layers
    with env_overlay(env):
        cfg = RunConfig.from_env()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(
        np.asarray(topo.devices[:chips]).reshape(chips, 1, 1, 1), AXES)
    model = get_model(
        cfg.model, input_dim=plan.input_dim, compute_dtype=jnp.bfloat16,
        mesh=mesh,
    )
    seq, g = plan.seq_len, plan.global_batch
    state = jax.eval_shape(lambda: create_train_state(
        model, input_dim=plan.input_dim, lr=cfg.train.lr,
        seed=cfg.train.seed, example_shape=(1, seq, plan.input_dim),
        grad_clip_norm=cfg.train.grad_clip_norm,
        optimizer=cfg.train.optimizer,
    ))
    shardings = state_shardings(state, mesh, family=cfg.model.name)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, shardings,
    )
    stack = stacked_batch_sharding(mesh)

    def stacks(steps):
        return (
            jax.ShapeDtypeStruct(
                (steps, g, seq, plan.input_dim), jnp.float32, sharding=stack),
            jax.ShapeDtypeStruct((steps, g, seq), jnp.int32, sharding=stack),
            jax.ShapeDtypeStruct((steps, g), jnp.float32, sharding=stack),
        )

    fused = make_epoch_train_eval_step(
        donate=False, accum_steps=1, donate_stacks=True, with_grad_norms=True)
    compiled = fused.lower(
        state, *stacks(plan.steps), *stacks(plan.val_batches)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    state_bytes = sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in jax.tree.leaves(state))
    collectives = {
        k: text.count(f" {k}(") + text.count(f" {k}-start(")
        for k in ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute")
    }
    return {
        "workload": workload, "layers": layers, "chips": chips,
        "argument_gb": mem.argument_size_in_bytes / 1e9,
        "output_gb": mem.output_size_in_bytes / 1e9,
        "temp_gb": mem.temp_size_in_bytes / 1e9,
        "alias_gb": mem.alias_size_in_bytes / 1e9,
        "program_total_gb": (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 1e9,
        "state_gb": state_bytes / 1e9,
        "mosaic_custom_calls": text.count("tpu_custom_call"),
        "collectives": {k: v for k, v in collectives.items() if v},
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="sc2_3b.fit_seq4096_e40")
    ap.add_argument("--layers", type=int, nargs="+", default=[4, 3])
    ap.add_argument("--chips", type=int, nargs="+", default=[1, 4])
    args = ap.parse_args()
    import json

    for chips in args.chips:
        for layers in args.layers:
            try:
                print(json.dumps(rehearse(args.workload, layers, chips)),
                      flush=True)
            except Exception as e:  # noqa: BLE001 - the refusal is the result
                print(json.dumps({
                    "workload": args.workload, "layers": layers,
                    "chips": chips, "refused": f"{type(e).__name__}: "
                    + str(e)[:600]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

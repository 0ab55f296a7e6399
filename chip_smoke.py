#!/usr/bin/env python3
"""chip_smoke.py — the main path, once, on the TPU, with its results checked.

    python chip_smoke.py

One process drives what a user drives, through the same entry points:
seeded synthetic CSV -> the ETL job's function -> ``Trainer.fit`` entered the
way ``jobs/train_tpu.py`` enters it (``RunConfig.from_env``, both checkpoint
tiers, tracker, events) -> ``generate_score_package`` -> a local rollout
endpoint served by the jitted scorer (``DCT_SERVE_ENGINE=jax``) answering
``POST /score``. Two models: the parity MLP (the control) and
``weather_transformer_causal`` at the widest configuration the repo records
(d_model 512, 8 heads, 4 layers, d_ff 2048, seq 1024, per-device batch 32,
bf16 compute). Then the Pallas kernel table, and on a multi-chip host the
multi-device legs.

It refuses any platform but ``tpu`` (``JAX_PLATFORMS=cpu python
chip_smoke.py`` exits 2), raises out of the first phase that fails, writes
only under ``chiprun_out/chip_smoke/`` (plus the compile cache the resolver
names and the native plane's own ``build/``), starts no child that needs the
chip, and prints one JSON object as its last line. The times it prints are
information for whoever sizes the benchmark, not benchmark metrics.

The phases are importable: ``tests/test_platform.py`` drives the same control
flow at :data:`TOY` size on CPU with ``DCT_FLASH=interpret``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import sys
import threading
import time
import urllib.error
import urllib.request

_REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

OUT_ROOT = os.path.join(_REPO_ROOT, "chiprun_out", "chip_smoke")

#: One bf16 ulp at unit scale. f32 GEMMs take bf16 operand passes on the
#: TPU at DEFAULT matmul precision (no code here sets another), and the
#: transformer trains and the kernels run in bf16 outright — so every
#: on-chip comparison below is stated in multiples of this.
BF16_EPS = 2.0 ** -8


@dataclasses.dataclass(frozen=True)
class Size:
    """Everything a run is sized by. ``transformer`` holds ModelConfig
    fields; ``kernels`` rows are (name, B, H, H_kv, T, D, window[, value
    width where it is not D]);
    ``fit_env`` joins every fit's environment; ``dryrun_legs`` runs
    ``__graft_entry__.dryrun_multichip`` among the multi-device legs."""

    rows: int
    mlp_epochs: int
    transformer: dict
    tf_batch: int
    tf_epochs: int
    kernels: tuple
    ring_t: int
    ring_d: int
    fit_env: dict = dataclasses.field(default_factory=dict)
    dryrun_legs: bool = True


#: The real size. rows: windows = rows - seq_len; the contiguous split
#: keeps 80% for training and drops a seq_len gap before validation, so
#: 6800 rows leave ~4.6k train windows (144 steps of 32 on one chip, 36
#: of 128 on four) and ~130 validation windows. One staged epoch is
#: ~115 MB of HBM — room to spare on a 16 GB chip.
FULL = Size(
    rows=6800,
    mlp_epochs=3,
    transformer=dict(
        d_model=512, n_heads=8, n_layers=4, d_ff=2048, seq_len=1024,
    ),
    tf_batch=32,
    tf_epochs=2,
    kernels=(
        ("d64_T1024_causal", 4, 8, 8, 1024, 64, None),
        ("d128_T4096_causal", 1, 4, 4, 4096, 128, None),
        ("d128_T4096_window2048", 1, 4, 4, 4096, 128, 2048),
        ("gqa_32q_4kv_d128_T1024", 1, 32, 4, 1024, 128, None),
        # The benchmark's two attention shapes (BENCHMARK.json, sc2_3b
        # at 4,096 and at 512 positions), on the tiles the cells run.
        ("sc2_3b_seq4096", 2, 24, 2, 4096, 128, 4096),
        ("sc2_3b_seq512", 16, 24, 2, 512, 128, 4096),
        # lfm2_24b_ep8.fit_seq8192_balanced: head size 64, 8,192 positions,
        # full causal, 4 query heads a key-value head. The kernels run the
        # cell's whole grid; the comparator takes it 8 query heads at a
        # time (_comparator_kv_heads).
        ("lfm2_seq8192", 1, 32, 8, 8192, 64, None),
        # moonlight_16b_ep8.fit_seq8192: latent attention, queries and
        # keys 192 wide against values of 128 (the eighth field).
        ("moonlight_seq8192", 1, 16, 16, 8192, 192, None, 128),
    ),
    ring_t=2048,
    ring_d=128,
)

#: The same control flow at a size the CPU interpreter finishes quickly:
#: seq 256 is the shortest the policy sends to flash, and most of the
#: windows go to validation so the seq_len gap leaves some (the split
#: needs windows > (seq_len + global batch) / val_fraction).
TOY = Size(
    rows=640,
    mlp_epochs=3,
    transformer=dict(
        d_model=16, n_heads=1, n_layers=1, d_ff=32, seq_len=256,
    ),
    tf_batch=1,
    tf_epochs=2,
    kernels=(
        ("d64_T256_causal", 1, 2, 2, 256, 64, None),
        ("d64_T256_window128", 1, 2, 2, 256, 64, 128),
        ("d64_T256_window256", 1, 2, 2, 256, 64, 256),
        ("gqa_4q_2kv_d64_T256", 1, 4, 2, 256, 64, None),
        ("d48_values32_T256", 1, 2, 2, 256, 48, None, 32),
    ),
    ring_t=512,
    ring_d=64,
    fit_env={"DCT_VAL_FRACTION": 0.7},
    dryrun_legs=False,
)


class SmokeFailure(AssertionError):
    """A phase produced something wrong. Never caught here."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def passed(phase: str, **facts) -> None:
    detail = " ".join(f"{k}={v}" for k, v in facts.items())
    print(f"[chip_smoke] PASS {phase}: {detail}", flush=True)


@contextlib.contextmanager
def env_overlay(overrides: dict):
    """Set env vars for one phase and put the old values back after."""
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update({k: str(v) for k, v in overrides.items()})
    try:
        yield
    finally:
        for k, old in saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old


# ----------------------------------------------------------------------
# phases


def phase_device() -> dict:
    """What JAX selected, and the versions that selected it."""
    import jax
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — informational only
        libtpu = "unknown"
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print(
        f"[chip_smoke] platform={device['platform']} "
        f"device_kind={device['kind']!r} device_count={device['count']} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}",
        flush=True,
    )
    return device


def phase_native() -> str:
    """Rebuild the C++ data plane from the tracked source (the copy on
    the chip machine may carry a stale ``build/``) and say which plane
    will serve the loader."""
    from dct_tpu import native
    from dct_tpu.native.build import build

    so = build(force=True)
    plane = "native" if so and native.available() else "numpy"
    passed("native data plane", loader=plane, built=bool(so))
    return plane


def phase_etl(work: str, rows: int, seed: int = 0) -> str:
    from dct_tpu.data.dataset import load_processed_dataset
    from dct_tpu.data.synthetic import generate_weather_csv
    from dct_tpu.etl.preprocess import preprocess_csv_to_parquet

    csv = os.path.join(work, "raw", "weather.csv")
    processed = os.path.join(work, "processed")
    generate_weather_csv(csv, rows=rows, seed=seed)
    preprocess_csv_to_parquet(csv, processed)
    data = load_processed_dataset(processed)
    check(len(data) == rows, f"ETL kept {len(data)} of {rows} rows")
    passed("ETL", rows=len(data), features=data.input_dim)
    return processed


def _run_env(work: str, tag: str, processed: str) -> dict:
    """The paths one fit writes to — all under the smoke's work dir."""
    run = os.path.join(work, tag)
    return {
        "DCT_PROCESSED_DIR": processed,
        "DCT_MODELS_DIR": os.path.join(run, "models"),
        "DCT_TRACKING_DIR": os.path.join(run, "mlruns"),
        "DCT_EVENTS_DIR": os.path.join(run, "events"),
        "DCT_HEARTBEAT_DIR": os.path.join(run, "heartbeats"),
        # Compile cache on, placed by the one resolver; the trainer's AOT
        # artifacts sit beside it so a second run finds them again (the
        # models dir is wiped with the rest of the work dir).
        "DCT_COMPILE_CACHE": "on",
        "DCT_COMPILE_CACHE_AOT_DIR": os.path.join(_cache_dir(), "aot"),
        "DCT_COMPILE_CACHE_WARM_SIZES": "1,4",
    }


def _cache_dir() -> str:
    from dct_tpu.compilecache import resolve_cache_dir

    return resolve_cache_dir({**os.environ, "DCT_COMPILE_CACHE": "on"})


def _events(events_dir: str) -> list:
    out = []
    with open(os.path.join(events_dir, "events.jsonl")) as f:
        for line in f:
            out.append(json.loads(line))
    return out


def phase_fit(work: str, tag: str, processed: str, overrides: dict) -> dict:
    """One training run, entered like ``jobs/train_tpu.py`` with
    ``overrides`` in its environment. Returns the facts the later phases
    need."""
    import numpy as np

    from dct_tpu.config import RunConfig
    from dct_tpu.parallel.distributed import initialize_from_env
    from dct_tpu.parallel.mesh import layout_of
    from dct_tpu.train.trainer import Trainer

    env = {**_run_env(work, tag, processed), **overrides}
    entries_before = _cache_entries()
    with env_overlay(env):
        cfg = RunConfig.from_env()
        initialize_from_env(cfg.dist)
        trainer = Trainer(cfg)
        t0 = time.perf_counter()
        result = trainer.fit()
        wall = time.perf_counter() - t0
    mesh = {k: int(v) for k, v in trainer.mesh.shape.items()}
    hist = result.history
    losses = [h["train_loss"] for h in hist]
    check(len(hist) == cfg.train.epochs, f"{tag}: ran {len(hist)} epochs")
    check(
        all(np.isfinite(h["train_loss"]) and np.isfinite(h["val_loss"])
            for h in hist),
        f"{tag}: non-finite loss in {hist}",
    )
    check(
        losses[-1] < losses[0],
        f"{tag}: train loss did not fall: {losses}",
    )
    # The files jobs/train_tpu.py requires, in both tiers.
    models = env["DCT_MODELS_DIR"]
    check(
        result.best_model_path and os.path.exists(result.best_model_path),
        f"{tag}: no best checkpoint at {result.best_model_path}",
    )
    check(os.path.exists(result.last_model_path), f"{tag}: no last.ckpt")
    state_dir = os.path.join(models, "train_state", "p0")
    check(
        os.path.isdir(state_dir) and os.listdir(state_dir),
        f"{tag}: resume tier empty at {state_dir}",
    )
    place = check_placement(tag, mesh, result)
    evs = _events(env["DCT_EVENTS_DIR"])
    windows = [e for e in evs if e.get("event") == "compile.window"]
    misses = [e for e in evs if e.get("event") == "compile.cache_miss"]
    check(not misses, f"{tag}: loud AOT misses: {misses}")
    ends = [e["ts"] for e in evs if e.get("event") == "epoch_end"]
    epoch_s = [round(b - a, 2) for a, b in zip(ends, ends[1:])]
    passed(
        f"fit {tag}",
        mesh=mesh, layout=layout_of(trainer.mesh), model=cfg.model.name,
        epochs=len(hist),
        train_loss=[round(x, 4) for x in losses],
        val_loss=round(result.val_loss, 4), val_acc=round(result.val_acc, 4),
        state_devices=place["state"], batch_devices=place["batch"],
        aot={e["program"]: e.get("cache") for e in windows},
        compile_s=round(sum(e.get("seconds", 0.0) for e in windows), 1),
        new_cache_entries=_cache_entries() - entries_before,
        later_epochs_s=epoch_s, wall_s=round(wall, 1),
    )
    passed(
        f"checkpoint files {tag}",
        best=os.path.basename(result.best_model_path),
        last=os.path.basename(result.last_model_path),
        train_state=len(os.listdir(state_dir)),
    )
    return {
        "cfg": cfg, "result": result, "env": env, "mesh": mesh,
    }


def check_placement(tag: str, mesh: dict, result) -> dict:
    """Every device of the mesh — and the mesh covers every device JAX
    has, or ``make_mesh`` refuses it — must have held shards of the train
    state and of the batch. A run confined to device 0 fails here."""
    import math

    n_dev = math.prod(mesh.values())
    place = result.placement
    check(
        len(place["state"]) == n_dev and len(place["batch"]) == n_dev,
        f"{tag}: mesh {mesh} left devices idle: state on "
        f"{place['state']}, batch on {place['batch']} of {n_dev} devices",
    )
    return place


def assert_flash_path(model_cfg, *, batch: int, input_dim: int) -> None:
    """The attention path the program resolves for this model must be the
    Pallas kernel, compiled (not interpreted) unless DCT_FLASH=interpret
    asked for the interpreter — read off the traced step, not assumed."""
    import jax
    import jax.numpy as jnp

    from dct_tpu.models.registry import get_model
    from dct_tpu.ops.attention import (
        flash_interpret_mode,
        select_attention_path,
    )

    path = select_attention_path(model_cfg.seq_len)
    check(path == "flash", f"attention path resolved to {path!r}, not flash")
    interpret = flash_interpret_mode()
    explicit = os.environ.get("DCT_FLASH", "").strip().lower() == "interpret"
    check(
        interpret is False or explicit,
        f"flash would run interpret={interpret} without DCT_FLASH=interpret",
    )
    model = get_model(model_cfg, input_dim=input_dim,
                      compute_dtype=jnp.bfloat16)
    x = jnp.zeros((batch, model_cfg.seq_len, input_dim), jnp.float32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x[:1])
    )

    def loss(p):
        return model.apply(p, x, train=False).sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(params))
    n_calls = jaxpr.count("pallas_call")
    # Forward + dK/dV + dQ per layer.
    check(
        n_calls >= 3 * model_cfg.n_layers,
        f"traced step holds {n_calls} pallas_call(s), expected "
        f">= {3 * model_cfg.n_layers} (fwd + both FA2 backward kernels)",
    )
    passed(
        "attention path", path=path, interpret=bool(interpret),
        pallas_calls=n_calls,
    )


def _post(url: str, payload: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=300) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        raise SmokeFailure(
            f"POST /score answered {e.code}: {e.read()[:2000]!r}"
        ) from e


def phase_serve(work: str, tag: str, fit: dict, *, platform: str) -> None:
    """Package the best checkpoint, deploy it to a local rollout endpoint,
    serve it in-process with the jitted scorer, and compare its answers
    with the numpy twin scoring the same package."""
    import numpy as np

    from dct_tpu.config import ServingConfig
    from dct_tpu.data.dataset import load_processed_dataset
    from dct_tpu.deploy.local import LocalEndpointClient
    from dct_tpu.serving.runtime import score_payload
    from dct_tpu.serving.score_gen import generate_score_package
    from dct_tpu.serving.server import make_endpoint_server

    env = fit["env"]
    cfg = fit["cfg"]
    pkg = os.path.join(work, tag, "package")
    state = os.path.join(work, tag, "endpoint_state.json")
    entries_before = _cache_entries()
    with env_overlay({**env, "DCT_SERVE_ENGINE": "jax"}):
        generate_score_package(fit["result"].best_model_path, pkg)
        for name in ("model.npz", "model_meta.json", "score.py"):
            check(os.path.exists(os.path.join(pkg, name)), f"no {name}")
        # Packaging warms the scorer at batch 1 and 4. A fresh compile is
        # published into <package>/aot; one the persistent cache served
        # is not (it is in that cache already).
        aot_dir = os.path.join(pkg, "aot")
        warmed = sorted(os.listdir(aot_dir)) if os.path.isdir(aot_dir) else []
        client = LocalEndpointClient(state_path=state)
        client.create_endpoint("smoke")
        client.deploy("smoke", "blue", pkg)
        client.set_traffic("smoke", {"blue": 100})
        serving = ServingConfig.from_env()
        check(serving.engine == "jax", f"serving engine {serving.engine!r}")
        server = make_endpoint_server(
            "smoke", state_path=state, serving=serving
        )
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        try:
            url = f"http://127.0.0.1:{server.server_address[1]}/score"
            data = load_processed_dataset(env["DCT_PROCESSED_DIR"])
            seq = (
                cfg.model.seq_len if cfg.model.name != "weather_mlp" else None
            )
            weights, meta = client.load_slot("smoke", "blue")
            worst = 0.0
            shape = None
            # Batch sizes 1, 4 (both warmed) and 3 (padded to 4).
            for i, n in enumerate((1, 4, 3)):
                if seq is None:
                    x = data.features[i * 8:i * 8 + n]
                else:
                    x = np.stack([
                        data.features[j:j + seq]
                        for j in range(i * 8, i * 8 + n)
                    ])
                got = np.asarray(
                    _post(url, {"data": x.tolist()})["probabilities"]
                )
                want = np.asarray(
                    score_payload(weights, meta, x.tolist())["probabilities"]
                )
                check(got.shape == want.shape, f"{got.shape} != {want.shape}")
                check(np.isfinite(got).all(), f"{tag}: non-finite scores")
                check(
                    np.allclose(got.sum(-1), 1.0, atol=1e-3),
                    f"{tag}: probabilities do not sum to 1",
                )
                worst = max(worst, float(np.abs(got - want).max()))
                shape = got.shape
        finally:
            server.shutdown()
            server.server_close()
            thread.join(timeout=30)
    check(not thread.is_alive(), "server thread did not stop")
    # f32 package, DEFAULT matmul precision: exact-ish f32 on CPU (the
    # band the CPU tests pin), bf16 operand passes on the TPU.
    tol = 2e-5 if platform == "cpu" else 8 * BF16_EPS
    check(
        worst <= tol,
        f"{tag}: jitted scorer is {worst:.3g} from the numpy twin "
        f"(tolerance {tol:.3g})",
    )
    evs = _events(env["DCT_EVENTS_DIR"])
    hits = [
        e for e in evs if e.get("event") == "compile.cache_hit"
        and e.get("program") == "serve_scorer"
    ]
    misses = [e for e in evs if e.get("event") == "compile.cache_miss"]
    check(not misses, f"{tag}: loud AOT misses while serving: {misses}")
    new_entries = _cache_entries() - entries_before
    check(
        len(hits) == len(warmed),
        f"{tag}: the package carries {warmed} but the served scorer "
        f"loaded {len(hits)} of them",
    )
    check(
        len(warmed) == 2 or new_entries == 0,
        f"{tag}: packaging published {len(warmed)} of 2 scorer artifacts "
        f"yet the persistent cache grew by {new_entries} entries — "
        "some scorer compile was neither published nor served from cache",
    )
    passed(
        f"serve {tag}",
        engine="jax", requests=3, probs_shape=shape,
        max_abs_delta_vs_numpy=f"{worst:.3g}", tolerance=f"{tol:.3g}",
        precision="f32 params, DEFAULT matmul precision"
        + ("" if platform == "cpu" else " (bf16 passes on TPU)"),
        aot_artifacts=len(warmed), aot_hits=len(hits),
        new_cache_entries=new_entries,
    )


def _rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-6))


def _fwd_bwd(fn):
    """jit of (q, k, v, g) -> (o, dq, dk, dv) for an attention ``fn``."""
    import jax

    def run(q, k, v, g):
        o, vjp = jax.vjp(fn, q, k, v)
        return (o, *vjp(g))

    return jax.jit(run)


def _errors(got, want) -> dict:
    return {
        name: _rel_err(a, w)
        for name, a, w in zip(("o", "dq", "dk", "dv"), got, want)
    }


#: Kernel-vs-blockwise tolerance: both run bf16 operands with f32
#: accumulation on the same device, so they differ by the rounding of p
#: and of the outputs — a few bf16 ulps of the tensor's scale.
KERNEL_TOL = 8 * BF16_EPS


def _comparator_kv_heads(b: int, h: int, h_kv: int, t: int) -> int:
    """Key-value heads the blockwise comparator takes at once: its backward
    holds several [B, heads, T, T] f32 buffers (23.7 GB at 32 heads of
    8,192 positions), so the most whose query heads keep B x heads x T x T
    within 3 x 2^28 elements (5.9 GB measured at 8 heads of 8,192)."""
    group = h // h_kv
    return max(
        c for c in range(1, h_kv + 1)
        if h_kv % c == 0 and (c == 1 or b * c * group * t * t <= 3 << 28)
    )


def phase_kernels(cases) -> None:
    """Each case: flash forward + both FA2 backward kernels, against
    ``blockwise_attention`` on the same device in the same dtype."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dct_tpu.ops.attention import (
        blockwise_attention,
        flash_interpret_mode,
    )
    from dct_tpu.ops.pallas_attention import flash_attention, flash_tiles

    interpret = flash_interpret_mode()
    check(interpret is not None, "flash is off on this backend")
    for name, b, h, h_kv, t, d, window, *rest in cases:
        d_v = rest[0] if rest else d
        rng = np.random.default_rng(0)
        q, k, v, g = (
            jnp.asarray(
                rng.standard_normal((b, heads, t, width)), jnp.bfloat16)
            for heads, width in ((h, d), (h_kv, d), (h_kv, d_v), (h, d_v))
        )

        def flash(q, k, v):
            return flash_attention(
                q, k, v, causal=True, interpret=bool(interpret),
                window=window,
            )

        def block(q, k, v, window=window):
            return blockwise_attention(
                q, k, v, block_size=min(512, t), causal=True, window=window
            )

        t0 = time.perf_counter()
        got = jax.block_until_ready(_fwd_bwd(flash)(q, k, v, g))
        t_flash = time.perf_counter() - t0
        # Query head j reads key-value head j // (h / h_kv), so a run of
        # key-value heads with its query heads is an attention of its own.
        c = _comparator_kv_heads(b, h, h_kv, t)
        cq = c * (h // h_kv)
        block_fwd_bwd = _fwd_bwd(block)
        want = tuple(
            jnp.concatenate(part, axis=1) for part in zip(*(
                jax.block_until_ready(block_fwd_bwd(
                    q[:, i * cq:(i + 1) * cq], k[:, i * c:(i + 1) * c],
                    v[:, i * c:(i + 1) * c], g[:, i * cq:(i + 1) * cq]))
                for i in range(h_kv // c)))
        )
        errs = _errors(got, want)
        check(
            all(np.isfinite(np.asarray(a, np.float32)).all() for a in got),
            f"kernel {name}: non-finite output",
        )
        check(
            max(errs.values()) <= KERNEL_TOL,
            f"kernel {name}: {errs} exceeds {KERNEL_TOL:.3g} of blockwise",
        )
        band = None
        if window is not None and window < t:
            # The band must bite: on the rows past the window (the only
            # ones it changes, and small next to the first rows' scale)
            # the windowed kernel has to be far from full-causal
            # blockwise.
            causal_o = jax.jit(lambda q, k, v: block(q, k, v, None))(q, k, v)
            band = _rel_err(got[0][..., window:, :], causal_o[..., window:, :])
            check(
                band > 4 * KERNEL_TOL,
                f"kernel {name}: past the window the output is within "
                f"{band:.3g} of full causal attention — the band was not "
                "applied",
            )
        passed(
            f"kernel {name}",
            shape=(b, h, h_kv, t, d, *rest), window=window,
            tiles=flash_tiles(t, t, d, jnp.bfloat16, d_v),
            interpret=bool(interpret), comparator_heads=cq,
            rel_err={n: f"{e:.2g}" for n, e in errs.items()},
            tol=f"{KERNEL_TOL:.3g}",
            **({} if band is None else {"band_vs_full_causal": f"{band:.2g}"}),
            first_call_s=round(t_flash, 1),
        )


def phase_ring(t: int, d: int) -> None:
    """Causal ring attention over ``seq=2`` at a kernel-aligned size, so
    the contiguous AND the striped flash rings leave the JAX-level body:
    forward and backward against blockwise on the gathered arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dct_tpu.config import MeshConfig
    from dct_tpu.ops.attention import (
        blockwise_attention,
        flash_interpret_mode,
        ring_attention,
    )
    from dct_tpu.parallel.mesh import layout_of, make_mesh

    n = len(jax.devices())
    mesh = make_mesh(MeshConfig(data=n // 2, seq=2))
    interpret = flash_interpret_mode()
    check(interpret is not None, "flash is off on this backend")
    b, h = 2 * (n // 2), 4
    rng = np.random.default_rng(1)
    q, k, v, g = (
        jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.bfloat16)
        for _ in range(4)
    )

    want = jax.block_until_ready(_fwd_bwd(
        lambda q, k, v: blockwise_attention(
            q, k, v, block_size=min(512, t), causal=True
        )
    )(q, k, v, g))
    kernel_calls = {}
    for striped in (False, True):
        run = _fwd_bwd(
            lambda q, k, v, s=striped: ring_attention(
                q, k, v, mesh=mesh, causal=True, use_flash=True, striped=s
            )
        )
        kernel_calls[striped] = str(
            jax.make_jaxpr(run)(q, k, v, g)
        ).count("pallas_call")
        check(
            kernel_calls[striped] > 0,
            f"ring striped={striped}: no pallas_call in the traced ring "
            "(it fell back to the JAX-level body)",
        )
        got = jax.block_until_ready(run(q, k, v, g))
        errs = _errors(got, want)
        devices = sorted(d_.id for d_ in got[0].sharding.device_set)
        check(len(devices) == n, f"ring output lives on {devices} only")
        check(
            max(errs.values()) <= KERNEL_TOL,
            f"ring striped={striped}: {errs} exceeds {KERNEL_TOL:.3g}",
        )
        passed(
            f"flash ring {'striped' if striped else 'contiguous'}",
            mesh={k_: int(v_) for k_, v_ in mesh.shape.items()},
            layout=layout_of(mesh), T=t, t_local=t // 2, d=d, interpret=bool(interpret),
            pallas_calls=kernel_calls[striped],
            rel_err={nm: f"{e:.2g}" for nm, e in errs.items()},
            devices=devices,
        )
    # The striped body splits every shard in two half-chunk blocks: it
    # must have traced more kernel calls than the contiguous one, or the
    # layout flag was ignored.
    check(
        kernel_calls[True] > kernel_calls[False],
        f"striped ring traced {kernel_calls[True]} kernel calls, contiguous "
        f"{kernel_calls[False]} — the striped layout did not run",
    )


def phase_multichip(work: str, processed: str, size: Size) -> None:
    """The trainer on a mixed mesh (``data x seq=2`` — the causal ring
    runs), the dry-run legs on real devices, and the aligned ring."""
    import importlib.util

    import jax

    n = len(jax.devices())
    check(n % 2 == 0, f"multi-device legs need an even device count, got {n}")
    phase_fit(
        work, f"transformer_data{n // 2}_seq2", processed,
        {**_transformer_env(size), "DCT_MESH_DATA": n // 2,
         "DCT_MESH_SEQ": 2},
    )
    if size.dryrun_legs:
        spec = importlib.util.spec_from_file_location(
            "graft_entry", os.path.join(_REPO_ROOT, "__graft_entry__.py")
        )
        entry = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(entry)
        entry.dryrun_multichip(n)
        passed("dryrun_multichip legs", devices=n)
    phase_ring(size.ring_t, size.ring_d)


def _transformer_env(size: Size) -> dict:
    tf = size.transformer
    return {
        **size.fit_env,
        "DCT_MODEL": "weather_transformer_causal",
        "DCT_D_MODEL": tf["d_model"], "DCT_N_HEADS": tf["n_heads"],
        "DCT_N_LAYERS": tf["n_layers"], "DCT_D_FF": tf["d_ff"],
        "DCT_SEQ_LEN": tf["seq_len"], "DCT_BATCH_SIZE": size.tf_batch,
        "DCT_EPOCHS": size.tf_epochs, "DCT_LR": 1e-3,
        "DCT_GRAD_CLIP_NORM": 1.0,
    }


def _cache_entries() -> int:
    path = _cache_dir()
    if not os.path.isdir(path):
        return 0
    return sum(
        1 for n in os.listdir(path)
        if os.path.isfile(os.path.join(path, n))
    )


def _prune(work: str, keep_bytes: int = 1 << 20) -> None:
    """After a pass, drop what is bulky and reproducible from the seed
    (checkpoints, packages, parquet): the tool brings the output
    directory back only while it is small, and the event logs are the
    part worth reading afterwards."""
    for d, _dirs, names in os.walk(work):
        for n in names:
            path = os.path.join(d, n)
            if os.path.getsize(path) > keep_bytes:
                os.remove(path)


def run_phases(size: Size, work: str, device: dict) -> None:
    """Every phase, in order; the first failure raises out."""
    from dct_tpu import compilecache
    from dct_tpu.config import ModelConfig

    if os.path.isdir(work):
        shutil.rmtree(work)
    os.makedirs(work)
    with env_overlay({"DCT_COMPILE_CACHE": "on"}):
        cache_dir = compilecache.enable_from_env()
    placed = os.environ.get(compilecache.CACHE_DIR_ENV)
    print(
        f"[chip_smoke] compile cache: {cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if placed else 'default in checkout'}"
        f"), {_cache_entries()} entries before",
        flush=True,
    )
    # Relative defaults (logs/..., mlruns_local) must land in the work
    # dir too; the cache resolver does not depend on the working dir.
    prev_cwd = os.getcwd()
    os.chdir(work)
    try:
        phase_native()
        processed = phase_etl(work, size.rows)
        mlp = phase_fit(
            work, "mlp", processed,
            {**size.fit_env, "DCT_EPOCHS": size.mlp_epochs},
        )
        phase_serve(work, "mlp", mlp, platform=device["platform"])
        tf_cfg = ModelConfig(
            name="weather_transformer_causal", **size.transformer
        )
        assert_flash_path(tf_cfg, batch=2, input_dim=5)
        tf = phase_fit(work, "transformer", processed, _transformer_env(size))
        check(
            all(
                getattr(tf["cfg"].model, k) == v
                for k, v in size.transformer.items()
            ),
            "the fitted model is not the configured one",
        )
        phase_serve(work, "transformer", tf, platform=device["platform"])
        phase_kernels(size.kernels)
        if device["count"] > 1:
            phase_multichip(work, processed, size)
        else:
            print(
                "[chip_smoke] one device: multi-device legs not applicable",
                flush=True,
            )
    finally:
        os.chdir(prev_cwd)
    _prune(work)
    print(
        f"[chip_smoke] compile cache: {_cache_entries()} entries after",
        flush=True,
    )


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX selected platform "
            f"{dev.platform!r} ({dev.device_kind}); nothing was run",
            file=sys.stderr,
        )
        return 2
    device = phase_device()
    t0 = time.perf_counter()
    run_phases(FULL, OUT_ROOT, device)
    print(
        f"[chip_smoke] all phases passed in {time.perf_counter() - t0:.0f}s",
        flush=True,
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Batch-inference job: checkpoint + processed parquet -> predictions
parquet through the same numpy runtime the deployed score.py embeds."""

import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train(processed_dir, tmp_path, model_env=None):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "DCT_PROCESSED_DIR": processed_dir,
        "DCT_MODELS_DIR": str(tmp_path / "models"),
        "DCT_TRACKING_DIR": str(tmp_path / "runs"),
        "DCT_EPOCHS": "1",
        "DCT_BATCH_SIZE": "8",
        "DCT_BF16_COMPUTE": "0",
        **(model_env or {}),
    }
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "jobs", "train_tpu.py")],
        env=env, capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return env


@pytest.mark.slow
@pytest.mark.parametrize(
    "model_env",
    [
        None,  # flagship MLP
        {"DCT_MODEL": "weather_transformer_causal", "DCT_SEQ_LEN": "8",
         "DCT_D_MODEL": "16", "DCT_N_HEADS": "2", "DCT_N_LAYERS": "1",
         "DCT_D_FF": "32"},
    ],
)
def test_predict_job_end_to_end(processed_dir, tmp_path, model_env):
    env = _train(processed_dir, tmp_path, model_env)
    out = str(tmp_path / "pred" / "predictions.parquet")
    env["DCT_PREDICTIONS"] = out
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "jobs", "predict.py")],
        env=env, capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    df = pd.read_parquet(out)
    assert {"row", "predicted", "prob_0", "prob_1", "label"} <= set(df.columns)
    assert len(df) > 0
    np.testing.assert_allclose(
        df["prob_0"] + df["prob_1"], np.ones(len(df)), atol=1e-5
    )
    # A trained model must beat coin-flip on its own training stream.
    acc = float((df["predicted"] == df["label"]).mean())
    assert acc > 0.6, acc


@pytest.mark.slow
def test_predict_job_multi_horizon(processed_dir, tmp_path):
    """A horizon=3 causal checkpoint yields per-horizon prediction and
    probability columns; next-step `predicted` keeps the base contract."""
    env = _train(
        processed_dir, tmp_path,
        {"DCT_MODEL": "weather_transformer_causal", "DCT_SEQ_LEN": "8",
         "DCT_D_MODEL": "16", "DCT_N_HEADS": "2", "DCT_N_LAYERS": "1",
         "DCT_D_FF": "32", "DCT_HORIZON": "3"},
    )
    out = str(tmp_path / "pred" / "predictions.parquet")
    env["DCT_PREDICTIONS"] = out
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "jobs", "predict.py")],
        env=env, capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    df = pd.read_parquet(out)
    expect = {
        "row", "predicted", "label",
        "prob_h1_0", "prob_h1_1", "pred_h2", "prob_h2_0", "prob_h2_1",
        "pred_h3", "prob_h3_0", "prob_h3_1",
    }
    assert expect <= set(df.columns), sorted(df.columns)
    for h in (1, 2, 3):
        np.testing.assert_allclose(
            df[f"prob_h{h}_0"] + df[f"prob_h{h}_1"], np.ones(len(df)),
            atol=1e-5,
        )


def test_predict_job_missing_checkpoint(tmp_path, processed_dir):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "DCT_PROCESSED_DIR": processed_dir,
        "DCT_MODELS_DIR": str(tmp_path / "empty"),
    }
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "jobs", "predict.py")],
        env=env, capture_output=True, text=True,
    )
    assert r.returncode != 0
    assert "No checkpoint" in r.stderr


def test_predict_chunking_matches_single_pass(processed_dir, tmp_path):
    """Chunked scoring (review fix) must equal one whole-dataset pass."""
    env = _train(processed_dir, tmp_path)
    for chunk, sub in (("64", "a"), ("100000", "b")):
        e = dict(env)
        e["DCT_PREDICT_CHUNK"] = chunk
        e["DCT_PREDICTIONS"] = str(tmp_path / sub / "p.parquet")
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "jobs", "predict.py")],
            env=e, capture_output=True, text=True,
        )
        assert r.returncode == 0, r.stderr[-2000:]
    a = pd.read_parquet(str(tmp_path / "a" / "p.parquet"))
    b = pd.read_parquet(str(tmp_path / "b" / "p.parquet"))
    np.testing.assert_allclose(a["prob_1"], b["prob_1"], atol=1e-6)


def test_predict_picks_newest_best_by_mtime(processed_dir, tmp_path):
    """Review regression: an older-but-lexicographically-later best file
    must not win over the newest best checkpoint."""
    import time

    env = _train(processed_dir, tmp_path)
    models = str(tmp_path / "models")
    import glob as _glob
    import shutil

    best = _glob.glob(os.path.join(models, "weather-best-*.ckpt"))[0]
    decoy = os.path.join(models, "weather-best-99-9.99.ckpt")
    shutil.copy2(best, decoy)
    os.utime(decoy, (time.time() - 3600, time.time() - 3600))  # older
    out = str(tmp_path / "pred2" / "p.parquet")
    env["DCT_PREDICTIONS"] = out
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "jobs", "predict.py")],
        env=env, capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert os.path.basename(best) in r.stdout, r.stdout


@pytest.mark.slow
@pytest.mark.parametrize(
    "model_env",
    [
        None,  # flagship MLP
        {"DCT_MODEL": "weather_transformer", "DCT_SEQ_LEN": "8",
         "DCT_D_MODEL": "16", "DCT_N_HEADS": "2", "DCT_D_FF": "32"},
        # Causal family: the jax engine must slice the last position to
        # match the numpy twin's forecast contract.
        {"DCT_MODEL": "weather_transformer_causal", "DCT_SEQ_LEN": "8",
         "DCT_D_MODEL": "16", "DCT_N_HEADS": "2", "DCT_N_LAYERS": "1",
         "DCT_D_FF": "32"},
        # Multi-horizon causal: probs come back [N, H, C] in BOTH
        # engines (per-horizon prob/pred columns).
        {"DCT_MODEL": "weather_transformer_causal", "DCT_SEQ_LEN": "8",
         "DCT_D_MODEL": "16", "DCT_N_HEADS": "2", "DCT_N_LAYERS": "1",
         "DCT_D_FF": "32", "DCT_HORIZON": "3"},
    ],
    ids=["mlp", "transformer", "causal", "causal_h3"],
)
def test_predict_jax_engine_matches_numpy(processed_dir, tmp_path, model_env):
    """DCT_PREDICT_ENGINE=jax (mesh-sharded accelerator scoring) must
    match the numpy serving twin to f32 tolerance — including across
    the fixed-chunk padding of the last piece."""
    env = _train(processed_dir, tmp_path, model_env)
    outs = {}
    for engine in ("numpy", "jax"):
        out = str(tmp_path / f"pred_{engine}.parquet")
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "jobs", "predict.py")],
            env={**env, "DCT_PREDICTIONS": out,
                 "DCT_PREDICT_ENGINE": engine,
                 "DCT_PREDICT_CHUNK": "96"},  # forces a padded tail
            capture_output=True, text=True,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        outs[engine] = pd.read_parquet(out)
    a, b = outs["numpy"], outs["jax"]
    assert (a["row"] == b["row"]).all()
    prob_cols = [c for c in a.columns if c.startswith("prob")]
    assert prob_cols
    for c in prob_cols:
        np.testing.assert_allclose(a[c], b[c], atol=2e-5)
    assert (a["predicted"] == b["predicted"]).mean() > 0.999


def test_predict_unknown_engine_fails_loudly(processed_dir, tmp_path):
    env = _train(processed_dir, tmp_path)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "jobs", "predict.py")],
        env={**env, "DCT_PREDICT_ENGINE": "cuda"},
        capture_output=True, text=True,
    )
    assert r.returncode != 0
    assert "DCT_PREDICT_ENGINE" in r.stderr

"""North-star val-loss parity (BASELINE.md protocol row 1).

The parity band: the reference's exact end-to-end protocol — 10 epochs,
batch 4, Adam lr 0.01, seeded 80/20 random split, MLP 5->64(ReLU,
dropout 0.2)->2 (reference jobs/train_lightning_ddp.py:14,57-61,88,
117,122,132) — run in torch AND through the product ``Trainer.fit()``
on the same parquet must converge to the same val_loss. RNG streams
differ across frameworks (shuffle order, dropout masks), so the claim
is the converged band, not a bitwise trajectory (test_train_step.py
pins the bitwise single-step parity separately).
"""

import importlib
import json
import os
import tempfile

import pytest


@pytest.fixture()
def bench_mod(tmp_path, monkeypatch):
    monkeypatch.setenv("DCT_BENCH_ROWS", "4000")
    monkeypatch.setenv(
        "DCT_BENCH_PARTIAL", str(tmp_path / "BENCH_PARTIAL.json")
    )
    import bench

    bench = importlib.reload(bench)
    yield bench
    monkeypatch.undo()
    importlib.reload(bench)


@pytest.mark.slow
def test_val_loss_parity_band(bench_mod, tmp_path):
    data = bench_mod._prepare_data(str(tmp_path))
    rec = {}
    bench_mod._LIVE_RECORD = rec
    try:
        out = bench_mod.bench_val_parity(data, str(tmp_path))
    finally:
        bench_mod._LIVE_RECORD = None
    # Both stacks must actually have learned the task...
    assert out["torch_val_acc"] > 0.8
    assert out["jax_val_acc"] > 0.8
    # ...and converge into the same val_loss band. Observed on this
    # protocol: |diff| ~ 8e-4; the band leaves ~35x headroom while still
    # catching any systematic training divergence (a dropout/optimizer/
    # split bug moves val_loss by >> 0.03 at loss ~0.3).
    assert out["abs_diff"] < 0.03, out
    # The leg must have streamed into the partial record the moment it
    # was measured (unstreamed values die with a later failure).
    with open(bench_mod._PARTIAL_PATH) as f:
        on_disk = json.load(f)
    assert on_disk["scaled_legs"]["val_parity"]["abs_diff"] == out["abs_diff"]

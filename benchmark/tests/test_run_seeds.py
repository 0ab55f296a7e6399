"""``tools/run_seeds.py``: what it keeps of a run, on hand-made files, and
one run of a command that finds no chip."""

import json
import os
import sys

from benchmark import manifest as mf

rs = mf.load_module(f"{mf.BENCH_DIR}/tools/run_seeds.py", "bench_run_seeds")


def test_epoch_counters_and_the_table_line(tmp_path):
    run = tmp_path / "build/benchmark/some.cell/mlruns/exp/run1"
    run.mkdir(parents=True)
    rows = [
        {"step": 580, "val_loss": 0.4, "moe_rows": 4.75e6,
         "moe_rows_bound": 9.5e6, "moe_rows_3": 6e5,
         "moe_rows_max_over_mean": 1.05, "moe_rows_overflowed": 0.0},
        {"step": 290, "val_loss": 0.5, "moe_rows": 4.0e6,
         "moe_rows_bound": 9.5e6, "moe_rows_max_over_mean": 1.3,
         "moe_bias_abs_max": 0.5, "moe_rows_overflowed": 0.0},
        {"step": 3, "lr": 1e-4},  # a row that closes no epoch
    ]
    (run / "metrics.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in rows))
    counters = rs._epoch_counters(str(tmp_path), "some.cell")
    assert [c["step"] for c in counters] == [290, 580]
    assert "moe_rows_3" not in counters[1] and "val_loss" not in counters[1]
    line = rs.summary({
        "seed": 7, "rc": 0, "wall_s": 131.26, "epoch_counters": counters,
        "trace": 1,
        "result": {"correct": True, "device": {"memory_peak_bytes": 11},
                   "metrics": {"step.mfu": {"value": 33.7, "unit": "%"}}},
        "notes": {"why_not_correct": [], "end_to_end_in_this_run": {
            "fit_tokens_per_s": 59000.5, "setup_s": 88.1,
            "epoch_seconds": [40.1]}}})
    assert (line["correct"], line["fit_tokens_per_s"], line["setup_s"]) == (
        True, 59000.5, 88.1)
    assert line["why_not_correct"] is None and line["memory_peak_bytes"] == 11
    assert line["metrics"] == {"step.mfu": 33.7}
    assert line["epoch0"]["bound_over_routed"] == 9.5 / 4.0
    assert line["epoch1"] == {
        "rows": 4.75e6, "max_over_mean": 1.05, "bound_over_routed": 2.0,
        "bias_abs_max": None, "overflowed": 0.0}


def test_a_run_that_prints_no_result_is_kept_with_its_error(tmp_path):
    fails = [sys.executable, "-c",
             "import sys; print('no chip', file=sys.stderr); sys.exit(2)"]
    rec = rs.run_one(str(tmp_path), fails, "some.cell", 5, 1, 0)
    assert (rec["rc"], rec["result"], rec["notes"]) == (2, None, None)
    assert "no chip" in rec["stderr_tail"] and rec["epoch_counters"] == []
    line = rs.summary(rec)
    assert line["correct"] is None and line["fit_tokens_per_s"] is None
    assert "metrics" not in line
    assert os.listdir(tmp_path) == []

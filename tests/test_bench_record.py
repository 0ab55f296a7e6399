"""The bench's evidence-preservation machinery: streamed legs, partial
flushes, and deadline gates. Round 4 lost ~35 min of on-chip scanned-leg
measurements to an exception AFTER the legs had run — these tests pin
the hedges that prevent a recurrence (bench.py:_leg/_flush_partial,
_over_deadline, and the skip markers)."""

import importlib
import json
import os
import time

import pytest


@pytest.fixture()
def bench_mod(tmp_path, monkeypatch):
    # conftest.py already puts the repo root on sys.path for every test.
    monkeypatch.setenv(
        "DCT_BENCH_PARTIAL", str(tmp_path / "BENCH_PARTIAL.json")
    )
    import bench

    bench = importlib.reload(bench)
    yield bench
    # Reload again so the monkeypatched partial path does not leak into
    # other suites that import bench.
    monkeypatch.undo()
    importlib.reload(bench)


def _partial(bench):
    with open(bench._PARTIAL_PATH) as f:
        return json.loads(f.read())


def test_leg_streams_into_live_record(bench_mod):
    rec = {"metric": "m"}
    bench_mod._LIVE_RECORD = rec
    try:
        bench_mod._leg("attn_blockwise_ms", 12.34)
        bench_mod._leg("attn_gqa", {"speedup": 1.5})
    finally:
        bench_mod._LIVE_RECORD = None
    on_disk = _partial(bench_mod)
    assert on_disk["scaled_legs"]["attn_blockwise_ms"] == 12.34
    assert on_disk["scaled_legs"]["attn_gqa"] == {"speedup": 1.5}
    assert rec["scaled_legs"] == on_disk["scaled_legs"]


def test_leg_without_live_record_is_stderr_only(bench_mod, capsys):
    bench_mod._LIVE_RECORD = None
    bench_mod._leg("attn_flash_ms", 7.0)  # must not raise
    assert "attn_flash_ms=7.0" in capsys.readouterr().err
    assert not os.path.exists(bench_mod._PARTIAL_PATH)


def test_partial_flush_is_atomic_and_additive(bench_mod):
    bench_mod._flush_partial({"a": 1})
    bench_mod._flush_partial({"a": 1, "b": 2})
    assert _partial(bench_mod) == {"a": 1, "b": 2}
    assert not os.path.exists(bench_mod._PARTIAL_PATH + ".tmp")


def test_deadline_fraction_gates(bench_mod, monkeypatch):
    monkeypatch.setattr(bench_mod, "_DEADLINE", 100.0)
    # Shift the bench's own epoch so ~60s appear elapsed: over a 55%
    # budget (55s), under the full deadline. (Patching bench state, not
    # the global clock — stdlib perf_counter stays untouched.)
    monkeypatch.setattr(
        bench_mod, "_BENCH_T0", time.perf_counter() - 60.0
    )
    assert bench_mod._over_deadline("x", frac=0.55) is True
    assert bench_mod._over_deadline("x") is False
    # Deadline disabled -> never over, any fraction.
    monkeypatch.setattr(bench_mod, "_DEADLINE", 0.0)
    assert bench_mod._over_deadline("x", frac=0.55) is False


def _worst_case_record() -> dict:
    """A record with EVERY section populated at realistic on-chip size
    (val_parity + all sections at once)."""
    return {
        "metric": "weather_parity_train_samples_per_sec_per_chip",
        "unit": "samples/sec/chip",
        "mfu": 0.2134,
        "generated_utc": "2026-08-04T12:00:00Z",
        "baseline_torch_cpu_samples_per_sec": 5278.9,
        "value": 8342288.3,
        "vs_baseline": 1580.31,
        "final_train_loss": 0.0037,
        "platform": "tpu",
        "trainer_loop_samples_per_sec_per_chip": 198817.8,
        "trainer_loop_vs_baseline": 37.66,
        "trainer_gap": {"fused": 8342288.3, "fit": 198817.8,
                        "fused_over_fit": 41.96, "prefetch_spans": 1},
        "trainer_loop_chunked_samples_per_sec_per_chip": 205000.1,
        "deadline_skipped": ["scaled_moe", "val_parity", "serving",
                             "host_dataplane"],
        "scaled": {
            "config": {"d_model": 512, "n_heads": 8, "n_layers": 4,
                       "d_ff": 2048, "seq_len": 1024, "batch": 32,
                       "dtype": "bfloat16", "scan_len": 16,
                       "remat": True},
            "step_time_ms": 15.31, "step_time_dispatch_ms": 45.98,
            "flops_per_step": 3305111224320.0, "tflops_per_sec": 215.88,
            "attn_blockwise_ms": 16.76, "attn_flash_ms": 15.31,
            "samples_per_sec_per_chip": 2090.8,
            "attn_window": 256,
            "attn_causal_flash_ms": 9.97, "attn_causal_blockwise_ms": 14.2,
            "attn_window_flash_ms": 5.44, "attn_window_blockwise_ms": 13.9,
            "attn_gqa": {"kv_heads": 2, "mha_ms": 4.021, "gqa_ms": 3.312,
                         "speedup": 1.21},
            "deadline_skipped": ["window_blockwise", "gqa"],
            "chip_peak_bf16_tflops": 197.0, "mfu": 0.2134,
        },
        "moe": {"config": {"d_model": 512, "n_heads": 8, "n_layers": 2,
                           "d_ff": 1024, "seq_len": 512, "n_experts": 32,
                           "batch": 8, "dtype": "bfloat16"},
                "sorted_ms": 21.4, "einsum_ms": 44.1,
                "sorted_speedup": 2.06,
                "deadline_skipped": ["einsum"]},
        "val_parity": {
            "protocol": (
                "10 epochs, batch 4, Adam lr 0.01, seeded 80/20 split, "
                "seed 42 (train_lightning_ddp.py:14,88,117,122,132)"
            ),
            "torch_val_loss": 0.30294, "torch_val_acc": 0.86643,
            "jax_val_loss": 0.31351, "jax_val_acc": 0.86292,
            "abs_diff": 0.01057,
        },
        "serving": {
            "single_row": {"numpy_p50_ms": 0.0518, "torch_p50_ms": 0.1023,
                           "speedup": 1.97},
            "batch64": {"numpy_p50_ms": 0.0671, "torch_p50_ms": 0.1388,
                        "speedup": 2.07},
        },
        "serving_load": {
            "processes": 1,
            "levels": [
                {"mode": "closed", "concurrency": c, "requests": 300,
                 "errors": 0, "duration_s": 0.4, "qps": q,
                 "p50_ms": p50, "p99_ms": p99}
                for c, q, p50, p99 in (
                    (1, 2186.7, 0.3982, 0.9883),
                    (4, 2493.1, 1.4849, 3.7727),
                    (16, 1477.6, 4.5024, 11.4212),
                )
            ],
            "knee_concurrency": 4, "knee_qps": 2493.1,
            "saturated_qps": 2493.1, "saturated_concurrency": 4,
            "baseline_qps": 2186.7, "batched_over_single": 1.14,
            "parity": True, "score_batched_over_single": 15.96,
        },
        # The streamed crash hedges a failed section leaves behind (a
        # scaled failure keeps scaled_legs in the record), val_parity
        # hedge with its full protocol prose included.
        "scaled_legs": {
            "attn_blockwise_ms": 16.76, "attn_flash_ms": 15.31,
            "attn_causal_flash_ms": 9.97,
            "attn_gqa": {"kv_heads": 2, "mha_ms": 4.021, "gqa_ms": 3.312,
                         "speedup": 1.21},
            "moe_sorted_ms": 21.4, "moe_einsum_ms": 44.1,
            "val_parity_torch": {"torch_val_loss": 0.30294,
                                 "torch_val_acc": 0.86643},
            "val_parity": {
                "protocol": (
                    "10 epochs, batch 4, Adam lr 0.01, seeded 80/20 "
                    "split, seed 42 "
                    "(train_lightning_ddp.py:14,88,117,122,132)"
                ),
                "torch_val_loss": 0.30294, "jax_val_loss": 0.31351,
                "abs_diff": 0.01057,
            },
        },
        "restart_spinup": {
            "cold_step_s": 15.828, "warm_step_s": 4.866,
            "cold_compile_s": 10.242, "warm_compile_s": 2.68,
            "warm_cache": ["hit"], "step_speedup": 3.25,
            "cold_score_s": 2.0097, "warm_score_s": 0.8364,
            "score_speedup": 2.4,
        },
        "cycle_freshness": {
            "generations": 2,
            "epochs_per_gen_serial": 200, "loop_round_epochs": 8,
            "soak_s": 0.35,
            "serial": {
                "freshness_s": [7.071, 11.748],
                "mean_freshness_s": 9.41, "cycle_s": 4.597,
                "cycles": 6, "promotions": 4, "held": 2,
                "goodput": 0.1357,
                "train_samples_per_sec_per_chip": 68309.9,
                "wall_s": 28.875,
            },
            "loop": {
                "freshness_s": [2.39, 2.413],
                "mean_freshness_s": 2.402, "rounds": 11,
                "promotions": 8, "held": 0, "goodput": 0.0381,
                "train_samples_per_sec_per_chip": 76164.4,
                "wall_s": 6.46, "stop_reason": "freshness_measured",
            },
            "serial_mean_freshness_s": 9.41,
            "loop_mean_freshness_s": 2.402,
            "goodput_serial": 0.1357, "goodput_loop": 0.0381,
            "freshness_speedup": 3.92, "train_throughput_ratio": 1.11,
        },
        "multi_tenant": {
            "tenants": 2, "rounds": 12, "preempts": 1, "wall_s": 14.8,
            "min_goodput_fraction": 0.0312, "mean_round_wait_s": 0.41,
            "quota_max_rel_err": 0.11,
            "per_tenant": {
                "light": {"weight": 1.0, "priority_rank": 1, "chips": 1,
                          "rounds": 4, "preempted_rounds": 0,
                          "granted_chip_s": 4.91, "goodput_s": 0.19,
                          "badput_s": 4.72, "goodput_fraction": 0.0387,
                          "mean_wait_s": 0.62, "fair_share": 0.3333,
                          "granted_share": 0.3602, "state": "stopped"},
                "heavy": {"weight": 2.0, "priority_rank": 1, "chips": 1,
                          "rounds": 8, "preempted_rounds": 1,
                          "granted_chip_s": 8.72, "goodput_s": 0.27,
                          "badput_s": 8.45, "goodput_fraction": 0.0312,
                          "mean_wait_s": 0.2, "fair_share": 0.6667,
                          "granted_share": 0.6398, "state": "stopped"},
            },
        },
        "model_sharded": {
            "devices": 4,
            "config": {
                "seq_len": 16, "d_model": 64, "n_heads": 2,
                "n_layers": 2, "d_ff": 128, "batch": 32, "scan_len": 8,
            },
            "dp_sps": 2100.5, "sharded_sps": 1772.0,
            "dp_peak_rss_mb": 302.8, "sharded_peak_rss_mb": 315.1,
            "loss_delta": 0.00083673,
            "sharded_sps_ratio": 0.844, "peak_rss_ratio": 0.961,
        },
        "mpmd_pipeline": {
            "stages": 2, "microbatches": 8,
            "config": {"seq_len": 32, "d_model": 128, "n_heads": 4,
                       "n_layers": 2, "d_ff": 512, "mb_rows": 32},
            "gpipe_bubble_fraction": 0.1111,
            "mpmd_steady_bubble": 0.0758,
            "mpmd_step_bubble": 0.1208,
            "mpmd_slope_bubble": 0.0381,
            "mpmd_transfer_wait_s": 0.0977,
            "gpipe_sps": 139.1, "mpmd_sps": 193.7,
            "loss_delta": 2.1e-06,
            "bubble_reduction": 0.3149, "mpmd_sps_ratio": 1.392,
        },
        "host_dataplane": {
            "rows_native_ms": 0.23, "rows_numpy_ms": 0.51,
            "rows_speedup": 2.18, "windows_native_ms": 1.43,
            "windows_numpy_ms": 11.05, "windows_speedup": 7.71,
        },
        "elastic_serving": {
            "trace": {"base_qps": 60.0, "spike_qps": 240.0,
                      "base_s": 1.5, "spike_s": 2.5, "service_ms": 8.0},
            "off": {
                phase: {"mode": "open", "concurrency": 400,
                        "requests": n, "errors": 0, "duration_s": d,
                        "qps": q, "p50_ms": p50, "p99_ms": p99,
                        "target_qps": tq, "dropped": 0}
                for phase, n, d, q, p50, p99, tq in (
                    ("base", 90, 1.5, 59.9, 9.1, 11.0, 60.0),
                    ("spike", 600, 5.01, 119.7, 1272.0, 2497.0, 240.0),
                    ("recover", 90, 1.5, 59.8, 10.2, 14.1, 60.0),
                )
            },
            "on": {
                phase: {"mode": "open", "concurrency": 400,
                        "requests": n, "errors": 0, "duration_s": d,
                        "qps": q, "p50_ms": p50, "p99_ms": p99,
                        "shed": s, "shed_fraction": sf,
                        "shed_p50_ms": 0.65, "target_qps": tq,
                        "dropped": 0}
                for phase, n, d, q, p50, p99, s, sf, tq in (
                    ("base", 90, 1.5, 59.9, 9.3, 11.0, 0, 0.0, 60.0),
                    ("spike", 510, 2.51, 203.4, 13.3, 26.2, 90, 0.15,
                     240.0),
                    ("recover", 90, 1.5, 59.8, 9.8, 13.2, 0, 0.0, 60.0),
                )
            },
            "pre_spike_p99_ms": 10.98, "pre_spike_p99_off_ms": 10.62,
            "spike_p99_off_ms": 2497.01, "spike_p99_on_ms": 26.25,
            "p99_ratio_off": 227.46, "p99_ratio_on": 2.39,
            "overload_p99_s": 0.0262, "shed": 90, "admitted": 690,
            "shed_fraction": 0.1154, "admitted_errors": 0,
            "scale_events": 4, "bounded": True,
        },
        "telemetry_history": {
            "plain_publish_p50_ms": 0.2131, "armed_publish_p50_ms": 0.2298,
            "publish_overhead_ms": 0.0167, "overhead_frac": 0.0784,
            "detected": True, "detect_latency_s": 1.847,
            "rig": {"service_ms": 2.0, "fault_ms": 30.0,
                    "base_qps": 40.0, "spike_qps": 80.0,
                    "baseline_s": 1.6, "budget_s": 12.0},
        },
        "stream_ingest": {
            "n_events": 4000, "burst": 50, "burst_every_s": 0.05,
            "lag_bound_s": 0.25, "stream_poll_s": 0.1, "csv_poll_s": 2.0,
            "stream_events_per_s": 936.6, "poll_events_per_s": 123.7,
            "stream_lag_p99_s": 0.112, "poll_lag_p99_s": 2.0273,
            "stream": {"trainable": 4000, "in_bound": 4000,
                       "in_bound_events_per_s": 936.6,
                       "lag_p99_s": 0.112, "wall_s": 4.27},
            "poll": {"trainable": 4000, "in_bound": 500,
                     "in_bound_events_per_s": 123.7,
                     "lag_p99_s": 2.0273, "wall_s": 4.04},
            "backpressure": {"lag_budget": 64, "produced": 64,
                             "shed": 448, "end_lag_records": 64,
                             "bounded": True},
            "events_per_s_speedup": 7.57, "lag_bounded": True,
        },
        "low_precision": {
            "serving": {
                "f32": {"p50_ms": 0.3161, "batch64_rows_per_s": 5340.4,
                        "max_abs_prob_delta": 0.0},
                "int8": {"p50_ms": 0.4166,
                         "batch64_rows_per_s": 20485.8,
                         "max_abs_prob_delta": 0.004959,
                         "speedup_batch64": 3.84},
                "bf16": {"p50_ms": 0.2808,
                         "batch64_rows_per_s": 5413.1,
                         "max_abs_prob_delta": 0.001306,
                         "speedup_batch64": 1.01},
            },
            "quant_serving_speedup": 3.84,
            "train": {
                "config": {"d_model": 128, "n_heads": 4, "n_layers": 2,
                           "d_ff": 1024, "seq_len": 64, "batch": 64},
                "peak_source": "DCT_PEAK_TFLOPS",
                "f32": {"samples_per_s": 73.2,
                        "bytes_accessed": 5206724608.0,
                        "flops": 17284323328.0, "mfu": 0.169985},
                "bf16_rules": {"samples_per_s": 46.7,
                               "bytes_accessed": 3648292608.0,
                               "flops": 17310842880.0, "mfu": 0.108695},
                "bf16_bytes_ratio": 0.701, "bytes_reduction_pct": 29.9,
                "bf16_sps_ratio": 0.64, "bf16_mfu_delta": -0.06129,
            },
            "bf16_bytes_ratio": 0.701,
            "gate": {"clean": "promote", "corrupted": "rollback",
                     "parity": True},
        },
    }


def test_stdout_record_worst_case_fits_driver_tail(bench_mod):
    """ISSUE 5 satellite: the PRINTED line, with every section
    populated, must stay under 1,800 B (the driver truncates its parse
    tail at 2,000 B; a longer line parses null)."""
    record = _worst_case_record()
    line = json.dumps(
        bench_mod._stdout_record(record), default=bench_mod._json_default
    )
    assert len(line.encode()) <= 1800, len(line.encode())
    out = json.loads(line)
    # Headline measurements survive every shrink rung.
    assert out["value"] == 8342288.3
    assert out["trainer_loop_samples_per_sec_per_chip"] == 198817.8
    assert out["trainer_gap"]["fused_over_fit"] == 41.96
    assert out["mfu"] == 0.2134
    assert out["scaled"]["attn_blockwise_ms"] == 16.76
    assert out["scaled"]["attn_flash_ms"] == 15.31
    assert out["scaled"]["mfu"] == 0.2134
    assert out["moe"]["sorted_speedup"] == 2.06
    assert out["val_parity"]["abs_diff"] == 0.01057
    assert out["deadline_skipped"] == record["deadline_skipped"]
    # Both low-precision sentinel series survive every shrink rung.
    assert out["low_precision"]["quant_serving_speedup"] == 3.84
    assert out["low_precision"]["bf16_bytes_ratio"] == 0.701


def test_stdout_record_typical_round_is_not_collapsed(bench_mod):
    """A realistic record (no failure leftovers) must keep every
    HEADLINE stanza's numbers on stdout: the full scaled section, moe
    timings, val_parity's loss-parity numbers, the serving_load columnar
    digest, and the cycle_freshness architecture comparison. The
    least-headline rungs (host_dataplane detail, serving p50 detail, the
    val_parity accuracy pair) may yield — every yielded field lives on
    verbatim in BENCH_PARTIAL.json."""
    record = _worst_case_record()
    # A normal round: no chunked leg, no failed-section leftovers, and
    # the scaled section without the full variant-leg sweep.
    del record["trainer_loop_chunked_samples_per_sec_per_chip"]
    del record["deadline_skipped"]
    del record["scaled_legs"]
    for leg in ("attn_causal_flash_ms", "attn_causal_blockwise_ms",
                "attn_window_flash_ms", "attn_window_blockwise_ms",
                "attn_gqa", "attn_window", "deadline_skipped"):
        del record["scaled"][leg]
    out = bench_mod._stdout_record(record)
    line = json.dumps(out, default=bench_mod._json_default)
    assert len(line.encode()) <= bench_mod._STDOUT_BUDGET
    # Headline stanzas un-collapsed...
    assert out["scaled"]["step_time_dispatch_ms"] == 45.98
    assert out["moe"]["einsum_ms"] == 44.1
    # ...val_parity keeps the north-star LOSS parity (the accuracy pair
    # yields to the partial when the record is fully populated)...
    assert out["val_parity"]["torch_val_loss"] == 0.30294
    assert out["val_parity"]["jax_val_loss"] == 0.31351
    assert out["val_parity"]["abs_diff"] == 0.01057
    # ...the cycle_freshness architecture comparison rides stdout with
    # the sentinel's series (speedup + the loop mean); the serial mean
    # is derivable (loop_mean x speedup — yielded to fund the
    # mpmd_pipeline sentinel series) and the goodput pair yields to the
    # partial when every stanza is populated at once (the late rung
    # funding the stream_ingest sentinel series)...
    cf = out["cycle_freshness"]
    assert cf["freshness_speedup"] == 3.92
    assert "serial_mean_freshness_s" not in cf
    assert cf["loop_mean_freshness_s"] == 2.402
    assert "goodput_serial" not in cf and "goodput_loop" not in cf
    # ...the restart_spinup digest rides stdout with the sentinel's
    # warm series + both ratios (cold controls derivable, detail in
    # the partial)...
    assert out["restart_spinup"] == {
        "warm_step_s": 4.866, "step_speedup": 3.25,
        "warm_score_s": 0.8364, "score_speedup": 2.4,
    }
    # ...the model_sharded digest keeps the sentinel's throughput
    # ratio (the memory ratio/parity delta may yield to the partial
    # under a full-record squeeze)...
    ms = out["model_sharded"]
    assert ms["sharded_sps_ratio"] == 0.844
    assert "config" not in ms and "dp_sps" not in ms
    # ...the mpmd_pipeline digest keeps both sentinel series (steady
    # bubble, sps ratio) + the gpipe comparator (it would yield only
    # under a squeeze the goodput-pair rung did not already satisfy;
    # bubble_reduction = 1 - steady/gpipe recovers it from the
    # partial); the config dict and absolute sps detail stay in the
    # partial...
    mpp = out["mpmd_pipeline"]
    assert mpp["mpmd_steady_bubble"] == 0.0758
    assert mpp["gpipe_bubble_fraction"] == 0.1111
    assert mpp["mpmd_sps_ratio"] == 1.392
    assert "config" not in mpp and "gpipe_sps" not in mpp
    # ...serving keeps (at least) its speedup headlines...
    assert out["serving"]["single_row"] in (
        1.97, record["serving"]["single_row"]
    )
    # ...and serving_load rides stdout as the columnar digest with
    # every level's numbers intact (the per-level dict list stays in
    # the partial).
    sl = out["serving_load"]
    assert sl["levels"]["qps"] == [2186.7, 2493.1, 1477.6]
    assert sl["levels"]["p99_ms"] == [0.9883, 3.7727, 11.4212]
    assert sl["batched_over_single"] == 1.14
    assert sl["score_batched_over_single"] == 15.96
    # ...elastic_serving keeps both sentinel series on stdout (the A/B
    # ratio pair may yield to the partial when every stanza is
    # populated at once — the late rung funding telemetry_history);
    # the per-phase replay dicts stay in the partial.
    es = out["elastic_serving"]
    assert es["overload_p99_s"] == 0.0262
    assert es["shed_fraction"] == 0.1154
    assert "off" not in es and "on" not in es and "trace" not in es
    # ...telemetry_history keeps exactly its two sentinel series; the
    # plain/armed p50 pair and the rig knobs stay in the partial.
    assert out["telemetry_history"] == {
        "detect_latency_s": 1.847, "publish_overhead_ms": 0.0167,
    }
    # ...stream_ingest keeps its two sentinel series on stdout (the
    # vs-polling speedup and the acceptance bits yield to the partial
    # when every stanza is populated at once — the same late rung that
    # funds telemetry_history); the polling comparator's raw numbers,
    # the arrival-schedule shape and the backpressure counters stay in
    # the partial.
    assert out["stream_ingest"] == {
        "stream_events_per_s": 936.6, "stream_lag_p99_s": 0.112,
    }
    # ...and low_precision rides stdout as its digest: both sentinel
    # series + the accuracy-bound evidence + the gate bit (the train
    # A/B ratios may yield under a full-record squeeze; the per-variant
    # p50/bytes detail always stays in the partial).
    lp = out["low_precision"]
    assert lp["quant_serving_speedup"] == 3.84
    assert lp["bf16_bytes_ratio"] == 0.701
    assert lp["int8_prob_delta"] == 0.004959
    assert lp["gate_parity"] is True
    assert "serving" not in lp and "train" not in lp


def test_stdout_record_bounds_error_strings(bench_mod):
    """An on-chip failure embeds XLA error text that can run to
    kilobytes: a record carrying error sections must still print inside
    the driver tail — the shrink ladder's last rung truncates any long
    string leaf."""
    record = _worst_case_record()
    xla = "JaxRuntimeError: INTERNAL: Mosaic failed to compile: " + "x" * 4000
    record["serving"] = {"error": xla}
    record["moe"] = {"error": xla}
    record["scaled"]["attn_flash_error"] = xla
    record["scaled"]["attn_gqa"] = {"error": xla}
    line = json.dumps(
        bench_mod._stdout_record(record), default=bench_mod._json_default
    )
    assert len(line.encode()) <= 1800, len(line.encode())
    out = json.loads(line)
    # Headlines still survive alongside the (bounded) error evidence.
    assert out["value"] == 8342288.3
    assert out["trainer_gap"]["fused_over_fit"] == 41.96


def test_stdout_record_passthrough_without_digested_stanzas(bench_mod):
    """A record with no digested stanza must print unchanged."""
    rec = {"metric": "m", "value": 1.0, "scaled": None}
    assert bench_mod._stdout_record(rec) == rec


def test_stdout_record_failed_scaled_leaves_bounded_legs(bench_mod):
    """When the scaled section dies, its streamed scaled_legs hedge
    stays in the record — the ladder must reach it."""
    record = _worst_case_record()
    record["scaled"] = {"error": "JaxRuntimeError: UNAVAILABLE: " + "x" * 400}
    record["mfu"] = None
    line = json.dumps(
        bench_mod._stdout_record(record), default=bench_mod._json_default
    )
    assert len(line.encode()) <= 1800, len(line.encode())
    out = json.loads(line)
    # The legs hedge survives in digest form (headline kernels only).
    assert out["scaled_legs"]["attn_blockwise_ms"] == 16.76


def test_truncate_recurses_into_lists(bench_mod):
    """Some stanzas carry LISTS of dicts; a huge string inside one
    must still be bounded by the last rung."""
    record = _worst_case_record()
    record["scaled"]["attn_gqa"] = {
        "attempts": [
            {"n": i, "error": "INTERNAL: Mosaic " + "y" * 3000}
            for i in range(4)
        ],
        "error": "z" * 3000,
    }
    line = json.dumps(
        bench_mod._stdout_record(record), default=bench_mod._json_default
    )
    assert len(line.encode()) <= 1800, len(line.encode())


def test_flush_survives_numpy_scalars(bench_mod):
    """A np scalar leaking into a leg value must not raise FROM the
    hedge (a TypeError here would kill the section it protects)."""
    import numpy as np

    bench_mod._flush_partial({
        "v": np.float32(12.5), "flag": np.bool_(True),
        "arr_note": np.int64(3),
    })
    on_disk = _partial(bench_mod)
    assert on_disk["v"] == 12.5 and on_disk["flag"] == 1.0
    assert not os.path.exists(bench_mod._PARTIAL_PATH + ".tmp")

"""Bench-trajectory regression sentinel.

Reads a trajectory of per-round ``BENCH_r0N.json`` bench records and
FLAGS it — a record that overflowed to ``parsed: null`` included::

    python -m dct_tpu.observability.report BENCH_r0*.json
    python -m dct_tpu.observability.report            # globs ./BENCH_r*.json

Per round it extracts the comparable series (headline samples/s/chip,
trainer-loop throughput, serving single-row p50, serving-load saturated
qps), then compares CONSECUTIVE comparable rounds:

- a throughput metric dropping more than ``--threshold`` (default 10%)
  is a REGRESSION finding;
- a latency metric rising more than ``--latency-threshold`` (default
  25%) likewise;
- a round whose record is unparsable (``parsed: null`` — the stdout
  tail overflowed) or whose headline metric NAME changed is reported
  and excluded from deltas (comparing different metrics is noise, not
  signal).

Exit code 0 by default (the sentinel reports; CI decides) — ``--strict``
exits 1 when any regression is flagged. Read-only over the records.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

#: (label, path into parsed record, direction) — direction "up" means
#: bigger is better (drops regress), "down" means smaller is better
#: (rises regress).
SERIES = (
    ("headline", ("value",), "up"),
    ("trainer_loop", ("trainer_loop_samples_per_sec_per_chip",), "up"),
    ("serving_p50_ms", ("serving", "single_row", "numpy_p50_ms"), "down"),
    ("serving_load_qps", ("serving_load", "saturated_qps"), "up"),
    # Restart/spin-up debt (the restart_spinup bench leg): warm
    # time-from-SIGKILL-to-first-step and warm endpoint
    # time-to-first-score — cold-start latencies gated at the same
    # >25% rise threshold as the serving latency series.
    ("warm_step_s", ("restart_spinup", "warm_step_s"), "down"),
    ("warm_score_s", ("restart_spinup", "warm_score_s"), "down"),
    # Always-on loop (the cycle_freshness bench leg): data-arrival ->
    # deployed-model latency through the overlapped loop, and its
    # advantage over the serial episodic cycle. The latency gates at
    # the >25% rise threshold; the speedup at the >10% drop threshold.
    ("loop_freshness_s", ("cycle_freshness", "loop_mean_freshness_s"),
     "down"),
    ("freshness_speedup", ("cycle_freshness", "freshness_speedup"), "up"),
    # Sharded continuous training (the model_sharded bench leg):
    # partition-rule sharded throughput as a fraction of pure DP at
    # matched config — a drop past the >10% threshold means the sharded
    # layouts started paying for collectives they previously amortized.
    ("sharded_sps_ratio", ("model_sharded", "sharded_sps_ratio"), "up"),
    # Multi-tenant scheduler (the multi_tenant bench leg): the WORST
    # tenant's goodput fraction over its granted leases (a drop past
    # the >10% threshold means arbitration overhead started eating
    # lease time) and the roster's mean round-lease wait (gated like a
    # latency — a >25% rise means tenants queue longer for chips).
    ("tenant_goodput_fraction",
     ("multi_tenant", "min_goodput_fraction"), "up"),
    ("tenant_round_wait_s", ("multi_tenant", "mean_round_wait_s"), "down"),
    # MPMD pipeline trainer (the mpmd_pipeline bench leg): the
    # 1F1B steady-state bubble fraction — gated like a latency (a >25%
    # rise means the per-stage saturation regressed: transfer waits or
    # schedule skew crept into the steady window) — and MPMD throughput
    # as a fraction of the SPMD-GPipe comparator at matched config
    # (a >10% drop means the explicit transfer plane started costing
    # what the lockstep collectives used to).
    ("mpmd_bubble_fraction", ("mpmd_pipeline", "mpmd_steady_bubble"),
     "down"),
    ("mpmd_sps_ratio", ("mpmd_pipeline", "mpmd_sps_ratio"), "up"),
    # Roofline introspection (the roofline bench leg): cost-model MFU
    # (flags at the >10% drop threshold) and the MPMD step's
    # transfer-wait fraction, gated like a latency (a >25% rise means
    # inter-stage comms started eating the step).
    ("program_mfu", ("roofline", "mfu"), "up"),
    ("transfer_wait_frac",
     ("mpmd_pipeline", "mpmd_transfer_wait_frac"), "down"),
    # Elastic serving (the elastic_serving bench leg): p99 of ADMITTED
    # traffic during the 4x overload spike with the controls armed —
    # gated like a latency (a >25% rise means the admission budget or
    # the autoscaler's time-to-capacity regressed) — and the fraction
    # of the spike's offered load shed to keep it bounded (a >25% rise
    # means capacity or scale-up responsiveness dropped, pushing more
    # of the burden onto shedding).
    ("overload_p99_s", ("elastic_serving", "overload_p99_s"), "down"),
    ("shed_fraction", ("elastic_serving", "shed_fraction"), "down"),
    # Telemetry history (the telemetry_history bench leg): seconds from
    # planting a slow_score fault to the detector flagging queue depth
    # anomalous FROM THE ON-DISK HISTORY (a rise means the store/flush/
    # poll pipeline got slower at its one job), and the armed-vs-plain
    # snapshot-publish overhead (a rise means the history hook crept
    # onto the hot path — the bound the buffered flush design exists
    # to hold).
    ("anomaly_detect_latency_s",
     ("telemetry_history", "detect_latency_s"), "down"),
    ("history_publish_overhead_ms",
     ("telemetry_history", "publish_overhead_ms"), "down"),
    # Streaming ingest (the stream_ingest bench leg): events made
    # trainable WITHIN the configured arrival->trainable bound per
    # second of wall through the deployed stream watcher (a >10% drop
    # means the log/consumer/ETL path stopped keeping events fresh at
    # rate), and the stream side's arrival->trainable lag p99 — gated
    # like a latency (a >25% rise means the bounded-lag contract the
    # plane exists for started slipping).
    ("stream_events_per_s", ("stream_ingest", "stream_events_per_s"), "up"),
    ("stream_lag_p99_s", ("stream_ingest", "stream_lag_p99_s"), "down"),
    # Low precision (the low_precision bench leg): the int8 scorer's
    # batch-64 throughput over the f32 twin (a drop means the
    # integer-exact GEMM stopped paying for its quantize overhead),
    # and the bf16-dtype-rules train step's lowered bytes_accessed
    # over f32 at matched config (a rise means the mixed-precision
    # rules stopped shrinking the program's memory traffic — gated
    # like a latency, down = better).
    ("quant_serving_speedup",
     ("low_precision", "quant_serving_speedup"), "up"),
    ("bf16_bytes_ratio", ("low_precision", "bf16_bytes_ratio"), "down"),
)


def _dig(rec: dict, path: tuple):
    cur = rec
    for k in path:
        if not isinstance(cur, dict):
            return None
        cur = cur.get(k)
    return cur if isinstance(cur, (int, float)) else None


def load_round(path: str) -> dict:
    """One record -> {name, parsable, metric, series: {label: value}}."""
    name = os.path.basename(path)
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError) as e:
        return {"name": name, "parsable": False, "error": str(e)}
    parsed = rec.get("parsed") if isinstance(rec, dict) else None
    if not isinstance(parsed, dict):
        return {
            "name": name, "parsable": False,
            "error": "parsed: null (stdout record overflowed the "
                     "driver tail)",
        }
    out = {
        "name": name,
        "parsable": True,
        "metric": parsed.get("metric"),
        "series": {},
    }
    for label, path_keys, _direction in SERIES:
        v = _dig(parsed, path_keys)
        if v is not None:
            out["series"][label] = float(v)
    return out


def compare_rounds(
    rounds: list[dict],
    *,
    threshold: float = 0.10,
    latency_threshold: float = 0.25,
) -> list[dict]:
    """Consecutive-round deltas -> regression findings."""
    findings: list[dict] = []
    prev = None
    for rnd in rounds:
        if not rnd.get("parsable"):
            findings.append({
                "kind": "unparsable", "round": rnd["name"],
                "detail": rnd.get("error", ""),
            })
            continue
        if prev is not None:
            for label, _path, direction in SERIES:
                a = prev["series"].get(label)
                b = rnd["series"].get(label)
                if a is None or b is None or a <= 0:
                    continue
                if label == "headline" and (
                    prev.get("metric") != rnd.get("metric")
                ):
                    # The headline metric was redefined between rounds:
                    # the numbers are not comparable.
                    continue
                if direction == "up":
                    drop = (a - b) / a
                    if drop > threshold:
                        findings.append({
                            "kind": "regression", "round": rnd["name"],
                            "series": label, "prev": a, "cur": b,
                            "delta_pct": round(-100.0 * drop, 1),
                            "vs": prev["name"],
                        })
                else:
                    rise = (b - a) / a
                    if rise > latency_threshold:
                        findings.append({
                            "kind": "regression", "round": rnd["name"],
                            "series": label, "prev": a, "cur": b,
                            "delta_pct": round(100.0 * rise, 1),
                            "vs": prev["name"],
                        })
        prev = rnd
    return findings


def render_report(rounds: list[dict], findings: list[dict]) -> str:
    lines = ["=" * 72, "dct_tpu bench trajectory", "=" * 72]
    labels = [label for label, _p, _d in SERIES]
    header = f"{'round':18s}" + "".join(f"{h:>18s}" for h in labels)
    lines.append(header)
    for rnd in rounds:
        if not rnd.get("parsable"):
            lines.append(f"{rnd['name']:18s}{'(unparsable)':>18s}")
            continue
        row = f"{rnd['name']:18s}"
        for label in labels:
            v = rnd["series"].get(label)
            row += f"{v:>18.4g}" if v is not None else f"{'-':>18s}"
        lines.append(row)
    lines.append("")
    if findings:
        lines.append(f"Findings ({len(findings)}):")
        for f in findings:
            if f["kind"] == "regression":
                lines.append(
                    f"  REGRESSION {f['round']} {f['series']}: "
                    f"{f['prev']:.4g} -> {f['cur']:.4g} "
                    f"({f['delta_pct']:+.1f}% vs {f['vs']})"
                )
            else:
                lines.append(
                    f"  UNPARSABLE {f['round']}: {f['detail']}"
                )
    else:
        lines.append("Findings: none — trajectory holds.")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m dct_tpu.observability.report",
        description=(
            "Regression sentinel over the checked-in BENCH_r*.json "
            "trajectory: flags throughput drops, latency rises and "
            "unparsable records between rounds."
        ),
    )
    parser.add_argument(
        "records", nargs="*",
        help="bench record paths (default: ./BENCH_r*.json, sorted)",
    )
    parser.add_argument(
        "--threshold", type=float, default=0.10,
        help="throughput drop fraction that flags (default 0.10)",
    )
    parser.add_argument(
        "--latency-threshold", type=float, default=0.25,
        help="latency rise fraction that flags (default 0.25)",
    )
    parser.add_argument(
        "--strict", action="store_true",
        help="exit 1 when any regression is flagged (CI gate mode)",
    )
    parser.add_argument(
        "--json", action="store_true", dest="as_json",
        help="machine-readable output",
    )
    args = parser.parse_args(argv)
    paths = args.records or sorted(glob.glob("BENCH_r*.json"))
    if not paths:
        print("error: no bench records found", file=sys.stderr)
        return 2
    rounds = [load_round(p) for p in sorted(paths)]
    findings = compare_rounds(
        rounds,
        threshold=args.threshold,
        latency_threshold=args.latency_threshold,
    )
    if args.as_json:
        print(json.dumps(
            {"rounds": rounds, "findings": findings}, indent=2
        ))
    else:
        print(render_report(rounds, findings))
    regressions = [f for f in findings if f["kind"] == "regression"]
    return 1 if (args.strict and regressions) else 0


if __name__ == "__main__":
    sys.exit(main())

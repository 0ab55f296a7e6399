"""Metrics plane (ISSUE 8): registry semantics, cross-process snapshot
aggregation, SLO burn-rate alerting, compile/restart accounting,
heartbeat progress age, and exposition round-trip validity for every
``/metrics`` body the platform produces.
"""

from __future__ import annotations

import json
import math
import os
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dct_tpu.observability import aggregate, slo
from dct_tpu.observability.metrics import MetricsRegistry


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


# ======================================================================
# exposition round-trip parser — the validity oracle every body must
# pass (well-formed 0.0.4, monotone cumulative buckets, consistent
# _count/_sum presence).


_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (-?[0-9.eE+-]+|\+Inf|NaN)$"
)


def parse_exposition_strict(text: str) -> dict:
    """Parse an exposition body, asserting structural validity:

    - every non-comment, non-blank line is a well-formed sample;
    - every sample's base family has HELP and TYPE declared BEFORE it;
    - histograms: per label-set, bucket counts are monotone
      non-decreasing in ``le``, the ``+Inf`` bucket equals ``_count``,
      and ``_sum``/``_count`` are both present;
    - no family is declared twice (duplicate TYPE lines confuse
      scrapers).
    """
    types: dict[str, str] = {}
    helps: set[str] = set()
    samples: dict[str, float] = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            name = line.split()[2]
            helps.add(name)
            continue
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split(None, 3)
            assert name not in types, f"duplicate TYPE for {name}"
            assert mtype in ("counter", "gauge", "histogram"), line
            types[name] = mtype
            continue
        assert not line.startswith("#"), f"unknown comment: {line}"
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert base in types or name in types, (
            f"sample {name} has no TYPE declaration"
        )
        assert base in helps or name in helps, (
            f"sample {name} has no HELP declaration"
        )
        v = float("inf") if value == "+Inf" else float(value)
        samples[name + labels] = v

    # Histogram invariants per label set.
    hist_names = [n for n, t in types.items() if t == "histogram"]
    for hname in hist_names:
        by_labelset: dict[str, list[tuple[float, float]]] = {}
        for key, v in samples.items():
            if not key.startswith(hname + "_bucket{"):
                continue
            labels = key[len(hname) + len("_bucket{"):-1]
            parts = [p for p in labels.split(",") if not p.startswith('le=')]
            le = [p for p in labels.split(",") if p.startswith('le=')]
            assert le, f"bucket sample without le: {key}"
            le_val = le[0].split("=", 1)[1].strip('"')
            le_f = float("inf") if le_val == "+Inf" else float(le_val)
            by_labelset.setdefault(",".join(parts), []).append((le_f, v))
        for labelset, buckets in by_labelset.items():
            buckets.sort()
            counts = [c for _le, c in buckets]
            assert counts == sorted(counts), (
                f"{hname}{{{labelset}}}: buckets not monotone: {counts}"
            )
            assert buckets[-1][0] == float("inf"), (
                f"{hname}{{{labelset}}}: no +Inf bucket"
            )
            suffix = "{" + labelset + "}" if labelset else ""
            count_key = hname + "_count" + suffix
            sum_key = hname + "_sum" + suffix
            assert count_key in samples, f"missing {count_key}"
            assert sum_key in samples, f"missing {sum_key}"
            assert samples[count_key] == buckets[-1][1], (
                f"{hname}: _count != +Inf bucket"
            )
    return samples


# ======================================================================
# registry semantics


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "things")
    c.inc(2, {"slot": "a"})
    c.inc(3, {"slot": "a"})
    c.inc(1)
    g = reg.gauge("t_frac", "fraction", agg="last")
    g.set(0.5)
    g.set(0.75)
    h = reg.histogram("t_lat", "latency")
    h.observe(0.002)
    h.observe(5.0)
    samples = parse_exposition_strict(reg.render())
    assert samples['t_total{slot="a"}'] == 5
    assert samples["t_total"] == 1
    assert samples["t_frac"] == 0.75
    assert samples["t_lat_count"] == 2
    assert samples["t_lat_sum"] == pytest.approx(5.002)


def test_registry_conflicting_registration_raises():
    reg = MetricsRegistry()
    reg.counter("x_total", "x")
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x")
    reg.gauge("g", "g", agg="sum")
    with pytest.raises(ValueError):
        reg.gauge("g", "g", agg="max")
    reg.histogram("h", "h", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("h", "h", buckets=(1.0, 3.0))
    with pytest.raises(ValueError):
        reg.gauge("g2", "g", agg="median")


def test_registry_label_order_is_canonical():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "c")
    c.inc(1, {"a": "1", "b": "2"})
    c.inc(1, {"b": "2", "a": "1"})
    samples = parse_exposition_strict(reg.render())
    assert samples['c_total{a="1",b="2"}'] == 2


def test_registry_thread_safety_under_contention():
    reg = MetricsRegistry()
    c = reg.counter("n_total", "n")
    h = reg.histogram("n_lat", "n")

    def work():
        for _ in range(500):
            c.inc(1, {"t": "x"})
            h.observe(0.01)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    samples = parse_exposition_strict(reg.render())
    assert samples['n_total{t="x"}'] == 4000
    assert samples["n_lat_count"] == 4000


# ======================================================================
# snapshots: atomic publish, staleness, merge semantics


def _snap(proc, *, pid=None, ts=1000.0, final=False, metrics=()):
    return {
        "proc": proc, "pid": pid if pid is not None else os.getpid(),
        "ts": ts, "final": final, "metrics": list(metrics),
    }


def _counter_metric(name, value, labels=None):
    return {
        "name": name, "type": "counter", "help": name,
        "samples": [{"labels": labels or {}, "value": value}],
    }


def test_snapshot_write_is_atomic_and_replaces(tmp_path):
    d = str(tmp_path)
    path = aggregate.write_snapshot(
        _snap("a", metrics=[_counter_metric("x_total", 1)]), d
    )
    assert path and os.path.exists(path)
    assert not [f for f in os.listdir(d) if ".tmp." in f]
    aggregate.write_snapshot(
        _snap("a", metrics=[_counter_metric("x_total", 7)]), d
    )
    snaps = aggregate.read_snapshots(d)
    assert len(snaps) == 1
    assert snaps[0]["metrics"][0]["samples"][0]["value"] == 7


def test_dead_pid_dropped_final_kept(tmp_path):
    d = str(tmp_path)
    # Find a dead pid: fork+exit, or use an absurd pid.
    dead_pid = 2 ** 22 - 7  # beyond default pid_max
    aggregate.write_snapshot(
        _snap("dead", pid=dead_pid,
              metrics=[_counter_metric("x_total", 5)]), d,
    )
    aggregate.write_snapshot(
        _snap("batch", pid=dead_pid, final=True,
              metrics=[_counter_metric("x_total", 3)]), d,
    )
    aggregate.write_snapshot(
        _snap("live", metrics=[_counter_metric("x_total", 2)]), d,
    )
    merged = aggregate.merge_snapshots(aggregate.read_snapshots(d))
    # dead dropped; final + live kept.
    assert sorted(merged.procs) == ["batch", "live"]
    assert merged.total("x_total") == 5


def test_old_mtime_dropped_for_live_not_final(tmp_path):
    d = str(tmp_path)
    p1 = aggregate.write_snapshot(
        _snap("stale", metrics=[_counter_metric("x_total", 5)]), d
    )
    p2 = aggregate.write_snapshot(
        _snap("batch", final=True,
              metrics=[_counter_metric("x_total", 3)]), d,
    )
    old = time.time() - 1000
    os.utime(p1, (old, old))
    os.utime(p2, (old, old))
    snaps = aggregate.read_snapshots(d, stale_s=30.0)
    assert [s["proc"] for s in snaps] == ["batch"]


def test_unparsable_snapshot_skipped(tmp_path):
    d = str(tmp_path)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "junk.metrics.json"), "w") as f:
        f.write("{not json")
    aggregate.write_snapshot(
        _snap("ok", metrics=[_counter_metric("x_total", 1)]), d
    )
    assert [s["proc"] for s in aggregate.read_snapshots(d)] == ["ok"]


def test_merge_counters_sum_gauges_by_agg_histograms_bucketwise():
    def snap(proc, ts, req, frac, wall, lat_counts, lat_sum, lat_n):
        return _snap(proc, ts=ts, metrics=[
            _counter_metric("r_total", req, {"slot": "s"}),
            {
                "name": "g_frac", "type": "gauge", "help": "", "agg": "last",
                "samples": [{"labels": {}, "value": frac}],
            },
            {
                "name": "g_max", "type": "gauge", "help": "", "agg": "max",
                "samples": [{"labels": {}, "value": wall}],
            },
            {
                "name": "lat", "type": "histogram", "help": "",
                "buckets": [0.1, 1.0],
                "samples": [{
                    "labels": {}, "counts": lat_counts,
                    "count": lat_n, "sum": lat_sum,
                }],
            },
        ])

    merged = aggregate.merge_snapshots([
        snap("a", 10.0, 4, 0.25, 7.0, [1, 2], 1.5, 3),
        snap("b", 20.0, 6, 0.75, 5.0, [2, 2], 0.2, 2),
    ])
    assert merged.value("r_total", {"slot": "s"}) == 10
    assert merged.value("g_frac") == 0.75  # newest ts wins for "last"
    assert merged.value("g_max") == 7.0
    hist = merged.histogram_total("lat")
    assert hist["counts"] == [3, 4]
    assert hist["count"] == 5
    assert hist["sum"] == pytest.approx(1.7)
    # Per-proc series preserved under the proc label in the rendering.
    text = aggregate.render_merged(merged)
    samples = parse_exposition_strict(text)
    assert samples['r_total{slot="s"}'] == 10
    assert samples['r_total{slot="s",proc="a"}'] == 4
    assert samples['r_total{slot="s",proc="b"}'] == 6


def test_merge_skips_mismatched_histogram_buckets():
    a = _snap("a", metrics=[{
        "name": "h", "type": "histogram", "help": "", "buckets": [1.0],
        "samples": [{"labels": {}, "counts": [1], "count": 1, "sum": 0.5}],
    }])
    b = _snap("b", metrics=[{
        "name": "h", "type": "histogram", "help": "", "buckets": [2.0],
        "samples": [{"labels": {}, "counts": [9], "count": 9, "sum": 9.9}],
    }])
    merged = aggregate.merge_snapshots([a, b])
    hist = merged.histogram_total("h")
    assert hist["count"] == 1  # the disagreeing family was skipped


def test_publisher_throttles_and_timer_refreshes(tmp_path):
    clock = FakeClock()
    reg = MetricsRegistry(clock=clock)
    reg.counter("x_total", "x").inc(1)
    pub = aggregate.SnapshotPublisher(
        reg, str(tmp_path), proc="p", interval_s=5.0, clock=clock,
        start_timer=False,
    )
    assert pub.maybe_publish() is True
    clock.advance(1.0)
    assert pub.maybe_publish() is False  # inside the throttle window
    clock.advance(5.0)
    assert pub.maybe_publish() is True
    pub.close()
    # close() without final retires the snapshot file.
    assert aggregate.read_snapshots(str(tmp_path), clock=clock) == []
    pub2 = aggregate.SnapshotPublisher(
        reg, str(tmp_path), proc="p", interval_s=5.0, clock=clock,
        start_timer=False,
    )
    pub2.publish()
    pub2.close(final=True)
    snaps = aggregate.read_snapshots(str(tmp_path), clock=clock)
    assert len(snaps) == 1 and snaps[0]["final"] is True
    # A straggler publish after close must not clear the final flag
    # (nor resurrect a retired snapshot on the non-final path).
    assert pub2.publish() is None
    snaps = aggregate.read_snapshots(str(tmp_path), clock=clock)
    assert len(snaps) == 1 and snaps[0]["final"] is True


# ======================================================================
# SLO monitor


def _avail_merged(total, errors):
    return aggregate.merge_snapshots([_snap("s", metrics=[
        _counter_metric("dct_requests_total", total, {"slot": "d"}),
        _counter_metric("dct_request_errors_total", errors, {"slot": "d"}),
    ])])


def test_slo_spec_grammar():
    specs = slo.parse_slo_spec(
        "availability:0.999;p99=latency:0.25@0.99;goodput:0.5;"
        "freshness:3600"
    )
    assert [s.name for s in specs] == [
        "availability", "p99", "goodput", "freshness"
    ]
    assert specs[1].threshold == 0.25
    assert specs[1].objective == 0.99
    assert specs[3].threshold == 3600
    for bad in (
        "availability", "latency:0.25", "availability:1.5",
        "latency:0@0.5", "nonsense:1", "freshness:-5",
    ):
        with pytest.raises(slo.SLOSpecError):
            slo.parse_slo_spec(bad)
    assert slo.parse_slo_spec("") == []


def test_availability_burn_rate_multi_window():
    emitted = []
    clock = FakeClock(0.0)
    mon = slo.SLOMonitor(
        slo.parse_slo_spec("availability:0.9"),
        fast_window_s=10.0, slow_window_s=100.0, burn_threshold=1.0,
        clock=clock,
        emit=lambda comp, event, **f: emitted.append((comp, event, f)),
    )
    # First observation: no window delta yet, no alert.
    st = mon.evaluate(_avail_merged(100, 0), now=0.0)
    assert st[0]["alerting"] is False
    # 100 more requests, all failing: burn = 1.0/0.1 = 10x on both.
    st = mon.evaluate(_avail_merged(200, 100), now=5.0)
    assert st[0]["burn_fast"] == pytest.approx(10.0)
    assert st[0]["alerting"] is True
    assert emitted and emitted[0][:2] == ("slo", "slo.alert")
    # Recovery: errors stop, windows roll past the burst.
    st = mon.evaluate(_avail_merged(1200, 100), now=120.0)
    assert st[0]["alerting"] is False
    assert emitted[-1][1] == "slo.resolved"
    # Edge-triggered: exactly one alert and one resolve.
    assert [e[1] for e in emitted] == ["slo.alert", "slo.resolved"]


def test_latency_slo_over_threshold_fraction():
    def merged(counts, count, total_sum):
        return aggregate.merge_snapshots([_snap("s", metrics=[{
            "name": "dct_request_latency_seconds", "type": "histogram",
            "help": "", "buckets": [0.1, 0.5, 1.0],
            "samples": [{
                "labels": {}, "counts": counts, "count": count,
                "sum": total_sum,
            }],
        }])])

    mon = slo.SLOMonitor(
        slo.parse_slo_spec("latency:0.5@0.9"),
        fast_window_s=10.0, slow_window_s=10.0, burn_threshold=1.0,
        clock=FakeClock(0.0),
    )
    mon.evaluate(merged([10, 10, 10], 10, 1.0), now=0.0)
    # 10 new requests, 5 over 0.5s: violation rate 0.5, budget 0.1 ->
    # burn 5x.
    st = mon.evaluate(merged([15, 15, 18], 20, 9.0), now=5.0)
    assert st[0]["burn_fast"] == pytest.approx(5.0)
    assert st[0]["alerting"] is True


def test_latency_threshold_between_buckets_counts_violations():
    """A threshold BETWEEN bucket boundaries must over-report, never
    under-report: only requests provably <= the threshold (the largest
    boundary at or below it) count as under. With the old >=-boundary
    pick, 100% of requests at 0.4 s would have met a 0.3 s SLO."""
    from dct_tpu.observability.slo import _latency_over_threshold

    hist = {"buckets": [0.25, 0.5, 1.0], "counts": [0, 10, 10],
            "count": 10, "sum": 4.0}  # all 10 requests took ~0.4 s
    total, over = _latency_over_threshold(hist, 0.3)
    assert (total, over) == (10, 10)
    # Exactly on a boundary: that boundary's count is provably under.
    assert _latency_over_threshold(hist, 0.5) == (10, 0)
    # Below every boundary: nothing is provably under.
    assert _latency_over_threshold(hist, 0.1) == (10, 10)
    # Beyond the last finite bucket: the +Inf tail counts as over.
    hist2 = {"buckets": [0.25], "counts": [4], "count": 10, "sum": 9.0}
    assert _latency_over_threshold(hist2, 5.0) == (10, 6)


def test_goodput_slo_uses_worst_gauge():
    merged = aggregate.merge_snapshots([_snap("t", metrics=[{
        "name": "dct_train_goodput_fraction", "type": "gauge",
        "help": "", "agg": "last",
        "samples": [
            {"labels": {"run_id": "a"}, "value": 0.9},
            {"labels": {"run_id": "b"}, "value": 0.2},
        ],
    }])])
    mon = slo.SLOMonitor(
        slo.parse_slo_spec("goodput:0.5"), burn_threshold=1.0,
        clock=FakeClock(0.0),
    )
    st = mon.evaluate(merged, now=0.0)
    # worst = 0.2 -> burn = 0.8/0.5 = 1.6 on both windows.
    assert st[0]["burn_fast"] == pytest.approx(1.6)
    assert st[0]["alerting"] is True


def test_freshness_slo_from_event_log(tmp_path):
    events = tmp_path / "events.jsonl"
    with open(events, "w") as f:
        f.write(json.dumps({"ts": 1000.0, "event": "full_rollout"}) + "\n")
        f.write(json.dumps({"ts": 2000.0, "event": "deploy_new_slot"}) + "\n")
    mon = slo.SLOMonitor(
        slo.parse_slo_spec("freshness:100"), burn_threshold=1.0,
        clock=FakeClock(0.0), events_path=str(events),
    )
    st = mon.evaluate(aggregate.merge_snapshots([]), now=2050.0)
    assert st[0]["burn_fast"] == pytest.approx(0.5)
    assert st[0]["alerting"] is False
    st = mon.evaluate(aggregate.merge_snapshots([]), now=2300.0)
    assert st[0]["burn_fast"] == pytest.approx(3.0)
    assert st[0]["alerting"] is True


def test_slo_no_data_never_alerts():
    mon = slo.SLOMonitor(
        slo.parse_slo_spec("availability:0.999;goodput:0.5"),
        clock=FakeClock(0.0),
    )
    st = mon.evaluate(aggregate.merge_snapshots([]), now=0.0)
    assert all(not s["alerting"] for s in st)
    assert all(s["data"] is False for s in st)


def test_slo_gauges_render_valid():
    mon = slo.SLOMonitor(
        slo.parse_slo_spec("availability:0.9"), clock=FakeClock(0.0),
    )
    text = mon.render(_avail_merged(10, 0), now=0.0)
    samples = parse_exposition_strict(text)
    assert 'dct_slo_burn_rate{slo="availability",window="fast"}' in samples
    assert samples['dct_slo_alert_active{slo="availability"}'] == 0


# ======================================================================
# exposition round-trip over every real /metrics body


def test_trainer_dump_body_roundtrips(tmp_path):
    from dct_tpu.observability.dump import write_train_metrics_prom
    from dct_tpu.observability.goodput import GoodputLedger

    led = GoodputLedger(clock=FakeClock(0.0))
    led.start()
    led.add("train_step", 5.0)
    path = str(tmp_path / "train_metrics.prom")
    out = write_train_metrics_prom(
        path, led.summary(), run_id="dct-t",
        samples_per_sec=42.0, val_loss=0.5,
        health={"events": {"nan_loss": 1}, "last_grad_norm": 2.0},
        resilience={"faults_injected": 0, "startup_debt_s": 1.5},
        compile_windows=[{
            "program": "scan_k1", "family": "weather_mlp",
            "config_hash": "abcd1234", "mesh": "data8_model1_seq1_pipe1",
            "count": 1, "seconds": 0.7,
        }],
        metrics_dir=str(tmp_path / "metrics"), proc="train-rank0",
    )
    assert out == path
    samples = parse_exposition_strict(open(path).read())
    assert samples['dct_train_samples_per_sec{run_id="dct-t"}'] == 42.0
    key = (
        'dct_compile_seconds_total{cache="disabled",'
        'config_hash="abcd1234",'
        'family="weather_mlp",mesh="data8_model1_seq1_pipe1",'
        'program="scan_k1",run_id="dct-t"}'
    )
    assert samples[key] == pytest.approx(0.7)
    # The final snapshot landed on the metrics plane and survives the
    # trainer's death (final flag).
    snaps = aggregate.read_snapshots(str(tmp_path / "metrics"))
    assert [s["proc"] for s in snaps] == ["train-rank0"]
    assert snaps[0]["final"] is True


def test_single_server_metrics_body_roundtrips():
    from dct_tpu.serving.server import _SlotMetrics

    m = _SlotMetrics()
    m.record("blue", 0.002, ok=True)
    m.record("blue", 0.3, ok=False)
    m.record("green", 0.004, ok=True)
    m.observe_batch(4, 2, 1)
    samples = parse_exposition_strict(m.prometheus_text())
    assert samples['dct_requests_total{slot="blue"}'] == 2
    assert samples['dct_request_errors_total{slot="blue"}'] == 1
    assert samples['dct_request_errors_total{slot="green"}'] == 0
    assert samples['dct_request_latency_seconds_count{slot="blue"}'] == 2
    assert samples["dct_serve_batch_rows_count"] == 1


def test_aggregated_pool_body_roundtrips(tmp_path):
    reg_a = MetricsRegistry()
    reg_a.counter("dct_requests_total", "r").inc(3, {"slot": "default"})
    reg_a.histogram("dct_request_latency_seconds", "l").observe(
        0.01, {"slot": "default"}
    )
    reg_b = MetricsRegistry()
    reg_b.counter("dct_requests_total", "r").inc(4, {"slot": "default"})
    aggregate.write_snapshot(reg_a.snapshot(proc="serve-1"), str(tmp_path))
    aggregate.write_snapshot(reg_b.snapshot(proc="serve-2"), str(tmp_path))
    text, merged = aggregate.aggregate_text(str(tmp_path))
    samples = parse_exposition_strict(text)
    assert samples['dct_requests_total{slot="default"}'] == 7
    assert samples['dct_requests_total{slot="default",proc="serve-1"}'] == 3
    assert merged.total("dct_requests_total") == 7


# ======================================================================
# live servers: in-process aggregation + the SLO alert e2e


def _post(url: str, body: bytes):
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"}
    )
    return urllib.request.urlopen(req, timeout=30)


def _scrape(url: str) -> str:
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        return r.read().decode()


@pytest.fixture()
def plane_env(tmp_path, monkeypatch):
    from dct_tpu.observability import events as events_mod

    metrics_dir = str(tmp_path / "metrics")
    monkeypatch.setenv("DCT_METRICS_DIR", metrics_dir)
    monkeypatch.setenv("DCT_METRICS_PUBLISH_S", "0")
    monkeypatch.setenv("DCT_EVENTS_DIR", str(tmp_path / "events"))
    monkeypatch.setenv("DCT_TELEMETRY_FLUSH_S", "0")
    # An earlier test's trainer may have installed ITS event log as the
    # process default (event_log_from_config -> set_default); the SLO
    # alert must land in THIS test's env-built log.
    monkeypatch.setattr(events_mod, "_explicit", None)
    monkeypatch.setattr(events_mod, "_cached", None)
    return metrics_dir


def _start_server(weights, meta):
    import threading as _threading

    from dct_tpu.serving.server import make_server_from_weights

    server = make_server_from_weights(weights, meta)
    thread = _threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    return server, f"http://127.0.0.1:{server.server_address[1]}"


def test_two_servers_one_scrape_reports_fleet_totals(plane_env):
    """The tier-1 aggregation acceptance: traffic lands on TWO servers
    sharing one metrics dir (distinct proc names — the in-process twin
    of the SO_REUSEPORT pool, which the CI smoke drives forked); ONE
    scrape of either must report the fleet totals, with per-proc series
    summing to them."""
    from dct_tpu.serving.loadgen import synthetic_mlp

    weights, meta = synthetic_mlp()
    body = json.dumps({"data": [[0.1, -0.2, 0.3, 0.0, 1.0]]}).encode()
    server_a, url_a = _start_server(weights, meta)
    server_b, url_b = _start_server(weights, meta)
    # Distinct proc names: both servers share this test process's pid.
    server_a.metrics_publisher.proc = "serve-a"
    server_b.metrics_publisher.proc = "serve-b"
    try:
        for _ in range(3):
            with _post(url_a + "/score", body) as r:
                assert r.status == 200
        for _ in range(5):
            with _post(url_b + "/score", body) as r:
                assert r.status == 200
        text = _scrape(url_a)
        samples = parse_exposition_strict(text)
        assert samples['dct_requests_total{slot="default"}'] == 8
        assert samples[
            'dct_requests_total{slot="default",proc="serve-a"}'
        ] == 3
        assert samples[
            'dct_requests_total{slot="default",proc="serve-b"}'
        ] == 8 - 3
        # Histograms summed bucket-wise across processes.
        assert samples[
            'dct_request_latency_seconds_count{slot="default"}'
        ] == 8
        # Scraping the OTHER process gives the same totals.
        other = parse_exposition_strict(_scrape(url_b))
        assert other['dct_requests_total{slot="default"}'] == 8
    finally:
        server_a.shutdown()
        server_a.server_close()
        server_b.shutdown()
        server_b.server_close()


def test_slo_burn_alert_fires_on_live_server(plane_env, tmp_path,
                                             monkeypatch):
    """The synthetic SLO e2e: a broken model makes every request a
    server fault; with tiny windows the second scrape must flip
    dct_slo_alert_active to 1 and put slo.alert on the event log."""
    from dct_tpu.serving.loadgen import synthetic_mlp

    monkeypatch.setenv("DCT_SLO_SPEC", "availability:0.99")
    monkeypatch.setenv("DCT_SLO_FAST_WINDOW_S", "30")
    monkeypatch.setenv("DCT_SLO_SLOW_WINDOW_S", "30")
    weights, meta = synthetic_mlp()
    server, url = _start_server(weights, meta)
    try:
        body = json.dumps({"data": [[0.1, -0.2, 0.3, 0.0, 1.0]]}).encode()
        with _post(url + "/score", body) as r:
            assert r.status == 200
        first = _scrape(url)
        assert 'dct_slo_alert_active{slo="availability"} 0' in first
        # Break the model: forwards now raise -> per-request 500s.
        server.model_weights = {"w0": np.zeros((2, 2), np.float32)}
        for _ in range(10):
            try:
                _post(url + "/score", body).close()
            except urllib.error.HTTPError as e:
                assert e.code == 500
        text = _scrape(url)
        samples = parse_exposition_strict(text)
        assert samples['dct_slo_alert_active{slo="availability"}'] == 1
        assert samples[
            'dct_slo_burn_rate{slo="availability",window="fast"}'
        ] > 1.0
        events_path = os.path.join(
            os.environ["DCT_EVENTS_DIR"], "events.jsonl"
        )
        recs = [
            json.loads(line) for line in open(events_path)
        ]
        alerts = [r for r in recs if r.get("event") == "slo.alert"]
        assert alerts and alerts[0]["slo"] == "availability"
        assert alerts[0]["component"] == "slo"
    finally:
        server.shutdown()
        server.server_close()


def test_plane_off_keeps_legacy_local_body(tmp_path, monkeypatch):
    monkeypatch.delenv("DCT_METRICS_DIR", raising=False)
    from dct_tpu.serving.loadgen import synthetic_mlp

    weights, meta = synthetic_mlp()
    server, url = _start_server(weights, meta)
    try:
        assert getattr(server, "metrics_publisher", None) is None
        body = json.dumps({"data": [[0.1, -0.2, 0.3, 0.0, 1.0]]}).encode()
        with _post(url + "/score", body) as r:
            assert r.status == 200
        samples = parse_exposition_strict(_scrape(url))
        assert samples['dct_requests_total{slot="default"}'] == 1
        assert not any("proc=" in k for k in samples)
    finally:
        server.shutdown()
        server.server_close()


def test_malformed_slo_spec_disables_monitor_not_server(
    plane_env, monkeypatch, capfd
):
    monkeypatch.setenv("DCT_SLO_SPEC", "latency:borked")
    from dct_tpu.serving.loadgen import synthetic_mlp

    weights, meta = synthetic_mlp()
    server, url = _start_server(weights, meta)
    try:
        assert getattr(server, "slo_monitor", None) is None
        assert server.metrics_publisher is not None
        assert "DCT_SLO_SPEC disabled" in capfd.readouterr().err
    finally:
        server.shutdown()
        server.server_close()


# ======================================================================
# compile accounting


def test_ledger_records_compile_windows():
    from dct_tpu.observability.goodput import (
        GoodputLedger,
        compile_report,
        config_hash,
        mesh_descriptor,
    )

    clock = FakeClock(0.0)
    led = GoodputLedger(clock=clock)
    led.start()
    with led.dispatch("train_step", key="scan_k4"):
        clock.advance(3.0)  # first dispatch: compile
    with led.dispatch("train_step", key="scan_k4"):
        clock.advance(0.1)  # seen key: train_step
    led.add_dispatch("train_step", "scan_k1", 0.5)
    assert led.compile_windows == [("scan_k4", 3.0), ("scan_k1", 0.5)]
    assert led.seconds["compile"] == pytest.approx(3.5)
    assert led.seconds["train_step"] == pytest.approx(0.1)

    report = compile_report(
        led.compile_windows, family="weather_mlp",
        config_hash="ffff0000", mesh="data8_model1_seq1_pipe1",
    )
    assert {r["program"]: r["count"] for r in report} == {
        "scan_k4": 1, "scan_k1": 1
    }
    assert all(r["family"] == "weather_mlp" for r in report)
    # Identity helpers are stable and order-insensitive.
    assert config_hash({"a": 1, "b": 2}) == config_hash({"b": 2, "a": 1})

    class M:
        data, model, seq, pipe = 8, 1, 1, 1

    assert mesh_descriptor(M()) == "data8_model1_seq1_pipe1"


# ======================================================================
# heartbeat progress age


def test_heartbeat_progress_age_vs_write_age(tmp_path):
    from dct_tpu.observability.heartbeat import (
        HeartbeatMonitor,
        HeartbeatWriter,
    )

    clock = FakeClock(0.0)
    w = HeartbeatWriter(str(tmp_path), 0, run_id="r", clock=clock)
    mon = HeartbeatMonitor(
        str(tmp_path), 1, stall_seconds=60.0, run_id="r", clock=clock,
    )
    w.beat(step=1, epoch=0, force=True)
    clock.advance(10.0)
    # Same step beaten again: the write is fresh, progress is not.
    w.beat(step=1, epoch=0, force=True)
    clock.advance(5.0)
    s = mon.scan()[0]
    assert s.state == "ok"
    assert s.age_seconds == pytest.approx(5.0)
    assert s.progress_age_seconds == pytest.approx(15.0)
    # Progress resumes: the progress clock resets, write age unchanged.
    w.beat(step=2, epoch=0, force=True)
    clock.advance(2.0)
    s = mon.scan()[0]
    assert s.progress_age_seconds == pytest.approx(2.0)
    rep = mon.report()
    assert rep["max_progress_age_seconds"] == pytest.approx(2.0)


def test_heartbeat_progress_age_missing_field_falls_back(tmp_path):
    from dct_tpu.observability.heartbeat import (
        HeartbeatMonitor,
        heartbeat_path,
    )

    clock = FakeClock(100.0)
    rec = {"rank": 0, "run_id": "r", "pid": os.getpid(), "time": 90.0,
           "step": 3, "epoch": 1, "phase": "train"}
    os.makedirs(tmp_path, exist_ok=True)
    with open(heartbeat_path(str(tmp_path), 0), "w") as f:
        json.dump(rec, f)
    mon = HeartbeatMonitor(
        str(tmp_path), 1, stall_seconds=60.0, run_id="r", clock=clock,
    )
    s = mon.scan()[0]
    assert s.progress_age_seconds == pytest.approx(s.age_seconds)


def test_launcher_publishes_progress_gauge(tmp_path, monkeypatch):
    """The launcher's monitor pass lands per-rank progress-age gauges
    on the metrics plane (unit-level: _flag_heartbeats with a real
    publisher)."""
    from dct_tpu.launch.launcher import (
        LocalProcessLauncher,
        _launcher_metrics_publisher,
    )
    from dct_tpu.observability.events import EventLog
    from dct_tpu.observability.heartbeat import (
        HeartbeatMonitor,
        HeartbeatWriter,
    )

    hb_dir = str(tmp_path / "hb")
    metrics_dir = str(tmp_path / "metrics")
    clock = FakeClock(0.0)
    w = HeartbeatWriter(hb_dir, 0, run_id="r", clock=clock)
    w.beat(step=5, epoch=1, force=True)
    # Rank 1 finished cleanly: its age grows by design and must NOT be
    # published (a max-agg gauge would page on a healthy completion).
    w1 = HeartbeatWriter(hb_dir, 1, run_id="r", clock=clock)
    w1.beat(step=9, epoch=2, phase="done", force=True)
    # Rank 2 already exited and was reaped — same exclusion.
    w2 = HeartbeatWriter(hb_dir, 2, run_id="r", clock=clock)
    w2.beat(step=3, epoch=0, force=True)
    clock.advance(7.0)
    env = {
        "DCT_METRICS_DIR": metrics_dir,
        "DCT_METRICS_PUBLISH_S": "0",
        "DCT_RUN_ID": "r",
    }
    pub = _launcher_metrics_publisher(env, "launcher-test")
    assert pub is not None
    gauge = pub.registry.gauge(
        "dct_rank_progress_age_seconds", "progress", agg="max"
    )
    launcher = LocalProcessLauncher()
    monitor = HeartbeatMonitor(
        hb_dir, 3, stall_seconds=60.0, run_id="r", clock=clock
    )
    launcher._flag_heartbeats(
        monitor, {2: 0}, set(), EventLog(None, run_id="r"),
        progress_gauge=gauge, metrics_pub=pub,
    )
    merged = aggregate.merge_snapshots(
        aggregate.read_snapshots(metrics_dir)
    )
    assert merged.value(
        "dct_rank_progress_age_seconds", {"rank": 0}
    ) == pytest.approx(7.0)
    assert merged.value(
        "dct_rank_progress_age_seconds", {"rank": 1}
    ) is None
    assert merged.value(
        "dct_rank_progress_age_seconds", {"rank": 2}
    ) is None
    pub.close()


def test_metrics_plane_off_no_launcher_publisher():
    from dct_tpu.launch.launcher import _launcher_metrics_publisher

    assert _launcher_metrics_publisher({}, "launcher-x") is None
    assert _launcher_metrics_publisher(
        {"DCT_METRICS_DIR": "x", "DCT_OBSERVABILITY": "0"}, "launcher-x"
    ) is None


# ======================================================================
# inspector + report satellites


def test_inspect_report_covers_new_events(tmp_path):
    from dct_tpu.observability.inspect import build_report

    events = [
        {"ts": 1.0, "run_id": "r", "component": "trainer",
         "event": "fit_start"},
        {"ts": 2.0, "run_id": "r", "component": "serve",
         "event": "serve.batch_flush", "rows": 8, "requests": 4,
         "queue_depth": 0},
        {"ts": 2.5, "run_id": "r", "component": "serve",
         "event": "serve.batch_error", "rows": 2, "requests": 1},
        {"ts": 3.0, "run_id": "r", "component": "deploy",
         "event": "deploy.gate", "stage": "canary", "decision": "hold",
         "reason": "regression"},
        {"ts": 4.0, "run_id": "r", "component": "slo",
         "event": "slo.alert", "slo": "availability", "burn_fast": 9.0,
         "burn_slow": 2.0},
        {"ts": 5.0, "run_id": "r", "component": "compile",
         "event": "compile.window", "program": "scan_k4",
         "family": "weather_mlp", "config_hash": "ab12cd34",
         "mesh": "data8_model1_seq1_pipe1", "count": 1, "seconds": 2.5},
    ]
    report = build_report(events, [], [], "r", None)
    assert "deploy.gate" in report and "decision=hold" in report
    assert "slo.alert" in report and "availability" in report
    assert "compile.window" in report
    assert "4 requests merged into 8 rows" in report
    assert "flush errors: 1" in report
    assert "total compile: 2.5" in report


def test_inspect_surfaces_bench_mfu(tmp_path):
    from dct_tpu.observability.inspect import (
        _bench_mfu_lines,
        load_bench_record,
    )

    # No MFU in the record: said so, with the platform.
    with open(tmp_path / "BENCH_r09.json", "w") as f:
        json.dump({"parsed": {"platform": "cpu"}}, f)
    bench = load_bench_record(str(tmp_path))
    assert bench[0] == "BENCH_r09.json"
    text = "\n".join(_bench_mfu_lines(bench))
    assert "no MFU in the record" in text and "platform=cpu" in text
    # Unparsable shape (parsed: null) named, not silently omitted.
    with open(tmp_path / "BENCH_r10.json", "w") as f:
        json.dump({"parsed": None, "tail": "..."}, f)
    text = "\n".join(_bench_mfu_lines(load_bench_record(str(tmp_path))))
    assert "unparsable" in text
    # MFU present.
    with open(tmp_path / "BENCH_r11.json", "w") as f:
        json.dump({"parsed": {"mfu": 0.41, "platform": "tpu"}}, f)
    text = "\n".join(_bench_mfu_lines(load_bench_record(str(tmp_path))))
    assert "mfu=0.41" in text
    assert _bench_mfu_lines(None)[-1].startswith("  (no BENCH")


# ======================================================================
# env-contract sanity


def test_observability_config_metrics_plane_knobs(monkeypatch):
    from dct_tpu.config import ObservabilityConfig

    c = ObservabilityConfig.from_env()
    assert c.metrics_dir == "" and c.metrics_publish_s == 2.0
    monkeypatch.setenv("DCT_METRICS_DIR", "/tmp/x")
    monkeypatch.setenv("DCT_SLO_SPEC", "goodput:0.5")
    monkeypatch.setenv("DCT_SLO_BURN_THRESHOLD", "2.5")
    c = ObservabilityConfig.from_env()
    assert c.metrics_dir == "/tmp/x"
    assert c.slo_spec == "goodput:0.5"
    assert c.slo_burn_threshold == 2.5
    # The default spec must parse — a shipped default that raises would
    # disable SLO monitoring everywhere.
    assert len(slo.parse_slo_spec(ObservabilityConfig().slo_spec)) == 2


def test_nan_values_render_parseable():
    reg = MetricsRegistry()
    reg.gauge("g", "g").set(float("nan"))
    reg.gauge("g2", "g").set(math.inf)
    samples = parse_exposition_strict(reg.render())
    assert math.isnan(samples["g"])
    assert samples["g2"] == math.inf

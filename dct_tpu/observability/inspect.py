"""Run-inspector CLI: join events + spans + goodput + heartbeats into a
human-readable cycle report and write the Perfetto trace export.

Usage::

    python -m dct_tpu.observability.inspect <run_dir> [--run-id ID]
        [--out trace.json] [--no-trace]

``run_dir`` is any directory holding a run's observability artifacts —
the events dir itself, or a parent containing ``events.jsonl``,
``spans/*.jsonl`` and ``rank_*.json`` heartbeat files anywhere below it
(the layouts the trainer/launcher produce by default). The report:

1. resolves the run-correlation ID (``--run-id`` pins one; otherwise
   the newest ID seen in the event log);
2. reconstructs the cycle timeline: launch window, per-rank training
   windows, per-epoch metrics, checkpoint saves, deploy stages;
3. names every rank's final heartbeat state and progress;
4. prints the goodput/badput breakdown from the run-end summary event;
5. lists health incidents (``health.*`` events);
6. merges all span files into ``trace.json`` (Chrome-trace-event JSON,
   Perfetto-loadable) and prints how to open it.

Everything is read-only over the artifacts; missing surfaces degrade to
"(none found)" lines, never errors — the inspector must work on partial
runs, which is exactly when an operator reaches for it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from dct_tpu.observability.trace_export import export_run


def _find_files(root: str, name_filter) -> list[str]:
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for fn in sorted(filenames):
            if name_filter(fn, dirpath):
                out.append(os.path.join(dirpath, fn))
    return out


def load_events(run_dir: str) -> list[dict]:
    from dct_tpu.observability.trace_export import read_jsonl

    recs = []
    for path in _find_files(
        run_dir, lambda fn, d: fn == "events.jsonl"
    ):
        recs.extend(read_jsonl(path, require_key="event"))
    recs.sort(key=lambda r: r.get("ts", 0.0))
    return recs


def load_heartbeats(run_dir: str) -> list[dict]:
    out = []
    for path in _find_files(
        run_dir,
        lambda fn, d: fn.startswith("rank_") and fn.endswith(".json"),
    ):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(rec, dict) and "rank" in rec and "phase" in rec:
            out.append(rec)
    out.sort(key=lambda r: int(r.get("rank", 0)))
    return out


def pick_run_id(events: list[dict], explicit: str | None) -> str | None:
    if explicit:
        return explicit
    latest: str | None = None
    latest_ts = -1.0
    for r in events:
        rid = r.get("run_id")
        if rid and r.get("ts", 0.0) >= latest_ts:
            latest, latest_ts = rid, r.get("ts", 0.0)
    return latest


def _fmt_ts(ts: float | None, t0: float | None) -> str:
    if ts is None or t0 is None:
        return "      ?"
    return f"+{ts - t0:7.2f}s"


def _fmt_num(v) -> str:
    if isinstance(v, (int, float)):
        return f"{v:.4f}" if isinstance(v, float) else str(v)
    return str(v)


def load_bench_record(run_dir: str) -> tuple[str, dict] | None:
    """Newest ``BENCH*.json`` under ``run_dir`` (rounds sort by name),
    or None. The cycle report surfaces its MFU instead of silently
    omitting the number an operator will otherwise chase."""
    paths = _find_files(
        run_dir,
        lambda fn, d: fn.startswith("BENCH") and fn.endswith(".json"),
    )
    if not paths:
        return None
    path = sorted(paths, key=lambda p: os.path.basename(p))[-1]
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return os.path.basename(path), {}
    return os.path.basename(path), rec if isinstance(rec, dict) else {}


def _bench_mfu_lines(bench: tuple[str, dict] | None) -> list[str]:
    lines = ["", "Bench MFU:"]
    if bench is None:
        lines.append("  (no BENCH*.json record in the run dir)")
        return lines
    name, rec = bench
    parsed = rec.get("parsed")
    if not isinstance(parsed, dict):
        lines.append(
            f"  {name}: record present but unparsable (stdout "
            "overflowed the driver tail?) — no MFU to report"
        )
        return lines
    mfu = parsed.get("mfu")
    if mfu is not None:
        line = f"  {name}: mfu={_fmt_num(mfu)}"
        source = parsed.get("mfu_source")
        if source:
            line += f" ({source})"
        lines.append(line)
    else:
        lines.append(
            f"  {name}: no MFU in the record "
            f"(platform={parsed.get('platform')}: CPU rounds carry "
            "no on-chip MFU)"
        )
    return lines


def build_report(
    events: list[dict],
    heartbeats: list[dict],
    spans: list[dict],
    run_id: str | None,
    trace_path: str | None,
    bench: tuple[str, dict] | None = None,
    lineage: list[dict] | None = None,
    incidents: list[dict] | None = None,
) -> str:
    """The cycle report as one printable string (pure function of the
    artifacts — unit-testable without capturing stdout)."""
    lines: list[str] = []
    ev = [e for e in events if run_id is None or e.get("run_id") == run_id]
    hb = [
        h for h in heartbeats
        if run_id is None or h.get("run_id") in (None, run_id)
    ]
    sp = [s for s in spans if run_id is None or s.get("trace_id") == run_id]
    t0 = ev[0]["ts"] if ev else (sp[0]["t0"] if sp else None)
    lines.append("=" * 72)
    lines.append(f"dct_tpu run inspector — run_id {run_id or '(unknown)'}")
    lines.append("=" * 72)
    lines.append(
        f"events: {len(ev)}   spans: {len(sp)}   "
        f"heartbeats: {len(hb)} rank file(s)"
    )

    # -- cycle timeline ------------------------------------------------
    lines.append("")
    lines.append("Cycle timeline (selected events):")
    interesting = {
        "launch_start", "launch_end", "fit_start", "fit_end",
        "fit_failed", "goodput_summary", "best_saved",
        "resume_state_saved", "run_start", "run_end",
        "deploy_new_slot", "shadow", "canary", "full_rollout",
        "rank_exit", "rank_stalled", "rank_missing",
    }
    # The cycle is no longer trainer-centric: serving, gating, SLO and
    # compile-accounting events belong on the same timeline (serve.*
    # stays OFF it — per-flush events would drown the launch story; the
    # Serving section below summarizes them instead).
    interesting_prefixes = (
        "health.", "deploy.", "slo.", "compile.", "restart.",
        # Always-on loop actors (docs/CONTINUOUS.md): rounds, ingested
        # generations and mid-run promotions are cycle landmarks.
        "loop.", "ingest.",
        # Multi-tenant scheduler (docs/SCHEDULER.md): leases, preempts
        # and tenant lifecycle are session landmarks.
        "sched.", "tenant.",
        # MPMD pipeline trainer (docs/PARALLELISM.md §MPMD): stage
        # lifecycle, cross-topology pivots, and transfer faults are
        # session landmarks (per-epoch mpmd.step_report stays off the
        # timeline — the MPMD section below summarizes it).
        "mpmd.",
        # Flight-recorder captures (docs/OBSERVABILITY.md §roofline):
        # an operator-triggered mid-run trace is a timeline landmark.
        # roofline.* stays off it — run-end batch records the Roofline
        # section below summarizes.
        "profile.",
        # Telemetry history plane (docs/OBSERVABILITY.md §9): anomaly
        # edges and assembled incident bundles are exactly the
        # landmarks an operator reads the timeline for.
        "anomaly.", "incident.",
        # Elastic serving (docs/SERVING.md §elasticity): pool deaths /
        # respawns / circuit-breaks, scale steps and (throttled) shed
        # episodes are rare and load-bearing — unlike per-flush
        # serve.batch_* they belong on the landmark timeline.
        "serve.pool_", "autoscale.", "admission.",
    )
    shown = 0
    for r in ev:
        name = r.get("event", "?")
        if name not in interesting and not name.startswith(
            interesting_prefixes
        ):
            continue
        if name == "mpmd.step_report":
            continue  # per-epoch; the MPMD section summarizes it
        who = (
            f"rank {r['rank']}" if r.get("rank") is not None else "host"
        )
        extra = ""
        if name == "launch_end":
            extra = f" returncodes={r.get('returncodes')}"
        if name.startswith("health."):
            extra = (
                f" value={r.get('value')} step={r.get('step')}"
                f" halt={r.get('halt')}"
            )
        if name == "deploy.gate":
            extra = (
                f" stage={r.get('stage')} decision={r.get('decision')}"
                f" reason={r.get('reason')}"
            )
        if name.startswith("slo."):
            extra = (
                f" slo={r.get('slo')} burn_fast={r.get('burn_fast')}"
                f" burn_slow={r.get('burn_slow')}"
            )
        if name == "compile.window":
            extra = (
                f" program={r.get('program')} "
                f"seconds={_fmt_num(r.get('seconds'))}"
            )
        if name == "loop.promoted":
            extra = (
                f" generation={r.get('generation')}"
                f" freshness_s={_fmt_num(r.get('freshness_s'))}"
            )
        if name == "ingest.processed":
            extra = (
                f" generation={r.get('generation')} mode={r.get('mode')}"
                f" rows={r.get('rows')}"
            )
        if name in ("sched.grant", "sched.release", "sched.preempt",
                    "tenant.parked"):
            extra = " " + " ".join(
                f"{k}={r[k]}" for k in (
                    "tenant", "wait_s", "waited_s", "outcome", "chip_s",
                    "waiter", "classification",
                )
                if r.get(k) is not None
            )
        lines.append(
            f"  {_fmt_ts(r.get('ts'), t0)}  "
            f"{r.get('component', '?'):10s} {who:8s} {name}{extra}"
        )
        shown += 1
    if not shown:
        lines.append("  (none found)")

    # -- per-epoch metrics ---------------------------------------------
    epochs = [r for r in ev if r.get("event") == "epoch_end"]
    lines.append("")
    lines.append("Epochs:")
    if epochs:
        for r in epochs:
            lines.append(
                f"  epoch {r.get('epoch')}: "
                f"train_loss={_fmt_num(r.get('train_loss'))} "
                f"val_loss={_fmt_num(r.get('val_loss'))} "
                f"val_acc={_fmt_num(r.get('val_acc'))} "
                f"goodput={_fmt_num(r.get('goodput_fraction'))}"
            )
    else:
        lines.append("  (none found)")

    # -- ranks ---------------------------------------------------------
    lines.append("")
    lines.append("Ranks (final heartbeat):")
    if hb:
        for h in hb:
            lines.append(
                f"  rank {h.get('rank')}: phase={h.get('phase')} "
                f"epoch={h.get('epoch')} step={h.get('step')} "
                f"pid={h.get('pid')}"
            )
    else:
        span_ranks = sorted(
            {s.get("rank") for s in sp if s.get("rank") is not None}
        )
        if span_ranks:
            for r in span_ranks:
                n = sum(1 for s in sp if s.get("rank") == r)
                lines.append(f"  rank {r}: {n} span(s), no heartbeat file")
        else:
            lines.append("  (none found)")

    # -- goodput -------------------------------------------------------
    lines.append("")
    lines.append("Goodput:")
    summaries = [r for r in ev if r.get("event") == "goodput_summary"]
    if summaries:
        s = summaries[-1]
        lines.append(
            f"  wall {_fmt_num(s.get('wall_seconds'))}s, "
            f"goodput_fraction {_fmt_num(s.get('goodput_fraction'))}"
        )
        for cat, sec in sorted((s.get("categories") or {}).items()):
            lines.append(f"    {cat:18s} {_fmt_num(sec)}s")
        ua = s.get("unattributed_seconds")
        if ua is not None:
            lines.append(f"    {'unattributed':18s} {_fmt_num(ua)}s")
    else:
        lines.append("  (no goodput_summary event)")

    # -- health --------------------------------------------------------
    lines.append("")
    lines.append("Health:")
    health = [
        r for r in ev if str(r.get("event", "")).startswith("health.")
    ]
    if health:
        for r in health:
            lines.append(
                f"  {r['event']}: value={r.get('value')} "
                f"step={r.get('step')} epoch={r.get('epoch')} "
                f"halt={r.get('halt')}"
            )
    else:
        lines.append("  (no health events — clean run)")

    # -- serving (micro-batcher + request-path events) ----------------
    lines.append("")
    lines.append("Serving:")
    flushes = [r for r in ev if r.get("event") == "serve.batch_flush"]
    berrors = [r for r in ev if r.get("event") == "serve.batch_error"]
    if flushes or berrors:
        rows = sum(int(r.get("rows") or 0) for r in flushes)
        reqs = sum(int(r.get("requests") or 0) for r in flushes)
        lines.append(
            f"  batch flushes: {len(flushes)} "
            f"({reqs} requests merged into {rows} rows"
            + (
                f", {reqs / len(flushes):.1f} req/flush"
                if flushes else ""
            )
            + f"); flush errors: {len(berrors)}"
        )
    else:
        lines.append(
            "  (no serve.* events — traffic untraced or none served; "
            "serving telemetry is opt-in via DCT_SERVE_TRACE)"
        )
    sheds = [r for r in ev if r.get("event") == "admission.shed"]
    scales = [
        r for r in ev
        if str(r.get("event", "")).startswith("autoscale.scale_")
    ]
    heals = [
        r for r in ev if r.get("event") == "serve.pool_respawn"
    ]
    if sheds or scales or heals:
        shed_total = sum(int(r.get("count") or 0) for r in sheds)
        ups = sum(
            1 for r in scales if r.get("event") == "autoscale.scale_up"
        )
        lines.append(
            f"  elasticity: {shed_total} shed "
            f"({len(sheds)} admission.shed records), "
            f"{ups} scale-up / {len(scales) - ups} scale-down, "
            f"{len(heals)} respawned workers"
        )

    # -- always-on loop -----------------------------------------------
    loop_ev = [
        r for r in ev
        if str(r.get("event", "")).startswith(("loop.", "ingest."))
    ]
    if loop_ev:
        lines.append("")
        lines.append("Continuous loop:")
        rounds = [r for r in loop_ev if r.get("event") == "loop.round"]
        ingests = [
            r for r in loop_ev if r.get("event") == "ingest.processed"
        ]
        promos = [r for r in loop_ev if r.get("event") == "loop.promoted"]
        held = [
            r for r in loop_ev if r.get("event") == "loop.promotion_held"
        ]
        lines.append(
            f"  rounds: {len(rounds)}; generations ingested: "
            f"{len(ingests)}; promotions: {len(promos)}; held: {len(held)}"
        )
        fresh = [
            r.get("freshness_s") for r in promos
            if isinstance(r.get("freshness_s"), (int, float))
        ]
        if fresh:
            lines.append(
                f"  freshness_s: last={_fmt_num(fresh[-1])} "
                f"mean={_fmt_num(sum(fresh) / len(fresh))} "
                f"worst={_fmt_num(max(fresh))}"
            )
        stops = [r for r in loop_ev if r.get("event") == "loop.stop"]
        if stops:
            s = stops[-1]
            lines.append(
                f"  stopped: reason={s.get('reason')} "
                f"goodput={_fmt_num(s.get('goodput'))} "
                f"wall={_fmt_num(s.get('wall_s'))}s"
            )

    # -- multi-tenant scheduler ---------------------------------------
    sched_ev = [
        r for r in ev
        if str(r.get("event", "")).startswith(("sched.", "tenant."))
    ]
    if sched_ev:
        lines.append("")
        lines.append("Tenants:")
        starts = [r for r in sched_ev if r.get("event") == "sched.start"]
        if starts:
            s = starts[-1]
            lines.append(
                f"  session: {len(s.get('tenants') or [])} tenant(s), "
                f"concurrent={s.get('concurrent')} "
                f"preempt_wait_s={s.get('preempt_wait_s')} "
                f"shared_cache={s.get('shared_cache')}"
            )
        names = sorted({
            r.get("tenant") for r in sched_ev if r.get("tenant")
        })
        for name in names:
            mine = [r for r in sched_ev if r.get("tenant") == name]
            grants = [r for r in mine if r["event"] == "sched.grant"]
            rels = [r for r in mine if r["event"] == "sched.release"]
            chip = sum(float(r.get("chip_s") or 0.0) for r in rels)
            waits = [
                float(r.get("wait_s") or 0.0) for r in grants
            ]
            preempted = sum(
                1 for r in rels if r.get("outcome") == "preempted"
            )
            restarts = sum(int(r.get("restarts") or 0) for r in rels)
            parked = [r for r in mine if r["event"] == "tenant.parked"]
            stops = [r for r in mine if r["event"] == "tenant.stop"]
            line = (
                f"  {name}: leases={len(rels)} "
                f"chip_s={chip:.2f}"
            )
            if waits:
                line += (
                    f" mean_wait_s={sum(waits) / len(waits):.2f}"
                )
            if preempted:
                line += f" preempted={preempted}"
            if restarts:
                line += f" healed_restarts={restarts}"
            if parked:
                line += (
                    f" PARKED ({parked[-1].get('classification')})"
                )
            if stops and stops[-1].get("promotions") is not None:
                line += f" promotions={stops[-1]['promotions']}"
            lines.append(line)
        sstops = [r for r in sched_ev if r.get("event") == "sched.stop"]
        if sstops:
            s = sstops[-1]
            lines.append(
                f"  stopped: reason={s.get('reason')} "
                f"rounds={s.get('total_rounds')} "
                f"preempts={s.get('preempts')} "
                f"wall={_fmt_num(s.get('wall_s'))}s"
            )

    # -- MPMD pipeline ------------------------------------------------
    mpmd_ev = [
        r for r in ev if str(r.get("event", "")).startswith("mpmd.")
    ]
    if mpmd_ev:
        lines.append("")
        lines.append("MPMD pipeline:")
        starts = [
            r for r in mpmd_ev if r.get("event") == "mpmd.stage_start"
        ]
        if starts:
            s = starts[-1]
            lines.append(
                f"  stages: {s.get('n_stages')} "
                f"schedule={s.get('schedule')}"
            )
        reports = [
            r for r in mpmd_ev if r.get("event") == "mpmd.step_report"
        ]
        if reports:
            last = reports[-1]
            lines.append(
                f"  epochs reported: {len(reports)}; last bubble: "
                f"steady={_fmt_num(last.get('steady_bubble'))} "
                f"step={_fmt_num(last.get('step_bubble'))} "
                f"analytic={_fmt_num(last.get('analytic_bubble'))}"
            )
            for st in last.get("stages") or []:
                lines.append(
                    f"    stage {st.get('stage')}: "
                    f"busy={_fmt_num(st.get('busy_s'))}s "
                    f"fill={_fmt_num(st.get('fill_s'))}s "
                    f"steady={_fmt_num(st.get('steady_s'))}s "
                    f"drain={_fmt_num(st.get('drain_s'))}s "
                    f"transfer_wait={_fmt_num(st.get('transfer_wait_s'))}s"
                )
        for r in mpmd_ev:
            if r.get("event") == "mpmd.pivot":
                lines.append(
                    f"  pivot: {r.get('direction')} "
                    f"@epochs={r.get('epochs_completed')}"
                )
            if r.get("event") == "mpmd.transfer_timeout":
                lines.append(
                    f"  TRANSFER TIMEOUT stage {r.get('stage')}: "
                    f"{str(r.get('error'))[:120]}"
                )

    # -- deploy gates / SLO -------------------------------------------
    lines.append("")
    lines.append("Gates & SLO:")
    gates = [r for r in ev if r.get("event") == "deploy.gate"]
    slo_ev = [
        r for r in ev
        if str(r.get("event", "")).startswith("slo.")
    ]
    for r in gates:
        lines.append(
            f"  gate {r.get('stage')}: {r.get('decision')} "
            f"({r.get('reason')})"
        )
    for r in slo_ev:
        lines.append(
            f"  {r['event']}: {r.get('slo')} "
            f"burn fast={_fmt_num(r.get('burn_fast'))} "
            f"slow={_fmt_num(r.get('burn_slow'))}"
        )
    if not gates and not slo_ev:
        lines.append("  (no deploy.gate or slo.* events)")

    # -- compile accounting -------------------------------------------
    lines.append("")
    lines.append("Compile windows (family/config-hash/mesh):")
    compiles = [r for r in ev if r.get("event") == "compile.window"]
    if compiles:
        for r in compiles:
            lines.append(
                f"  {r.get('program')}: {_fmt_num(r.get('seconds'))}s "
                f"x{r.get('count')} "
                f"[{r.get('family')}/{r.get('config_hash')}/"
                f"{r.get('mesh')}] "
                f"cache={r.get('cache', 'disabled')}"
            )
        total = sum(float(r.get("seconds") or 0.0) for r in compiles)
        by_cache: dict[str, int] = {}
        for r in compiles:
            c = str(r.get("cache", "disabled"))
            by_cache[c] = by_cache.get(c, 0) + int(r.get("count") or 1)
        cache_line = " / ".join(
            f"{k} {by_cache[k]}" for k in sorted(by_cache)
        )
        lines.append(
            f"  total compile: {total:.4f}s  (cache: {cache_line})"
        )
    else:
        lines.append("  (no compile.window events)")

    # -- roofline (cost-model efficiency accounting) ------------------
    lines.append("")
    lines.append("Roofline (XLA cost model x measured dispatch):")
    reports = [r for r in ev if r.get("event") == "roofline.report"]
    if not reports:
        # Fall back to the capture-time analytic records so a run that
        # died before the run-end join still shows its program costs.
        reports = [r for r in ev if r.get("event") == "roofline.program"]
    if reports:
        # Newest record per program name wins (a resumed session can
        # report a program twice).
        by_prog: dict[str, dict] = {}
        for r in reports:
            by_prog[str(r.get("program"))] = r
        for name in sorted(by_prog):
            r = by_prog[name]
            parts = [f"  {name}:"]
            if r.get("dtypes"):
                parts.append(f"dtype={r['dtypes']}")
            if r.get("flops") is not None:
                parts.append(f"flops={r['flops']:.4g}")
            if r.get("bytes_accessed") is not None:
                parts.append(f"bytes={r['bytes_accessed']:.4g}")
            if r.get("hbm_peak_bytes") is not None:
                parts.append(f"hbm_peak={r['hbm_peak_bytes']:.4g}")
            if r.get("arithmetic_intensity") is not None:
                parts.append(
                    f"intensity={r['arithmetic_intensity']:.4g}"
                )
            if r.get("mfu") is not None:
                parts.append(f"MFU={r['mfu']:.4g}")
            if r.get("bound") and r["bound"] != "unknown":
                parts.append(f"{r['bound']}-bound")
            lines.append(" ".join(parts))
    else:
        lines.append(
            "  (no roofline.* events — DCT_ROOFLINE=0, or a pre-"
            "roofline run)"
        )
    captures = [
        r for r in ev
        if str(r.get("event", "")).startswith("profile.capture")
    ]
    if captures:
        starts = sum(
            1 for r in captures if r["event"] == "profile.capture_start"
        )
        ends = [
            r for r in captures if r["event"] == "profile.capture_end"
        ]
        line = (
            f"  flight recorder: {starts} capture(s), "
            f"{len(ends)} completed"
        )
        if ends:
            line += f"; last trace: {ends[-1].get('dir')}"
        lines.append(line)

    # -- lineage -------------------------------------------------------
    if lineage:
        from dct_tpu.observability import lineage as _lineage

        lines.append("")
        lines.append("Lineage:")
        graph = _lineage.build_graph(lineage)
        kinds: dict[str, int] = {}
        for recs in graph["nodes"].values():
            kind = recs[-1].get("kind", "?")
            kinds[kind] = kinds.get(kind, 0) + 1
        counted = "  ".join(
            f"{k}={kinds[k]}" for k in sorted(kinds)
        )
        lines.append(
            f"  {len(graph['nodes'])} node(s), "
            f"{len(graph['edges'])} edge(s): {counted}"
        )
        loads = [
            r for r in lineage
            if r.get("type") == "node" and r.get("kind") == "model_load"
        ]
        if loads:
            head = max(loads, key=lambda r: r.get("ts") or 0.0)
            lines.append(f"  serving now: {head['id']}")
            anc = _lineage.ancestors(graph, head["id"])
            order = (
                "deploy_package", "gate_verdict", "eval_report",
                "checkpoint", "dataset_snapshot", "etl_basis",
                "ingest_delta",
            )
            for kind in order:
                hits = [
                    nid for nid in anc
                    if graph["nodes"][nid][-1].get("kind") == kind
                ]
                for nid in sorted(hits):
                    lines.append(f"    <- {nid}")
        lines.append(
            "  (query: python -m dct_tpu.observability.lineage "
            "trace|explain-serving|audit)"
        )

    # -- incidents -----------------------------------------------------
    if incidents:
        lines.append("")
        lines.append("Incidents:")
        for b in incidents:
            parts = [
                f"  {b.get('name', '?')}:",
                f"kind={b.get('kind', '?')}",
                f"signal={b.get('signal', '?')}",
            ]
            if b.get("lineage_id"):
                parts.append(f"serving={b['lineage_id']}")
            files = b.get("files") or []
            if files:
                parts.append(f"files={len(files)}")
                if "profile" in files:
                    parts.append("+profile")
            lines.append(" ".join(parts))
        lines.append(
            "  (inspect: python -m dct_tpu.observability.incident "
            "list|show <bundle>)"
        )

    # -- spans / trace -------------------------------------------------
    lines.append("")
    lines.append("Spans by component:")
    if sp:
        by_comp: dict[str, int] = {}
        for s in sp:
            by_comp[s.get("component", "?")] = (
                by_comp.get(s.get("component", "?"), 0) + 1
            )
        for comp in sorted(by_comp):
            lines.append(f"  {comp:12s} {by_comp[comp]}")
    else:
        lines.append("  (none found)")
    lines.extend(_bench_mfu_lines(bench))
    if trace_path:
        lines.append("")
        lines.append(f"Perfetto trace written: {trace_path}")
        lines.append(
            "  open https://ui.perfetto.dev and drag the file in "
            "(or chrome://tracing > Load)"
        )
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m dct_tpu.observability.inspect",
        description=(
            "Join a run's events, spans, goodput and heartbeats into a "
            "cycle report; write the Perfetto trace export."
        ),
    )
    parser.add_argument("run_dir", help="directory holding the run's logs")
    parser.add_argument(
        "--run-id", default=None,
        help="pin a run-correlation ID (default: newest in the event log)",
    )
    parser.add_argument(
        "--out", default=None,
        help="trace output path (default: <run_dir>/trace.json)",
    )
    parser.add_argument(
        "--no-trace", action="store_true",
        help="report only; skip the trace.json export",
    )
    args = parser.parse_args(argv)
    if not os.path.isdir(args.run_dir):
        print(f"error: {args.run_dir} is not a directory", file=sys.stderr)
        return 2

    events = load_events(args.run_dir)
    heartbeats = load_heartbeats(args.run_dir)
    if not heartbeats:
        # Default layout: heartbeats live in a SIBLING of the events
        # dir (logs/events vs logs/heartbeats), so the documented
        # `inspect logs/events` invocation must still find them.
        sibling = os.path.join(
            os.path.dirname(os.path.normpath(args.run_dir)), "heartbeats"
        )
        if os.path.isdir(sibling):
            heartbeats = load_heartbeats(sibling)
    run_id = pick_run_id(events, args.run_id)
    trace_path = None
    if args.no_trace:
        from dct_tpu.observability.trace_export import read_spans

        spans = read_spans(args.run_dir, trace_id=run_id)
    else:
        trace_path, spans = export_run(
            args.run_dir, out_path=args.out, trace_id=run_id
        )
    from dct_tpu.observability import lineage as _lineage

    lineage_records = _lineage.read_ledger(
        os.path.join(args.run_dir, _lineage.LEDGER_NAME)
    )
    from dct_tpu.observability import incident as _incident

    incident_dir = _incident._cli_dir(None)
    if not os.path.isdir(incident_dir):
        # Default layout: bundles live in a SIBLING of the events dir
        # (logs/events vs logs/incidents), same rule as heartbeats.
        incident_dir = os.path.join(
            os.path.dirname(os.path.normpath(args.run_dir)), "incidents"
        )
    bundles = (
        _incident.list_bundles(incident_dir)
        if os.path.isdir(incident_dir) else []
    )
    print(build_report(
        events, heartbeats, spans, run_id, trace_path,
        bench=load_bench_record(args.run_dir),
        lineage=lineage_records,
        incidents=bundles,
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())

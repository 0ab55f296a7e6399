"""Roofline introspection: XLA cost-model accounting per compiled program.

The platform could see *wall-clock* (goodput ledger, spans, SLO burn)
but not *hardware efficiency*: MFU came from a hand-rolled analytic
estimator covering one model family, and nothing knew whether a program
was compute- or memory-bound. This module grounds efficiency accounting
in the compiler's own cost model instead:

- **Analytic cost** — every :class:`~dct_tpu.compilecache.CachedProgram`
  (the trainer's fused epoch programs, the serving tier's jitted scorer,
  each MPMD stage program) captures ``compiled.cost_analysis()`` FLOPs /
  bytes-accessed and ``compiled.memory_analysis()`` HBM numbers at
  compile time (:func:`analyze_compiled`; the store-disabled path uses
  the pre-compile :func:`analyze_lowered` — a trace, no compile).
- **Measured windows** — the goodput ledger already times every
  dispatch per program key (``GoodputLedger.dispatch_stats``).
- **The join** (:func:`program_report`): analytic FLOPs x call count /
  measured seconds = achieved FLOPs/s; over the chip peak that is
  **live per-program MFU**; FLOPs / bytes accessed is the arithmetic
  intensity, and against the machine's FLOPs/byte ridge point it
  classifies the program **compute-bound** vs **memory-bound** — the
  roofline placement, per program, from artifacts instead of guesses.

Published three ways: ``roofline.program`` events at capture time and a
run-end ``roofline.report`` per program, ``dct_program_*`` gauge
families on the metrics plane (flops, bytes accessed, HBM peak, MFU,
arithmetic intensity), and the run inspector's "Roofline" section.

Cost-model caveats (documented in docs/OBSERVABILITY.md §roofline): XLA
counts algebraic FLOPs of the *optimized* HLO — fusion can eliminate
work, convolutions/matmuls count multiply-adds as 2 — so MFU here is a
*model*-FLOPs utilization consistent with the literature's convention,
not a hardware counter. Bytes accessed is the cost model's estimate of
operand traffic, not a DRAM counter. Both are exact enough to rank
programs and catch regressions, which is what this plane is for.
"""

from __future__ import annotations

import os

#: Best-effort HBM bandwidth per chip, bytes/sec, by device-kind
#: substring (same table style as profiling.chip_peak_flops). Public
#: figures: v2 700, v3 900, v4 1228, v5e 819, v5p 2765, v6e 1640 GB/s.
_HBM_GBPS_TABLE = (
    ("v6", 1640.0), ("v5p", 2765.0), ("v5 lite", 819.0), ("v5e", 819.0),
    ("v4", 1228.0), ("v3", 900.0), ("v2", 700.0),
)


def roofline_enabled() -> bool:
    """Master switch (``DCT_ROOFLINE``, default on). The capture cost is
    one ``cost_analysis`` call on the already-compiled executable — or,
    on the store-disabled path, one extra jit *trace* per program."""
    v = os.environ.get("DCT_ROOFLINE", "1").strip().lower()
    return v not in ("0", "false", "no", "off")


def chip_hbm_bytes_per_sec() -> float | None:
    """Best-effort HBM bandwidth per chip from the device kind (None
    when unknown — e.g. the CPU rig). Override with ``DCT_HBM_GBPS``."""
    env = os.environ.get("DCT_HBM_GBPS")
    if env:
        try:
            return float(env) * 1e9
        except ValueError:
            return None
    try:
        import jax

        kind = jax.devices()[0].device_kind.lower()
    except Exception:  # noqa: BLE001 — no backend = no bandwidth table
        return None
    for pat, gbps in _HBM_GBPS_TABLE:
        if pat in kind:
            return gbps * 1e9
    return None


def _normalize_cost(raw, source: str) -> dict | None:
    """One ``cost_analysis()`` result (dict, or list of per-device
    dicts) -> the normalized record. None when nothing usable."""
    if isinstance(raw, (list, tuple)):
        raw = raw[0] if raw else None
    if not isinstance(raw, dict):
        return None
    out = {"source": source}
    flops = raw.get("flops")
    if isinstance(flops, (int, float)) and flops >= 0:
        out["flops"] = float(flops)
    ba = raw.get("bytes accessed")
    if isinstance(ba, (int, float)) and ba >= 0:
        out["bytes_accessed"] = float(ba)
    tr = raw.get("transcendentals")
    if isinstance(tr, (int, float)) and tr > 0:
        out["transcendentals"] = float(tr)
    return out if len(out) > 1 else None


#: dtype -> roofline short name (anything unlisted keeps its full name).
_DTYPE_SHORT = {
    "float64": "f64", "float32": "f32", "float16": "f16",
    "bfloat16": "bf16", "int64": "i64", "int32": "i32", "int16": "i16",
    "int8": "i8", "uint8": "u8", "uint16": "u16", "uint32": "u32",
    "bool": "b1",
}


def dtype_summary(args) -> str:
    """The program's parameter/activation dtypes as dispatched: sorted
    unique short names of the call's array leaves, comma-joined — the
    roofline record's ``dtypes`` stamp, so a bf16-vs-f32
    ``bytes_accessed`` delta is attributable on one scrape. When dtype
    rules are active (``DCT_DTYPE_RULES``) the dispatched args are
    still the f32 masters (the cast happens inside the traced body), so
    the active rules digest is appended (``+rules:<digest>``) to keep
    the stamp honest about the compute precision."""
    names: set = set()
    try:
        import jax

        for leaf in jax.tree_util.tree_leaves(args):
            dt = getattr(leaf, "dtype", None)
            if dt is not None:
                names.add(_DTYPE_SHORT.get(str(dt), str(dt)))
    except Exception:  # noqa: BLE001 — accounting never fails a program
        return ""
    summary = ",".join(sorted(names))
    try:
        from dct_tpu.parallel.sharding_rules import (
            dtype_rules, dtype_rules_digest,
        )

        if dtype_rules():
            summary += f"+rules:{dtype_rules_digest()}"
    except Exception:  # noqa: BLE001 — a malformed env must not bite here
        pass
    return summary


def analyze_lowered(lowered) -> dict | None:
    """Cost analysis of a ``jax.stages.Lowered`` (pre-compile HLO): the
    capture path for programs the AOT store never compiles explicitly
    (store disabled — the default). No ``memory_analysis`` exists before
    compilation, so HBM fields are absent here. Never raises."""
    try:
        return _normalize_cost(lowered.cost_analysis(), "lowered")
    except Exception:  # noqa: BLE001 — accounting never fails a program
        return None


def analyze_compiled(compiled) -> dict | None:
    """Cost + memory analysis of a ``jax.stages.Compiled`` (or a
    deserialized AOT executable). Adds the HBM accounting: argument /
    output / temp / generated-code bytes and their peak-resident sum
    (aliased donation bytes subtracted — a donated input is not resident
    twice). Never raises; partial results are kept."""
    try:
        out = _normalize_cost(compiled.cost_analysis(), "compiled")
    except Exception:  # noqa: BLE001
        out = None
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        ma = None
    if ma is not None:
        mem = {}
        for field, key in (
            ("argument_size_in_bytes", "argument_bytes"),
            ("output_size_in_bytes", "output_bytes"),
            ("temp_size_in_bytes", "temp_bytes"),
            ("alias_size_in_bytes", "alias_bytes"),
            ("generated_code_size_in_bytes", "generated_code_bytes"),
        ):
            v = getattr(ma, field, None)
            if isinstance(v, int) and v >= 0:
                mem[key] = v
        if mem:
            peak = (
                mem.get("argument_bytes", 0)
                + mem.get("output_bytes", 0)
                + mem.get("temp_bytes", 0)
                - mem.get("alias_bytes", 0)
            )
            mem["hbm_peak_bytes"] = max(0, peak)
            out = {**(out or {"source": "compiled"}), **mem}
    return out


def resolve_peak_flops() -> tuple[float | None, str]:
    """(peak FLOPs/sec per chip, source): the ``DCT_PEAK_TFLOPS``
    override or the device table. Off the TPU there is no denominator
    — ``(None, "not_measured")`` — and no MFU is reported; nothing is
    substituted for it."""
    from dct_tpu.utils.profiling import chip_peak_flops

    peak = chip_peak_flops()
    if not peak:
        return None, "not_measured"
    return peak, (
        "DCT_PEAK_TFLOPS" if os.environ.get("DCT_PEAK_TFLOPS")
        else "device_table"
    )


# ----------------------------------------------------------------------
# The join: analytic cost x measured dispatch windows.


def classify(intensity: float | None, ridge: float | None) -> str:
    """Roofline placement: arithmetic intensity (FLOPs/byte) against the
    machine's ridge point (peak FLOPs/s over HBM bytes/s). Below the
    ridge the program cannot reach peak no matter how good the kernels
    are — it is bandwidth-bound."""
    if intensity is None or ridge is None:
        return "unknown"
    return "compute" if intensity >= ridge else "memory"


def program_report(
    costs: dict,
    dispatch_stats: dict | None = None,
    *,
    n_chips: int = 1,
    peak_flops: float | None = None,
    hbm_bytes_per_s: float | None = None,
    family: str = "",
    config_hash: str = "",
    mesh: str = "",
) -> list[dict]:
    """Join per-program analytic costs (``ExecutableStore.costs``) with
    the ledger's measured non-compile dispatch windows
    (``GoodputLedger.dispatch_stats``: key -> [count, seconds]) into one
    record per program: analytic FLOPs/bytes/HBM, call count + measured
    seconds, achieved FLOPs/s, **MFU**, arithmetic intensity, and the
    compute/memory-bound classification. Programs with no measured
    window (a scorer analyzed but never steadily dispatched) still get
    their analytic record — ``mfu`` stays absent, never wrong."""
    if peak_flops is None:
        from dct_tpu.utils.profiling import chip_peak_flops

        peak_flops = chip_peak_flops()
    if hbm_bytes_per_s is None:
        hbm_bytes_per_s = chip_hbm_bytes_per_sec()
    ridge = (
        peak_flops / hbm_bytes_per_s
        if peak_flops and hbm_bytes_per_s else None
    )
    out = []
    for program in sorted(costs):
        cost = costs[program]
        if not cost:
            continue
        rec = {
            "program": program,
            "family": family,
            "config_hash": config_hash,
            "mesh": mesh,
            **cost,
        }
        flops = cost.get("flops")
        ba = cost.get("bytes_accessed")
        intensity = (flops / ba) if flops and ba else None
        if intensity is not None:
            rec["arithmetic_intensity"] = round(intensity, 3)
        rec["bound"] = classify(intensity, ridge)
        stats = (dispatch_stats or {}).get(program)
        if stats:
            count, seconds = int(stats[0]), float(stats[1])
            rec["calls"] = count
            rec["seconds"] = round(seconds, 6)
            if flops and seconds > 0:
                achieved = flops * count / seconds
                rec["achieved_flops_per_s"] = round(achieved, 3)
                if peak_flops:
                    rec["mfu"] = round(
                        achieved / max(n_chips, 1) / peak_flops, 6
                    )
            if ba and seconds > 0 and hbm_bytes_per_s:
                rec["hbm_util"] = round(
                    ba * count / seconds
                    / max(n_chips, 1) / hbm_bytes_per_s, 6,
                )
        out.append(rec)
    return out


# ----------------------------------------------------------------------
# Metrics-plane families.


def add_roofline_metrics(reg, report: list[dict], labels: dict) -> None:
    """Stamp a :func:`program_report` into ``dct_program_*`` gauge
    families on ``reg`` (a MetricsRegistry). ``labels`` is the caller's
    base label set (run_id etc.); each series adds its program identity
    labels, and the MFU/intensity gauges carry the roofline ``bound``."""
    flops_g = reg.gauge(
        "dct_program_flops",
        "XLA cost-model FLOPs per dispatch of this compiled program.",
        agg="last",
    )
    bytes_g = reg.gauge(
        "dct_program_bytes_accessed",
        "XLA cost-model bytes accessed per dispatch.", agg="last",
    )
    hbm_g = reg.gauge(
        "dct_program_hbm_peak_bytes",
        "Peak resident HBM of the compiled program "
        "(arguments + outputs + temps - aliased).", agg="last",
    )
    mfu_g = reg.gauge(
        "dct_program_mfu",
        "Live model-FLOPs utilization: cost-model FLOPs x calls over "
        "measured dispatch seconds, per chip, over peak.", agg="last",
    )
    int_g = reg.gauge(
        "dct_program_arithmetic_intensity",
        "Cost-model FLOPs per byte accessed (roofline x-axis).",
        agg="last",
    )
    for rec in report:
        wl = {
            **labels,
            "program": rec.get("program", "?"),
            "family": rec.get("family", ""),
            "mesh": rec.get("mesh", ""),
        }
        # Precision attribution: one scrape separates the bf16
        # program's bytes from its f32 twin's. Unstamped records keep
        # the pre-dtype label set so their series identity is stable.
        if rec.get("dtypes"):
            wl["dtype"] = rec["dtypes"]
        if rec.get("flops") is not None:
            flops_g.set(rec["flops"], wl)
        if rec.get("bytes_accessed") is not None:
            bytes_g.set(rec["bytes_accessed"], wl)
        if rec.get("hbm_peak_bytes") is not None:
            hbm_g.set(rec["hbm_peak_bytes"], wl)
        bwl = {**wl, "bound": rec.get("bound", "unknown")}
        if rec.get("mfu") is not None:
            mfu_g.set(rec["mfu"], bwl)
        if rec.get("arithmetic_intensity") is not None:
            int_g.set(rec["arithmetic_intensity"], bwl)

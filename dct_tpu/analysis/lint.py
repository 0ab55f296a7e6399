"""dct-lint CLI: ``python -m dct_tpu.analysis.lint [paths...]``.

Exit codes (CI contract):

- ``0`` — no findings (baselined debt and stale-baseline notes do not
  fail the build; stale entries are printed so they get pruned).
- ``1`` — at least one finding (including baseline-hygiene: an entry
  with no written justification).
- ``2`` — usage or internal error (unknown rule id, unreadable
  baseline, ...).

Examples::

    python -m dct_tpu.analysis.lint dct_tpu/
    python -m dct_tpu.analysis.lint dct_tpu jobs dags scripts chip_smoke.py
    python -m dct_tpu.analysis.lint --format json dct_tpu/ | jq .
    python -m dct_tpu.analysis.lint --select env-registry,event-names
    python -m dct_tpu.analysis.lint --write-baseline   # grandfather, then justify
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from dct_tpu.analysis import core


def _parse_ids(raw: str | None, known: set[str]) -> set[str] | None:
    if raw is None:
        return None
    ids = {s.strip() for s in raw.split(",") if s.strip()}
    unknown = ids - known
    if unknown:
        raise SystemExit(
            f"dct-lint: unknown rule id(s): {', '.join(sorted(unknown))}; "
            f"known: {', '.join(sorted(known))}"
        )
    return ids


def _render_text(report: core.Report, *, baseline_path: str | None) -> str:
    lines: list[str] = []
    for f in report.findings:
        loc = f"{f.path}:{f.line}" if f.line else f.path
        lines.append(f"{loc}: [{f.rule}] {f.message}")
        if f.snippet:
            lines.append(f"    {f.snippet}")
    if report.baselined:
        lines.append(
            f"-- {len(report.baselined)} finding(s) suppressed by the "
            f"baseline ({baseline_path})"
        )
    for e in report.stale_baseline:
        lines.append(
            f"-- stale baseline entry {e.fingerprint} ({e.rule} @ {e.path}):"
            " no longer matches any finding — prune it"
        )
    n = len(report.findings)
    lines.append(
        f"dct-lint: {report.checked_files} file(s), "
        f"{len(report.active_rules)} rule(s), "
        + ("clean" if n == 0 else f"{n} finding(s)")
    )
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m dct_tpu.analysis.lint",
        description=(
            "Project-native static analysis: SPMD and continuous-"
            "training invariants (docs/ANALYSIS.md)."
        ),
    )
    parser.add_argument(
        "paths",
        nargs="*",
        help="files/dirs to lint (default: the dct_tpu package)",
    )
    parser.add_argument(
        "--root",
        default=None,
        help="repo root for cross-file rules (default: auto-detected "
        "as the directory containing the dct_tpu package)",
    )
    parser.add_argument(
        "--format", choices=("text", "json"), default="text"
    )
    parser.add_argument(
        "--baseline",
        default=None,
        help="baseline file (default: <root>/.dct-lint-baseline.json "
        "when it exists)",
    )
    parser.add_argument(
        "--no-baseline",
        action="store_true",
        help="ignore any baseline file (show the full finding set)",
    )
    parser.add_argument(
        "--write-baseline",
        action="store_true",
        help="write all current findings into the baseline file with "
        "TODO justifications (each MUST then be justified by hand — "
        "an unjustified entry is itself a finding), and exit 0",
    )
    parser.add_argument(
        "--select", default=None, help="comma-separated rule ids to run"
    )
    parser.add_argument(
        "--ignore", default=None, help="comma-separated rule ids to skip"
    )
    parser.add_argument(
        "--list-rules", action="store_true", help="print the rule catalog"
    )
    args = parser.parse_args(argv)

    rules = core.all_rules()
    if args.list_rules:
        for rid, rule in sorted(rules.items()):
            print(f"{rid}: {rule.name}")
            print(f"    {rule.doc}")
        return 0

    root = os.path.abspath(args.root or core.default_root())
    paths = args.paths or [os.path.join(root, "dct_tpu")]
    try:
        select = _parse_ids(args.select, set(rules))
        ignore = _parse_ids(args.ignore, set(rules))
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2

    baseline_path = args.baseline or os.path.join(
        root, ".dct-lint-baseline.json"
    )
    baseline: core.Baseline | None = None
    if not args.no_baseline and not args.write_baseline and os.path.exists(
        baseline_path
    ):
        try:
            baseline = core.Baseline.load(baseline_path)
        except (OSError, ValueError) as e:
            print(f"dct-lint: unreadable baseline {baseline_path}: {e}",
                  file=sys.stderr)
            return 2

    try:
        report = core.analyze(
            paths, root=root, select=select, ignore=ignore, baseline=baseline
        )
    except OSError as e:
        print(f"dct-lint: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        previous = None
        if os.path.exists(baseline_path):
            try:
                previous = core.Baseline.load(baseline_path)
            except (OSError, ValueError):
                previous = None  # unreadable: regenerate from scratch
        core.Baseline.from_findings(
            report.findings, previous=previous
        ).save(baseline_path)
        print(
            f"dct-lint: wrote {len(report.findings)} entr"
            f"{'y' if len(report.findings) == 1 else 'ies'} to "
            f"{baseline_path} — now REPLACE every TODO justification "
            "with the real reason (an unjustified entry fails the lint)"
        )
        return 0

    if args.format == "json":
        print(json.dumps(report.to_dict(), indent=2))
    else:
        print(_render_text(report, baseline_path=baseline_path))
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())

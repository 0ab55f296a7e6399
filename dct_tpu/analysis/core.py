"""The dct-lint framework: findings, rules, suppressions, baseline.

Design constraints that shaped this module:

- **stdlib-only.** The analyzer must run in a bare CI container (no
  jax), and must keep working when the code under analysis cannot even
  import — a syntax error becomes a ``parse`` finding, never a crash.
- **Line-drift-stable baselines.** A baseline entry fingerprints the
  *content* of the flagged line (rule + file + stripped source +
  occurrence ordinal), not its line number, so unrelated edits above a
  grandfathered finding do not invalidate the baseline.
- **Reviewable suppressions.** ``# dct: noqa[rule-id]`` on the flagged
  line suppresses named rules there; the same comment on a ``def`` /
  ``class`` line suppresses them for that whole body (the idiom for
  "this function is per-process by design"). A bare ``# dct: noqa``
  suppresses every rule on its line. Suppressions are expected to carry
  a justification in the trailing comment text; the baseline *requires*
  one (:class:`Baseline` treats empty/TODO justifications as findings).
"""

from __future__ import annotations

import ast
import hashlib
import io
import json
import os
import re
import tokenize
from dataclasses import dataclass, field

#: ``# dct: noqa`` / ``# dct: noqa[rule-a,rule-b] — why`` (trailing
#: prose after the bracket is the human justification, not parsed).
NOQA_RE = re.compile(r"#\s*dct:\s*noqa(?:\[([a-z0-9_\-, ]+)\])?", re.I)

#: Region markers consumed by the span-sync rule (and available to any
#: future region-scoped rule): ``# dct: begin-no-host-sync`` ...
#: ``# dct: end-no-host-sync``.
REGION_BEGIN_RE = re.compile(r"#\s*dct:\s*begin-no-host-sync")
REGION_END_RE = re.compile(r"#\s*dct:\s*end-no-host-sync")

_DEF_LINE_RE = re.compile(r"^\s*(?:async\s+def|def|class)\b")


@dataclass
class Finding:
    """One rule violation, anchored to a source line."""

    rule: str
    path: str  # repo-relative, '/'-separated
    line: int
    message: str
    snippet: str = ""
    fingerprint: str = ""

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "snippet": self.snippet,
            "fingerprint": self.fingerprint,
        }


class Rule:
    """Base class: subclasses set ``id``/``name``/``doc`` and implement
    :meth:`check`. Register with the :func:`register` decorator."""

    id: str = ""
    name: str = ""
    doc: str = ""

    def check(self, project: "Project") -> list[Finding]:  # pragma: no cover
        raise NotImplementedError


_REGISTRY: dict[str, Rule] = {}


def register(cls: type[Rule]) -> type[Rule]:
    inst = cls()
    if not inst.id:
        raise ValueError(f"rule {cls.__name__} has no id")
    if inst.id in _REGISTRY:
        raise ValueError(f"duplicate rule id {inst.id!r}")
    _REGISTRY[inst.id] = inst
    return cls


def all_rules() -> dict[str, Rule]:
    """id -> rule instance, loading the built-in rule modules on first
    use (imports under a function so ``core`` alone stays cycle-free)."""
    import dct_tpu.analysis.rules  # noqa: F401 — registers on import

    return dict(_REGISTRY)


# ----------------------------------------------------------------------
# Source files


class FileContext:
    """One parsed source file plus the lazy indexes rules share."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath
        self.source = source
        self.lines = source.splitlines()
        self.tree: ast.AST | None = None
        self.parse_error: str | None = None
        try:
            self.tree = ast.parse(source)
        except (SyntaxError, ValueError) as e:
            self.parse_error = f"{type(e).__name__}: {e}"
        self._parents: dict[ast.AST, ast.AST] | None = None
        self._suppress: dict[int, set[str]] | None = None
        self._comments: dict[int, str] | None = None

    # -- navigation ----------------------------------------------------
    def parents(self) -> dict[ast.AST, ast.AST]:
        if self._parents is None:
            self._parents = {}
            if self.tree is not None:
                for node in ast.walk(self.tree):
                    for child in ast.iter_child_nodes(node):
                        self._parents[child] = node
        return self._parents

    def ancestors(self, node: ast.AST):
        parents = self.parents()
        cur = parents.get(node)
        while cur is not None:
            yield cur
            cur = parents.get(cur)

    def line(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    # -- comments --------------------------------------------------------
    def comments(self) -> dict[int, str]:
        """line -> actual comment text on that line. Tokenizer-accurate
        for Python (a ``# dct:`` marker quoted inside a string literal
        or docstring is NOT a comment and must not arm a region or a
        suppression); plain ``#``-to-EOL scan for non-Python files
        (.env.example) where string literals don't exist."""
        if self._comments is not None:
            return self._comments
        out: dict[int, str] = {}
        if self.tree is not None:
            try:
                for tok in tokenize.generate_tokens(
                    io.StringIO(self.source).readline
                ):
                    if tok.type == tokenize.COMMENT:
                        out.setdefault(tok.start[0], tok.string)
            except (tokenize.TokenError, IndentationError, SyntaxError):
                out = self._comments_by_scan()
        else:
            out = self._comments_by_scan()
        self._comments = out
        return out

    def _comments_by_scan(self) -> dict[int, str]:
        out: dict[int, str] = {}
        for i, text in enumerate(self.lines, start=1):
            pos = text.find("#")
            if pos >= 0:
                out[i] = text[pos:]
        return out

    # -- suppressions ----------------------------------------------------
    def _def_keyword_line(self, node) -> int:
        """The line holding the ``def``/``class`` keyword (decorated
        nodes report the first decorator as ``lineno``)."""
        end = node.body[0].lineno if node.body else (node.end_lineno or node.lineno)
        for ln in range(node.lineno, end + 1):
            if _DEF_LINE_RE.match(self.line(ln)):
                return ln
        return node.lineno

    def suppressions(self) -> dict[int, set[str]]:
        """line -> suppressed rule ids ('*' = all). Block suppressions
        (noqa on a def/class line) are expanded to every body line."""
        if self._suppress is not None:
            return self._suppress
        out: dict[int, set[str]] = {}
        for i, text in sorted(self.comments().items()):
            m = NOQA_RE.search(text)
            if not m:
                continue
            ids = (
                {s.strip() for s in m.group(1).split(",") if s.strip()}
                if m.group(1)
                else {"*"}
            )
            out.setdefault(i, set()).update(ids)
        if self.tree is not None and out:
            for node in ast.walk(self.tree):
                if not isinstance(
                    node,
                    (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef),
                ):
                    continue
                def_line = self._def_keyword_line(node)
                ids = out.get(def_line)
                if not ids:
                    continue
                for ln in range(node.lineno, (node.end_lineno or node.lineno) + 1):
                    out.setdefault(ln, set()).update(ids)
        self._suppress = out
        return out

    def suppressed(self, rule_id: str, lineno: int) -> bool:
        ids = self.suppressions().get(lineno)
        return bool(ids) and ("*" in ids or rule_id in ids)

    # -- regions ---------------------------------------------------------
    def regions(self) -> list[tuple[int, int]]:
        """``begin-no-host-sync`` .. ``end-no-host-sync`` line ranges
        (exclusive of the marker lines). Fail-safe in both directions:
        an unclosed begin extends to EOF, and a duplicate begin before
        the end is ignored (the earlier, wider window wins) — better to
        over-check than silently shrink the protected region."""
        out: list[tuple[int, int]] = []
        start: int | None = None
        for i, text in sorted(self.comments().items()):
            if REGION_BEGIN_RE.search(text):
                if start is None:
                    start = i
            elif REGION_END_RE.search(text) and start is not None:
                out.append((start + 1, i - 1))
                start = None
        if start is not None:
            out.append((start + 1, len(self.lines)))
        return out

    def finding(self, rule_id: str, node_or_line, message: str) -> Finding:
        lineno = (
            node_or_line
            if isinstance(node_or_line, int)
            else getattr(node_or_line, "lineno", 1)
        )
        return Finding(
            rule=rule_id,
            path=self.relpath,
            line=lineno,
            message=message,
            snippet=self.line(lineno).strip(),
        )


# ----------------------------------------------------------------------
# Project


def default_root() -> str:
    """The repo root: the directory holding the ``dct_tpu`` package."""
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.dirname(pkg_dir)


#: Where first-party Python lives relative to the repo root — the scan
#: surface for repo-wide rules (env registry), independent of which
#: paths the CLI was pointed at. Tests are deliberately absent: a test
#: monkeypatching ``DCT_FOO`` does not make ``DCT_FOO`` part of the
#: platform's env contract.
REPO_CODE_DIRS = ("dct_tpu", "jobs", "dags", "scripts")
REPO_CODE_FILES = ("chip_smoke.py",)


class Project:
    """The analysis unit: target files plus root-relative access to the
    registry/docs files cross-file rules consult."""

    def __init__(self, root: str, contexts: list[FileContext]):
        self.root = os.path.abspath(root)
        self.contexts = contexts
        self._aux: dict[str, FileContext | None] = {}

    def read(self, relpath: str) -> str | None:
        """Raw text of a root-relative file, None if absent/unreadable."""
        try:
            with open(os.path.join(self.root, relpath), encoding="utf-8") as f:
                return f.read()
        except OSError:
            return None

    def parse_aux(self, relpath: str) -> FileContext | None:
        """Parse a root-relative file on demand (cached); reuses a
        target context when the file is already in the lint batch."""
        relpath = relpath.replace(os.sep, "/")
        if relpath in self._aux:
            return self._aux[relpath]
        ctx = next(
            (c for c in self.contexts if c.relpath == relpath), None
        )
        if ctx is None:
            src = self.read(relpath)
            if src is not None:
                ctx = FileContext(
                    os.path.join(self.root, relpath), relpath, src
                )
        self._aux[relpath] = ctx
        return ctx

    def repo_python_files(self) -> list[str]:
        """Root-relative paths of all first-party Python (the repo-wide
        scan surface — see :data:`REPO_CODE_DIRS`)."""
        out: list[str] = []
        for d in REPO_CODE_DIRS:
            base = os.path.join(self.root, d)
            for dirpath, dirnames, filenames in os.walk(base):
                dirnames[:] = [
                    n for n in dirnames
                    if n != "__pycache__" and not n.startswith(".")
                ]
                for name in sorted(filenames):
                    if name.endswith(".py"):
                        rel = os.path.relpath(
                            os.path.join(dirpath, name), self.root
                        )
                        out.append(rel.replace(os.sep, "/"))
        for f in REPO_CODE_FILES:
            if os.path.exists(os.path.join(self.root, f)):
                out.append(f)
        return sorted(out)


def collect_files(paths: list[str], root: str) -> list[FileContext]:
    """Expand CLI path arguments into parsed :class:`FileContext`\\ s."""
    seen: set[str] = set()
    contexts: list[FileContext] = []

    def add(path: str) -> None:
        apath = os.path.abspath(path)
        if apath in seen:
            return
        seen.add(apath)
        rel = os.path.relpath(apath, root).replace(os.sep, "/")
        try:
            with open(apath, encoding="utf-8") as f:
                src = f.read()
        except OSError as e:
            ctx = FileContext(apath, rel, "")
            ctx.parse_error = f"unreadable: {e}"
            contexts.append(ctx)
            return
        contexts.append(FileContext(apath, rel, src))

    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [
                    n for n in dirnames
                    if n != "__pycache__" and not n.startswith(".")
                ]
                for name in sorted(filenames):
                    if name.endswith(".py"):
                        add(os.path.join(dirpath, name))
        else:
            add(p)
    contexts.sort(key=lambda c: c.relpath)
    return contexts


# ----------------------------------------------------------------------
# Baseline


def _fingerprint(rule: str, path: str, snippet: str, ordinal: int) -> str:
    h = hashlib.sha1(
        f"{rule}::{path}::{snippet}::{ordinal}".encode()
    )
    return h.hexdigest()[:16]


def assign_fingerprints(findings: list[Finding]) -> None:
    """Stamp content-based fingerprints; identical lines in one file
    disambiguate by line-ordered ordinal."""
    counters: dict[tuple[str, str, str], int] = {}
    for f in sorted(findings, key=lambda f: (f.path, f.line)):
        key = (f.rule, f.path, f.snippet)
        n = counters.get(key, 0)
        counters[key] = n + 1
        f.fingerprint = _fingerprint(f.rule, f.path, f.snippet, n)


@dataclass
class BaselineEntry:
    fingerprint: str
    rule: str
    path: str
    snippet: str
    justification: str = ""

    def to_dict(self) -> dict:
        return {
            "fingerprint": self.fingerprint,
            "rule": self.rule,
            "path": self.path,
            "snippet": self.snippet,
            "justification": self.justification,
        }


class Baseline:
    """The reviewed debt ledger: findings listed here (by content
    fingerprint) do not fail the lint, but every entry must carry a
    real justification — an empty or TODO one is itself a finding."""

    def __init__(self, entries: list[BaselineEntry], path: str | None = None):
        self.entries = entries
        self.path = path

    @classmethod
    def load(cls, path: str) -> "Baseline":
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
        entries = [
            BaselineEntry(
                fingerprint=e.get("fingerprint", ""),
                rule=e.get("rule", ""),
                path=e.get("path", ""),
                snippet=e.get("snippet", ""),
                justification=e.get("justification", ""),
            )
            for e in raw.get("entries", [])
        ]
        return cls(entries, path=path)

    @classmethod
    def from_findings(
        cls,
        findings: list[Finding],
        path: str | None = None,
        previous: "Baseline | None" = None,
    ) -> "Baseline":
        """Build a baseline for ``findings``; entries whose fingerprint
        already exists in ``previous`` KEEP their hand-written
        justification (regenerating the baseline must never destroy the
        review record — only genuinely new findings get the TODO)."""
        keep = (
            {e.fingerprint: e.justification for e in previous.entries}
            if previous is not None
            else {}
        )
        return cls(
            [
                BaselineEntry(
                    fingerprint=f.fingerprint,
                    rule=f.rule,
                    path=f.path,
                    snippet=f.snippet,
                    justification=keep.get(
                        f.fingerprint,
                        "TODO: justify this grandfathered finding",
                    ),
                )
                for f in findings
            ],
            path=path,
        )

    def save(self, path: str) -> None:
        payload = {
            "comment": (
                "dct-lint baseline: reviewed, justified debt. Every entry "
                "MUST carry a non-TODO justification (docs/ANALYSIS.md)."
            ),
            "entries": [e.to_dict() for e in self.entries],
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=2, sort_keys=False)
            f.write("\n")
        os.replace(tmp, path)

    def hygiene_findings(self) -> list[Finding]:
        out = []
        for e in self.entries:
            just = e.justification.strip()
            if not just or just.upper().startswith("TODO"):
                out.append(
                    Finding(
                        rule="baseline-hygiene",
                        path=e.path or (self.path or ""),
                        line=0,
                        message=(
                            f"baseline entry {e.fingerprint} ({e.rule}) "
                            "has no written justification — the baseline "
                            "is a reviewed ledger, not a mute button"
                        ),
                        snippet=e.snippet,
                        fingerprint=e.fingerprint,
                    )
                )
        return out


# ----------------------------------------------------------------------
# Analysis driver


@dataclass
class Report:
    findings: list[Finding] = field(default_factory=list)
    baselined: list[Finding] = field(default_factory=list)
    stale_baseline: list[BaselineEntry] = field(default_factory=list)
    checked_files: int = 0
    active_rules: list[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "checked_files": self.checked_files,
            "active_rules": self.active_rules,
            "findings": [f.to_dict() for f in self.findings],
            "baselined": [f.to_dict() for f in self.baselined],
            "stale_baseline": [e.to_dict() for e in self.stale_baseline],
        }


def analyze(
    paths: list[str],
    *,
    root: str | None = None,
    select: set[str] | None = None,
    ignore: set[str] | None = None,
    baseline: Baseline | None = None,
) -> Report:
    """Run the registered rules over ``paths``; returns a :class:`Report`
    whose ``findings`` are post-noqa, post-baseline violations."""
    root = os.path.abspath(root or default_root())
    contexts = collect_files(paths, root)
    project = Project(root, contexts)

    rules = all_rules()
    active = [
        r
        for rid, r in sorted(rules.items())
        if (select is None or rid in select)
        and (ignore is None or rid not in ignore)
    ]

    raw: list[Finding] = []
    for ctx in contexts:
        if ctx.parse_error is not None:
            raw.append(
                Finding(
                    rule="parse",
                    path=ctx.relpath,
                    line=1,
                    message=f"cannot analyze: {ctx.parse_error}",
                )
            )
    for rule in active:
        for f in rule.check(project):
            # Resolve the finding's file for suppression even when it
            # is not a lint target (repo-wide rules anchor findings in
            # chip_smoke.py/.env.example/config.py regardless of CLI paths;
            # a noqa there must bind under every invocation).
            ctx = project.parse_aux(f.path)
            if ctx is not None and ctx.suppressed(f.rule, f.line):
                continue
            raw.append(f)

    assign_fingerprints(raw)
    raw.sort(key=lambda f: (f.path, f.line, f.rule))

    report = Report(
        checked_files=len(contexts),
        active_rules=[r.id for r in active],
    )
    if baseline is None:
        report.findings = raw
        return report

    by_fp = {e.fingerprint: e for e in baseline.entries}
    matched_fps: set[str] = set()
    for f in raw:
        entry = by_fp.get(f.fingerprint)
        if entry is not None:
            matched_fps.add(entry.fingerprint)
            report.baselined.append(f)
        else:
            report.findings.append(f)
    report.stale_baseline = [
        e for e in baseline.entries if e.fingerprint not in matched_fps
    ]
    report.findings.extend(baseline.hygiene_findings())
    return report

"""Registry-consistency rules: the DCT_* env contract and event names.

``env-registry`` — the platform's ~160-knob ``DCT_*`` environment
surface drifts three ways: code reads a key nobody documented, the
documented ``.env.example`` names a key nobody reads, or the declared
registry carries a dead entry. The single source of truth is
``ENV_REGISTRY`` in ``dct_tpu/config.py``; this rule holds all three
surfaces equal. The scan is repo-wide (``dct_tpu``/``jobs``/``dags``/
``scripts``/``chip_smoke.py``, tests excluded) regardless of which
paths the CLI was pointed at, so a partial lint cannot mistake a knob
only a root-level program reads for a dead one.

``event-names`` — ``EventLog.emit(component, event, ...)`` sites must
use (component, event) pairs documented in ``docs/OBSERVABILITY.md``'s
event table: the event log is an operator API, and an undocumented
name is a record no dashboard/inspector query will ever find.
Statically-unknowable names (f-strings, variables) are skipped — the
rule checks what it can prove, and the docs table remains the review
checklist for the rest.
"""

from __future__ import annotations

import ast
import re

from dct_tpu.analysis.core import Finding, Project, Rule, register
from dct_tpu.analysis.rules._helpers import (
    func_repr,
    iter_calls,
    string_candidates,
    unparse,
)

_ENV_TOKEN_RE = re.compile(r"DCT_[A-Z0-9_]+")


def _env_mentions(text: str) -> dict[str, int]:
    """DCT_* names mentioned in free text -> first line number.
    Wildcard mentions (``DCT_SERVE_*``, trailing underscore) are not
    names and are skipped."""
    out: dict[str, int] = {}
    for i, line in enumerate(text.splitlines(), start=1):
        for m in _ENV_TOKEN_RE.finditer(line):
            token = m.group(0)
            follow = line[m.end() : m.end() + 1]
            if token.endswith("_") or follow == "*":
                continue
            out.setdefault(token, i)
    return out


def _is_env_receiver(recv_src: str) -> bool:
    return (
        "environ" in recv_src
        or recv_src in ("env", "os")
        or recv_src.endswith(".env")
        or recv_src.endswith("_env")
    )


def collect_env_uses(ctx) -> dict[str, int]:
    """DCT_* keys this file provably touches -> first line number.

    Catches: ``_env("DCT_X", ...)``-style helper calls (any callee whose
    name mentions ``env``), ``os.environ``/``env`` ``.get/.pop/
    .setdefault``/``os.getenv`` with a literal key, subscript reads and
    writes on env-like receivers, ``NAME = "DCT_X"`` named-key
    constants, and ``DCT_X=...`` keyword arguments (the launchers build
    child envs that way). Dynamic keys are invisible — by design: the
    registry governs the *named* contract.
    """
    uses: dict[str, int] = {}
    if ctx.tree is None:
        return uses

    def note(value, lineno: int) -> None:
        if isinstance(value, str) and _ENV_TOKEN_RE.fullmatch(value):
            uses.setdefault(value, lineno)

    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            fname = func_repr(node)
            tail = fname.rsplit(".", 1)[-1]
            if ("env" in tail.lower() or tail == "getenv") and node.args:
                a = node.args[0]
                if isinstance(a, ast.Constant):
                    note(a.value, node.lineno)
            if isinstance(node.func, ast.Attribute) and node.func.attr in (
                "get",
                "pop",
                "setdefault",
            ):
                if _is_env_receiver(unparse(node.func.value)):
                    for a in node.args[:1]:
                        if isinstance(a, ast.Constant):
                            note(a.value, node.lineno)
            for kw in node.keywords:
                if kw.arg and _ENV_TOKEN_RE.fullmatch(kw.arg):
                    uses.setdefault(kw.arg, node.lineno)
        elif isinstance(node, ast.Subscript):
            if _is_env_receiver(unparse(node.value)) and isinstance(
                node.slice, ast.Constant
            ):
                note(node.slice.value, node.lineno)
        elif isinstance(node, ast.Assign):
            if (
                len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
            ):
                note(node.value.value, node.lineno)
    return uses


def parse_env_registry(ctx) -> dict[str, int] | None:
    """``ENV_REGISTRY`` keys -> declaration line from config.py's AST
    (statically — the analyzer never imports the code it checks).
    None when the dict is absent."""
    if ctx is None or ctx.tree is None:
        return None
    for node in ast.walk(ctx.tree):
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == "ENV_REGISTRY"
            and isinstance(node.value, ast.Dict)
        ) or (
            isinstance(node, ast.AnnAssign)
            and isinstance(node.target, ast.Name)
            and node.target.id == "ENV_REGISTRY"
            and isinstance(node.value, ast.Dict)
        ):
            value = node.value
            out: dict[str, int] = {}
            for k in value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    out.setdefault(k.value, k.lineno)
            return out
    return None


_CONFIG_RELPATH = "dct_tpu/config.py"
_ENV_EXAMPLE_RELPATH = ".env.example"


@register
class EnvRegistryRule(Rule):
    id = "env-registry"
    name = "DCT_* env keys: declared ⇄ documented ⇄ used"
    doc = (
        "Every DCT_* key read anywhere in first-party code must be "
        "declared in dct_tpu/config.py's ENV_REGISTRY and mentioned in "
        ".env.example; every declared key must be mentioned there and "
        "actually used; every key .env.example names must be declared. "
        "One registry, zero drift."
    )

    def check(self, project: Project) -> list[Finding]:
        out: list[Finding] = []
        cfg_ctx = project.parse_aux(_CONFIG_RELPATH)
        declared = parse_env_registry(cfg_ctx)
        if declared is None:
            anchor = cfg_ctx if cfg_ctx is not None else None
            out.append(
                Finding(
                    rule=self.id,
                    path=_CONFIG_RELPATH,
                    line=1,
                    message=(
                        "ENV_REGISTRY dict not found in dct_tpu/config.py "
                        "— the DCT_* env contract has no registry of "
                        "record to check against"
                    ),
                    snippet=anchor.line(1).strip() if anchor else "",
                )
            )
            return out

        env_example = project.read(_ENV_EXAMPLE_RELPATH)
        documented = _env_mentions(env_example) if env_example else {}

        uses: dict[str, tuple[str, int]] = {}
        for rel in project.repo_python_files():
            ctx = project.parse_aux(rel)
            if ctx is None:
                continue
            for key, lineno in collect_env_uses(ctx).items():
                uses.setdefault(key, (rel, lineno))

        for key, (rel, lineno) in sorted(uses.items()):
            if key not in declared:
                ctx = project.parse_aux(rel)
                out.append(
                    Finding(
                        rule=self.id,
                        path=rel,
                        line=lineno,
                        message=(
                            f"env var {key} is used here but not declared "
                            "in dct_tpu/config.py ENV_REGISTRY — add it "
                            "(with a one-line description) and to "
                            ".env.example"
                        ),
                        snippet=ctx.line(lineno).strip() if ctx else "",
                    )
                )
        cfg_line = (
            cfg_ctx.line if cfg_ctx is not None else (lambda _i: "")
        )
        for key, lineno in sorted(declared.items()):
            if key not in documented:
                out.append(
                    Finding(
                        rule=self.id,
                        path=_CONFIG_RELPATH,
                        line=lineno,
                        message=(
                            f"registry entry {key} is not mentioned in "
                            ".env.example — document the knob (a "
                            "commented `# {key}=` line suffices)"
                        ),
                        snippet=cfg_line(lineno).strip(),
                    )
                )
            if key not in uses:
                out.append(
                    Finding(
                        rule=self.id,
                        path=_CONFIG_RELPATH,
                        line=lineno,
                        message=(
                            f"registry entry {key} is never read or set "
                            "by any first-party code — dead entry; "
                            "delete it (and its .env.example mention) or "
                            "wire it up"
                        ),
                        snippet=cfg_line(lineno).strip(),
                    )
                )
        if env_example:
            for key, lineno in sorted(documented.items()):
                if key not in declared:
                    out.append(
                        Finding(
                            rule=self.id,
                            path=_ENV_EXAMPLE_RELPATH,
                            line=lineno,
                            message=(
                                f".env.example mentions {key}, which is "
                                "not declared in dct_tpu/config.py "
                                "ENV_REGISTRY — stale doc or missing "
                                "declaration"
                            ),
                            snippet=env_example.splitlines()[
                                lineno - 1
                            ].strip(),
                        )
                    )
        return out


# ----------------------------------------------------------------------
# event-names


_DOCS_RELPATH = "docs/OBSERVABILITY.md"
_TABLE_HEADER_RE = re.compile(
    r"^\|\s*component\s*\|\s*events\s*\|\s*$", re.I
)
_BACKTICK_RE = re.compile(r"`([^`]+)`")


def parse_event_table(markdown: str) -> dict[str, set[str]] | None:
    """The ``| component | events |`` table -> component -> allowed
    event names (every backticked token in the events cell; prose
    tokens only ever widen the allowlist). None when the table is
    absent."""
    lines = markdown.splitlines()
    for i, line in enumerate(lines):
        if not _TABLE_HEADER_RE.match(line.strip()):
            continue
        table: dict[str, set[str]] = {}
        for row in lines[i + 1 :]:
            row = row.strip()
            if not row.startswith("|"):
                break
            cells = [c.strip() for c in row.strip("|").split("|")]
            if len(cells) < 2 or set(cells[0]) <= {"-", " ", ":"}:
                continue
            comp_tokens = _BACKTICK_RE.findall(cells[0])
            if not comp_tokens:
                continue
            events = set()
            for cell in cells[1:]:
                events.update(_BACKTICK_RE.findall(cell))
            table[comp_tokens[0]] = events
        return table
    return None


@register
class EventNamesRule(Rule):
    id = "event-names"
    name = "EventLog emit sites use documented event names"
    doc = (
        "Every statically-resolvable `*.emit(component, event, ...)` "
        "site must use a (component, event) pair present in "
        "docs/OBSERVABILITY.md's event table. Emitting an undocumented "
        "name ships telemetry no operator query will find — document "
        "the event (one table row) in the same change that emits it."
    )

    def check(self, project: Project) -> list[Finding]:
        markdown = project.read(_DOCS_RELPATH)
        table = parse_event_table(markdown) if markdown else None
        if table is None:
            # No docs, nothing to hold emit sites against: only flag
            # when there are emit sites that would need it.
            table = {}
        out: list[Finding] = []
        for ctx in project.contexts:
            if ctx.tree is None:
                continue
            for call in iter_calls(ctx.tree):
                if (
                    not isinstance(call.func, ast.Attribute)
                    or call.func.attr != "emit"
                    or len(call.args) < 2
                ):
                    continue
                comps = string_candidates(call.args[0])
                events = string_candidates(call.args[1])
                if comps is None or events is None:
                    continue  # dynamic: not statically checkable
                for comp in comps:
                    allowed = table.get(comp)
                    if allowed is None:
                        out.append(
                            ctx.finding(
                                self.id,
                                call,
                                f"event component `{comp}` is not in "
                                f"{_DOCS_RELPATH}'s event table — add a "
                                "row documenting this component's events",
                            )
                        )
                        continue
                    for evt in events:
                        if evt not in allowed:
                            out.append(
                                ctx.finding(
                                    self.id,
                                    call,
                                    f"event `{comp}`/`{evt}` is not "
                                    f"documented in {_DOCS_RELPATH}'s "
                                    "event table — add it to the "
                                    f"`{comp}` row (telemetry schema is "
                                    "an operator API)",
                                )
                            )
        return out

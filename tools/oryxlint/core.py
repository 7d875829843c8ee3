"""oryxlint core: project model, annotations, suppression, checker SPI.

A ``Project`` holds every source module in scope parsed once (AST +
raw lines + per-line annotations); checkers receive the whole project so
cross-module reasoning (call graphs, class indexes) is cheap and shared.

Annotation grammar (trailing comments, parsed per line):

- suppression: ``oryxlint: disable=<rule>[,<rule>...]`` — suppresses
  findings of those rules reported on the same line (or the line
  directly below, for call sites wrapped past the comment). Naming a
  rule id that no checker defines is itself a finding (rule
  ``unknown-rule``), so a typo cannot silently disable nothing.
- off-loop proof: ``oryxlint: offloop`` on a ``def`` line — the function
  is proven to run on a worker thread, never an event loop; the
  blocking-call walk does not traverse into it.
- lock contract: ``oryxlint: holds=<lockattr>[,<lockattr>...]`` on a
  ``def`` line — every caller holds those locks (the machine-checked
  form of a "call under _lock" docstring); guarded-attribute accesses
  inside the function are treated as locked.
- guarded attribute: ``guarded-by: <lockattr>[|<alt>...]`` trailing an
  attribute assignment (normally its ``__init__`` declaration). Accesses
  of that attribute elsewhere in the class must hold one of the named
  locks. A ``(writes)`` qualifier restricts the check to stores — the
  idiom for snapshot-swap state whose reads are deliberately lock-free.
- donation contract: ``oryxlint: donates=<pos>`` on a ``def`` line
  declares a hand-written wrapper whose positional argument ``pos`` is
  consumed like a ``donate_argnums`` buffer; ``donates=<pos> when
  <kwarg>`` restricts it to call sites passing that keyword as a
  literal ``True`` (the conditional-donation wrapper idiom).
- terminal read: ``oryxlint: sink`` on a use (or read) line — the
  dataflow ``param-dropped`` rule treats the annotated use as an
  intentional terminal consumption of the value, even though it is
  neither a call argument, an attribute store, nor a returned value.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from pathlib import Path

ANN_DISABLE = re.compile(r"#\s*oryxlint:\s*disable=([A-Za-z0-9_,\- ]+)")
ANN_OFFLOOP = re.compile(r"#\s*oryxlint:\s*offloop\b")
ANN_HOLDS = re.compile(r"#\s*oryxlint:\s*holds=([A-Za-z0-9_,| ]+)")
ANN_GUARDED = re.compile(
    r"#\s*guarded-by:\s*([A-Za-z0-9_|.]+)(?:\s*\((writes)\))?"
)
ANN_DONATES = re.compile(
    r"#\s*oryxlint:\s*donates=(\d+)(?:\s+when\s+([A-Za-z_][A-Za-z0-9_]*))?"
)
ANN_SINK = re.compile(r"#\s*oryxlint:\s*sink\b")


@dataclass
class Finding:
    """One rule violation at a source location.

    ``severity`` and ``fix_hint`` are rule-level metadata attached by
    ``run_lint`` from the checker catalogs — stable fields of the
    ``--json`` schema (consumed by tools/precommit.sh for grouped
    display). The tier-1 gate fails on any active finding regardless of
    severity; the field is display/triage metadata, not policy."""

    path: str  # repo-relative
    line: int
    rule: str
    message: str
    severity: str = "error"
    fix_hint: str = ""

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    def as_dict(self) -> dict:
        return {
            "path": self.path,
            "line": self.line,
            "rule": self.rule,
            "severity": self.severity,
            "fix_hint": self.fix_hint,
            "message": self.message,
        }


class SourceModule:
    """One parsed source file plus its per-line oryxlint annotations."""

    def __init__(self, path: Path, relpath: str, text: str):
        self.path = path
        self.relpath = relpath
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=relpath)
        # line -> set of rule ids disabled there
        self.disables: dict[int, set[str]] = {}
        # def lines annotated offloop / holds=<locks>
        self.offloop_lines: set[int] = set()
        self.holds_lines: dict[int, tuple[str, ...]] = {}
        # line -> (lock alternatives, writes_only) for guarded-by comments
        self.guarded_lines: dict[int, tuple[tuple[str, ...], bool]] = {}
        # def lines annotated donates=<pos> [when <kwarg>]
        self.donates_lines: dict[int, tuple[int, str | None]] = {}
        # lines annotated `oryxlint: sink` (intentional terminal reads)
        self.sink_lines: set[int] = set()
        for i, ln in enumerate(self.lines, start=1):
            if "#" not in ln:
                continue
            m = ANN_DISABLE.search(ln)
            if m:
                rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
                self.disables.setdefault(i, set()).update(rules)
            if ANN_OFFLOOP.search(ln):
                self.offloop_lines.add(i)
            m = ANN_HOLDS.search(ln)
            if m:
                locks = tuple(
                    t.strip() for t in re.split(r"[|,]", m.group(1)) if t.strip()
                )
                self.holds_lines[i] = locks
            m = ANN_GUARDED.search(ln)
            if m:
                alts = tuple(
                    t.strip() for t in m.group(1).split("|") if t.strip()
                )
                self.guarded_lines[i] = (alts, m.group(2) == "writes")
            m = ANN_DONATES.search(ln)
            if m:
                self.donates_lines[i] = (int(m.group(1)), m.group(2))
            if ANN_SINK.search(ln):
                self.sink_lines.add(i)

    def decorated_span(self, node) -> range:
        """Line range covering a def and its decorators (annotations on
        either count for the function)."""
        start = min(
            [node.lineno] + [d.lineno for d in getattr(node, "decorator_list", [])]
        )
        return range(start, node.body[0].lineno if node.body else node.lineno + 1)

    def fn_offloop(self, node) -> bool:
        return any(i in self.offloop_lines for i in self.decorated_span(node))

    def fn_holds(self, node) -> tuple[str, ...]:
        out: tuple[str, ...] = ()
        for i in self.decorated_span(node):
            out += self.holds_lines.get(i, ())
        return out

    def fn_donates(self, node) -> tuple[int, str | None] | None:
        for i in self.decorated_span(node):
            if i in self.donates_lines:
                return self.donates_lines[i]
        return None


# Default lint scope relative to the repo root. tests/ hosts deliberate
# violation fixtures; tools/oryxlint/ hosts the annotation grammar itself
# (its docstrings would self-trigger the comment scanners).
SCOPE_DIRS = ("oryx_tpu",)
SCOPE_TOP_FILES = ("chip_smoke.py",)
SCOPE_TOOL_GLOB = "tools/*.py"


class Project:
    """Every source module in lint scope, parsed once."""

    def __init__(self, root: Path, modules: list[SourceModule]):
        self.root = Path(root)
        self.modules = modules

    @classmethod
    def load(cls, root: str | Path, files: list[str] | None = None) -> "Project":
        root = Path(root).resolve()
        paths: list[Path] = []
        if files is None:
            for d in SCOPE_DIRS:
                paths.extend(sorted((root / d).rglob("*.py")))
            for f in SCOPE_TOP_FILES:
                if (root / f).exists():
                    paths.append(root / f)
            paths.extend(sorted(root.glob(SCOPE_TOOL_GLOB)))
        else:
            paths = [root / f for f in files]
        modules: list[SourceModule] = []
        for p in paths:
            if "__pycache__" in p.parts or not p.exists():
                continue
            rel = str(p.relative_to(root))
            text = p.read_text(encoding="utf-8")
            modules.append(SourceModule(p, rel, text))
        return cls(root, modules)

    def module(self, relpath: str) -> SourceModule | None:
        for m in self.modules:
            if m.relpath == relpath:
                return m
        return None


class Checker:
    """Checker SPI: subclasses declare their rule catalog and visit the
    project. ``rules`` maps rule id -> one-line description (surfaced by
    ``--list-rules`` and validated against suppression comments).
    ``severities`` (rule id -> "error"|"warning", default "error") and
    ``fix_hints`` (rule id -> one-line remediation) feed the stable
    per-finding ``severity``/``fix_hint`` fields of the --json schema."""

    name = "checker"
    rules: dict[str, str] = {}
    severities: dict[str, str] = {}
    fix_hints: dict[str, str] = {}

    def check(self, project: Project) -> list[Finding]:  # pragma: no cover
        raise NotImplementedError


def _all_checkers() -> list[Checker]:
    from tools.oryxlint.checkers import ALL_CHECKERS

    return [cls() for cls in ALL_CHECKERS]


def known_rules(checkers: list[Checker] | None = None) -> dict[str, str]:
    out = {"unknown-rule": "a suppression comment names a rule id no checker defines"}
    for c in checkers if checkers is not None else _all_checkers():
        out.update(c.rules)
    return out


def _unknown_rule_findings(
    project: Project, rules: dict[str, str]
) -> list[Finding]:
    out = []
    for mod in project.modules:
        for line, ids in sorted(mod.disables.items()):
            for rid in sorted(ids):
                if rid not in rules:
                    out.append(Finding(
                        mod.relpath, line, "unknown-rule",
                        f"suppression names unknown rule {rid!r} "
                        f"(known: {', '.join(sorted(rules))})",
                    ))
    return out


def _suppressed(mod: SourceModule | None, f: Finding) -> bool:
    """A finding is suppressed by a disable comment on its own line or the
    line directly above (wrapped call sites). ``unknown-rule`` findings
    are never suppressible — they flag the suppression syntax itself."""
    if f.rule == "unknown-rule" or mod is None:
        return False
    for line in (f.line, f.line - 1):
        if f.rule in mod.disables.get(line, ()):
            return True
    return False


def run_lint(
    root: str | Path,
    checkers: list[Checker] | None = None,
    changed: set[str] | None = None,
) -> tuple[list[Finding], list[Finding]]:
    """Run checkers over the tree; returns (active, suppressed) findings.

    ``changed`` (repo-relative paths) filters per-file findings to those
    files — the ``--changed`` pre-commit mode. Whole-tree consistency
    findings (reference.conf / docs / ratchet drift) always report: they
    are cheap and a stale doc row is actionable no matter which file the
    commit touches.
    """
    project = Project.load(root)
    cs = checkers if checkers is not None else _all_checkers()
    rules = known_rules(cs)
    severities = {"unknown-rule": "error"}
    fix_hints = {
        "unknown-rule": "fix the rule id in the disable comment "
        "(see --list-rules)",
    }
    for c in cs:
        severities.update(c.severities)
        fix_hints.update(c.fix_hints)
    raw: list[Finding] = []
    for c in cs:
        raw.extend(c.check(project))
    raw.extend(_unknown_rule_findings(project, rules))
    for f in raw:
        # rule-catalog metadata fills defaults only: a checker that set a
        # per-finding severity/fix_hint keeps it
        if f.severity == "error":
            f.severity = severities.get(f.rule, "error")
        if not f.fix_hint:
            f.fix_hint = fix_hints.get(f.rule, "")
    mods = {m.relpath: m for m in project.modules}
    active: list[Finding] = []
    suppressed: list[Finding] = []
    for f in raw:
        if _suppressed(mods.get(f.path), f):
            suppressed.append(f)
        elif changed is not None and f.path in mods and f.path not in changed:
            continue  # per-file finding outside the changed set
        else:
            active.append(f)
    active.sort(key=lambda f: (f.path, f.line, f.rule))
    return active, suppressed

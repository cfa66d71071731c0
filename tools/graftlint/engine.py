"""graftlint engine: findings, suppressions, baseline, file walking.

jax-free on purpose — the linter runs anywhere (CI boxes without a chip,
pre-commit hooks) in milliseconds, using only stdlib ``ast``.  The
rules themselves live in ``tools/graftlint/rules.py``; this module owns the
plumbing they share:

- :class:`Finding` — one diagnosis (``file:line``, rule id, message, fix
  hint) keyed for baselining by ``file::rule::<normalized source line>`` so
  entries survive unrelated line-number drift.
- inline suppressions — ``# graftlint: disable=JG001[,JG002]`` trailing on
  the offending line, ``# graftlint: disable-next-line=JG001`` on the line
  above it, or ``# graftlint: disable-file=JG001`` anywhere in the file.
- the checked-in baseline (``tools/graftlint/baseline.json``): pre-existing
  findings are explicit and counted; only *new* findings (a key appearing
  more often than the baseline records) fail the run.
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

_SUPPRESS_RE = re.compile(r"#\s*graftlint:\s*disable=([A-Z0-9,\s]+)")
_SUPPRESS_NEXT_RE = re.compile(r"#\s*graftlint:\s*disable-next-line=([A-Z0-9,\s]+)")
_SUPPRESS_FILE_RE = re.compile(r"#\s*graftlint:\s*disable-file=([A-Z0-9,\s]+)")


@dataclass(frozen=True)
class Finding:
    """One diagnosis.  ``snippet`` is the stripped source line — part of the
    baseline key so baselined findings track the code, not the line number."""

    file: str  # repo-relative posix path
    line: int
    rule: str  # "JG001"
    message: str
    hint: str = ""
    snippet: str = ""

    @property
    def key(self) -> str:
        return f"{self.file}::{self.rule}::{self.snippet}"

    def render(self) -> str:
        out = f"{self.file}:{self.line}: {self.rule} {self.message}"
        if self.hint:
            out += f"\n    hint: {self.hint}"
        return out


class ModuleContext:
    """Shared per-file analysis state handed to every rule."""

    def __init__(self, relpath: str, source: str) -> None:
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source)
        self._parents: Dict[ast.AST, ast.AST] = {}
        # cache the full node list while building the parent map: every rule
        # iterates it via walk(), so the tree is traversed once per file
        # instead of once per rule
        self._nodes: List[ast.AST] = [self.tree]
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self._parents[child] = node
                self._nodes.append(child)
        self._jit_index = None

    def walk(self) -> List[ast.AST]:
        """Every node in the tree (ast.walk order) — the shared-walk path."""
        return self._nodes

    def jit_index(self):
        """The module's jit-wrapper index, built once and shared by every
        rule that needs it (JG002-JG005 each used to rebuild it)."""
        if self._jit_index is None:
            from tools.graftlint.rules import _JitIndex

            self._jit_index = _JitIndex(self)
        return self._jit_index

    # -- tree navigation ------------------------------------------------
    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self._parents.get(node)

    def ancestors(self, node: ast.AST) -> Iterable[ast.AST]:
        cur = self._parents.get(node)
        while cur is not None:
            yield cur
            cur = self._parents.get(cur)

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return anc
        return None

    def enclosing_loop(self, node: ast.AST) -> Optional[ast.AST]:
        """Nearest For/While ancestor within the same function scope."""
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return None
            if isinstance(anc, (ast.For, ast.AsyncFor, ast.While)):
                return anc
        return None

    def enclosing_statement(self, node: ast.AST) -> ast.AST:
        stmt = node
        for anc in self.ancestors(node):
            if isinstance(anc, ast.stmt):
                stmt = anc
                break
        return stmt

    def segment(self, node: ast.AST) -> str:
        try:
            return ast.get_source_segment(self.source, node) or ""
        except Exception:  # pragma: no cover - malformed position info
            return ""

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def finding(
        self, node: ast.AST, rule: str, message: str, hint: str = ""
    ) -> Finding:
        line = getattr(node, "lineno", 1)
        return Finding(
            file=self.relpath,
            line=line,
            rule=rule,
            message=message,
            hint=hint,
            snippet=self.line_text(line),
        )


# ---------------------------------------------------------------------------
# small AST helpers rules share


def attr_path(node: ast.AST) -> Optional[str]:
    """Dotted path for Name/Attribute chains ("self.agent.learn"); None if
    the chain passes through calls/subscripts/etc."""
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    return None


def root_name(node: ast.AST) -> Optional[str]:
    """Leftmost identifier of an expression (descends calls/attrs/subscripts)."""
    cur = node
    while True:
        if isinstance(cur, ast.Call):
            cur = cur.func
        elif isinstance(cur, (ast.Attribute, ast.Subscript, ast.Starred)):
            cur = cur.value
        elif isinstance(cur, ast.Name):
            return cur.id
        else:
            return None


def assign_target_paths(stmt: ast.AST) -> List[str]:
    """Dotted paths of every assignment target in a statement (tuple
    targets flattened); empty for non-assignments."""
    targets: List[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        targets = [stmt.target]
    out: List[str] = []
    for t in targets:
        stack = [t]
        while stack:
            cur = stack.pop()
            if isinstance(cur, (ast.Tuple, ast.List)):
                stack.extend(cur.elts)
            elif isinstance(cur, ast.Starred):
                stack.append(cur.value)
            else:
                p = attr_path(cur)
                if p is not None:
                    out.append(p)
    return out


# ---------------------------------------------------------------------------
# suppressions


def _parse_rules(blob: str) -> Set[str]:
    return {r.strip() for r in blob.split(",") if r.strip()}


def collect_suppressions(lines: Sequence[str]) -> Tuple[Dict[int, Set[str]], Set[str]]:
    """Map line -> suppressed rule ids, plus file-wide suppressed rules."""
    by_line: Dict[int, Set[str]] = {}
    file_wide: Set[str] = set()
    for i, text in enumerate(lines, start=1):
        m = _SUPPRESS_FILE_RE.search(text)
        if m:
            file_wide |= _parse_rules(m.group(1))
            continue
        m = _SUPPRESS_NEXT_RE.search(text)
        if m:
            by_line.setdefault(i + 1, set()).update(_parse_rules(m.group(1)))
            continue
        m = _SUPPRESS_RE.search(text)
        if m:
            by_line.setdefault(i, set()).update(_parse_rules(m.group(1)))
    return by_line, file_wide


# ---------------------------------------------------------------------------
# baseline


def load_baseline(path: str) -> Dict[str, int]:
    with open(path, "r") as f:
        data = json.load(f)
    entries = data.get("entries", {})
    return {str(k): int(v) for k, v in entries.items()}


def write_baseline(path: str, findings: Sequence[Finding]) -> None:
    counts: Dict[str, int] = {}
    for f in findings:
        counts[f.key] = counts.get(f.key, 0) + 1
    payload = {
        "version": 1,
        "generated_by": "python -m tools.graftlint --write-baseline",
        "entries": dict(sorted(counts.items())),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=False)
        f.write("\n")


def partition_new(
    findings: Sequence[Finding], baseline: Dict[str, int]
) -> Tuple[List[Finding], List[Finding]]:
    """Split findings into (baselined, new).  A key occurring more often
    than the baseline records spills the excess into ``new`` — adding a
    second violation on an already-baselined line still fails the gate."""
    budget = dict(baseline)
    old: List[Finding] = []
    new: List[Finding] = []
    for f in sorted(findings, key=lambda f: (f.file, f.line)):
        if budget.get(f.key, 0) > 0:
            budget[f.key] -= 1
            old.append(f)
        else:
            new.append(f)
    return old, new


# ---------------------------------------------------------------------------
# running


def lint_source(source: str, relpath: str) -> List[Finding]:
    """Lint one file's source with the per-file rules (JG001-JG005) only;
    the whole-program rules need the full tree — see :func:`lint_sources`."""
    from tools.graftlint.rules import RULES

    ctx = ModuleContext(relpath, source)
    by_line, file_wide = collect_suppressions(ctx.lines)
    findings: List[Finding] = []
    for rule_id, _title, fn in RULES:
        if rule_id in file_wide:
            continue
        for f in fn(ctx):
            if f.rule in by_line.get(f.line, ()):
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.line, f.rule))
    return findings


#: relpath suffix that marks a lint run as covering the whole program: the
#: telemetry registry is the host plane's innermost module, so a run that
#: includes it is linting the full tree and the global joins (JG007 both
#: directions, JG009 doc->code) are sound.  Single-file runs skip them.
_COMPLETE_SENTINEL = "runtime/telemetry.py"


def lint_sources(
    items: Sequence[Tuple[str, str]],
    catalog_text: Optional[str] = None,
    complete: Optional[bool] = None,
    stats_out: Optional[Dict[str, float]] = None,
) -> List[Finding]:
    """Two-phase lint over ``(relpath, source)`` pairs.

    Phase 1 runs the per-file rules and harvests each module's facts off
    the same parsed AST; phase 2 joins the facts across files and runs the
    whole-program rules (JG006-JG009).  Phase-2 findings honor the anchor
    file's inline/file-wide suppressions just like per-file findings.

    ``catalog_text`` is docs/OBSERVABILITY.md for JG009 (None skips it).
    ``complete`` marks the item set as the whole program; None auto-detects
    via :data:`_COMPLETE_SENTINEL`.  ``stats_out`` receives wall-clock
    seconds per stage when provided.
    """
    import time as _time

    from tools.graftlint.facts import harvest
    from tools.graftlint.rules import RULES
    from tools.graftlint.xrules import XRULES, Program, parse_catalog

    findings: List[Finding] = []
    all_facts = []
    lines_by_file: Dict[str, List[str]] = {}
    t_parse = t_rules = t_facts = 0.0

    for relpath, source in items:
        rel = relpath.replace(os.sep, "/")
        t0 = _time.perf_counter()
        try:
            ctx = ModuleContext(rel, source)
        except SyntaxError as e:
            t_parse += _time.perf_counter() - t0
            findings.append(
                Finding(
                    file=rel,
                    line=e.lineno or 1,
                    rule="JG000",
                    message=f"file does not parse: {e.msg}",
                    snippet="",
                )
            )
            continue
        t_parse += _time.perf_counter() - t0
        lines_by_file[rel] = ctx.lines
        by_line, file_wide = collect_suppressions(ctx.lines)

        t0 = _time.perf_counter()
        for rule_id, _title, fn in RULES:
            if rule_id in file_wide:
                continue
            for f in fn(ctx):
                if f.rule in by_line.get(f.line, ()):
                    continue
                findings.append(f)
        t_rules += _time.perf_counter() - t0

        t0 = _time.perf_counter()
        all_facts.append(harvest(ctx, by_line, file_wide))
        t_facts += _time.perf_counter() - t0

    t0 = _time.perf_counter()
    if complete is None:
        complete = any(m.relpath.endswith(_COMPLETE_SENTINEL) for m in all_facts)
    catalog = parse_catalog(catalog_text) if catalog_text is not None else None
    prog = Program(
        modules=all_facts,
        complete=complete,
        catalog=catalog,
        lines=lines_by_file,
    )
    if catalog_text is not None:
        prog.lines[prog.catalog_relpath] = catalog_text.splitlines()
    suppress = {m.relpath: (m.suppress_lines, m.suppress_file) for m in all_facts}
    for rule_id, _title, fn in XRULES:
        for f in fn(prog):
            by_line, file_wide = suppress.get(f.file, ({}, set()))
            if f.rule in file_wide or f.rule in by_line.get(f.line, ()):
                continue
            findings.append(f)
    if stats_out is not None:
        stats_out["join"] = _time.perf_counter() - t0
        stats_out["parse"] = t_parse
        stats_out["rules"] = t_rules
        stats_out["facts"] = t_facts
        stats_out["files"] = float(len(items))

    findings.sort(key=lambda f: (f.file, f.line, f.rule))
    return findings


def iter_python_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            out.append(p)
        elif os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for name in sorted(filenames):
                    if name.endswith(".py"):
                        out.append(os.path.join(dirpath, name))
    return sorted(set(out))


def lint_paths(
    paths: Sequence[str],
    repo_root: Optional[str] = None,
    stats_out: Optional[Dict[str, float]] = None,
) -> List[Finding]:
    """Two-phase lint of every .py under ``paths``; files that fail to
    parse yield a single parse-error finding instead of crashing the run.
    Picks up docs/OBSERVABILITY.md from ``repo_root`` for JG009 when it
    exists."""
    repo_root = repo_root or os.getcwd()
    items: List[Tuple[str, str]] = []
    for path in iter_python_files(paths):
        rel = os.path.relpath(os.path.abspath(path), repo_root).replace(os.sep, "/")
        with open(path, "r", encoding="utf-8") as f:
            items.append((rel, f.read()))
    catalog_text: Optional[str] = None
    catalog_path = os.path.join(repo_root, "docs", "OBSERVABILITY.md")
    if os.path.exists(catalog_path):
        with open(catalog_path, "r", encoding="utf-8") as f:
            catalog_text = f.read()
    return lint_sources(items, catalog_text=catalog_text, stats_out=stats_out)

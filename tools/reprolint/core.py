"""reprolint core — findings, suppressions, baseline, file walking.

The analyzer encodes the repo's correctness conventions (lease discipline,
no blocking under locks, journal-before-mutate, layering DAG) as AST
passes over the source tree. This module is the harness: it walks the
paths, parses each module once, dispatches the registered passes
(``tools.reprolint.passes``), and post-filters the findings through inline
suppressions and the checked-in baseline.

Inline suppression syntax (on the flagged line, or a comment line directly
above it)::

    some_flagged_code()  # reprolint: allow[rule-id] why this is legitimate

The reason string is REQUIRED — an empty reason does not suppress (the
finding is reported with a note instead), so every grandfathered site
documents itself.

Baseline: a checked-in file of fingerprinted findings that are known and
tolerated (target: empty). Fingerprints hash the rule id + the flagged
source line, so unrelated line-number drift does not invalidate them.
"""
from __future__ import annotations

import ast
import re
import zlib
from dataclasses import dataclass, field
from fnmatch import fnmatch
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

REPO_ROOT = Path(__file__).resolve().parents[2]

# fixture corpus of intentionally-bad examples: excluded by PATH (never by
# inline comments — the fixtures must stay byte-exact bad examples)
DEFAULT_EXCLUDES: Tuple[str, ...] = ("*__pycache__*", "*lint_fixtures*")

_SUPPRESS_RE = re.compile(
    r"#\s*reprolint:\s*allow\[([a-z0-9_,-]+)\]\s*(.*?)\s*$"
)


@dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    path: str  # repo-relative posix path (or as given, outside the repo)
    line: int  # 1-based
    rule: str
    message: str
    snippet: str = ""  # stripped source line (fingerprint input)

    @property
    def fingerprint(self) -> str:
        return f"{zlib.crc32((self.rule + chr(0) + self.snippet).encode()):08x}"

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclass
class ParsedModule:
    """One parsed source file handed to every pass."""

    path: Path
    rel: str  # repo-relative posix path
    text: str
    lines: List[str]
    tree: ast.Module
    module: Optional[str]  # dotted module name (``src/``-rooted), or None

    def finding(self, node_or_line, rule: str, message: str) -> Finding:
        line = getattr(node_or_line, "lineno", node_or_line)
        snippet = self.lines[line - 1].strip() if 0 < line <= len(self.lines) else ""
        return Finding(self.rel, line, rule, message, snippet)


@dataclass
class Suppression:
    line: int  # line the comment sits on
    rules: Tuple[str, ...]
    reason: str
    used: bool = False


@dataclass
class AnalysisResult:
    findings: List[Finding] = field(default_factory=list)  # actionable
    suppressed: List[Finding] = field(default_factory=list)
    baselined: List[Finding] = field(default_factory=list)
    files: int = 0

    @property
    def ok(self) -> bool:
        return not self.findings


def rel_path(path: Path) -> str:
    path = path.resolve()
    try:
        return path.relative_to(REPO_ROOT).as_posix()
    except ValueError:
        return path.as_posix()


def module_name(rel: str) -> Optional[str]:
    """Dotted module for a ``src/``-rooted file (layering pass key).
    ``src/repro/core/fs.py`` → ``repro.core.fs``; the LAST ``src`` path
    segment wins so fixture trees like ``tests/lint_fixtures/.../src/...``
    map the same way the real tree does."""
    parts = Path(rel).parts
    if "src" not in parts:
        return None
    idx = len(parts) - 1 - list(reversed(parts)).index("src")
    mod = list(parts[idx + 1 :])
    if not mod or not mod[-1].endswith(".py"):
        return None
    mod[-1] = mod[-1][:-3]
    if mod[-1] == "__init__":
        mod.pop()
    return ".".join(mod) if mod else None


def iter_py_files(paths: Sequence, exclude: Sequence[str]) -> List[Path]:
    """Expand files/directories into a sorted, de-duplicated .py file list,
    skipping anything whose repo-relative path matches an exclude glob."""
    out: List[Path] = []
    seen = set()

    def want(p: Path) -> bool:
        r = rel_path(p)
        return not any(fnmatch(r, pat) or fnmatch(p.name, pat)
                       for pat in exclude)

    for raw in paths:
        p = Path(raw)
        if p.is_dir():
            cands = sorted(p.rglob("*.py"))
        elif p.suffix == ".py":
            cands = [p]
        else:
            raise FileNotFoundError(f"not a .py file or directory: {raw}")
        for c in cands:
            rc = c.resolve()
            if rc not in seen and want(rc):
                seen.add(rc)
                out.append(rc)
    return out


def parse_module(path: Path) -> Tuple[Optional[ParsedModule], Optional[Finding]]:
    rel = rel_path(path)
    text = path.read_text(encoding="utf-8")
    try:
        tree = ast.parse(text, filename=str(path))
    except SyntaxError as e:
        return None, Finding(rel, e.lineno or 1, "parse-error",
                             f"syntax error: {e.msg}")
    return ParsedModule(path, rel, text, text.splitlines(), tree,
                        module_name(rel)), None


def collect_suppressions(mod: ParsedModule) -> Dict[int, Suppression]:
    """{effective line: Suppression}. A suppression comment covers the line
    it sits on; a comment-only line also covers the next line (so long
    statements can carry the comment above them)."""
    out: Dict[int, Suppression] = {}
    for i, raw in enumerate(mod.lines, start=1):
        m = _SUPPRESS_RE.search(raw)
        if not m:
            continue
        sup = Suppression(i, tuple(r.strip() for r in m.group(1).split(",")),
                          m.group(2).strip())
        out[i] = sup
        if raw.lstrip().startswith("#"):  # standalone comment: covers next line
            out.setdefault(i + 1, sup)
    return out


def load_baseline(path: Path) -> set:
    """Baseline lines: ``rule<TAB>path<TAB>fingerprint`` (+ ``#`` comments)."""
    entries = set()
    if not path.exists():
        return entries
    for raw in path.read_text(encoding="utf-8").splitlines():
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        parts = line.split("\t")
        if len(parts) != 3:
            raise ValueError(f"malformed baseline line: {raw!r}")
        entries.add((parts[0], parts[1], parts[2]))
    return entries


def format_baseline(findings: Iterable[Finding]) -> str:
    header = (
        "# reprolint baseline — grandfathered findings (target: EMPTY).\n"
        "# Each line: rule<TAB>path<TAB>fingerprint. Regenerate with\n"
        "#   python -m tools.reprolint --write-baseline <paths>\n"
    )
    body = "".join(
        f"{f.rule}\t{f.path}\t{f.fingerprint}\n"
        for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule))
    )
    return header + body


def run(paths: Sequence, *, rules: Optional[Sequence[str]] = None,
        exclude: Sequence[str] = DEFAULT_EXCLUDES,
        baseline: Optional[set] = None) -> AnalysisResult:
    """Programmatic entry point: analyze ``paths`` with the selected passes
    (default: all registered) and return the filtered result."""
    from tools.reprolint.passes import PASSES

    unknown = set(rules or ()) - set(PASSES)
    if unknown:
        raise ValueError(f"unknown rule(s): {sorted(unknown)}")
    active = {r: PASSES[r] for r in (rules or PASSES)}
    baseline = baseline or set()
    res = AnalysisResult()
    for path in iter_py_files(paths, exclude):
        res.files += 1
        mod, err = parse_module(path)
        if err is not None:
            res.findings.append(err)
            continue
        sups = collect_suppressions(mod)
        for rule, mod_pass in active.items():
            for f in mod_pass.check(mod):
                assert f.rule == rule, f"{mod_pass} emitted rule {f.rule}"
                sup = sups.get(f.line)
                if sup is not None and f.rule in sup.rules:
                    if not sup.reason:
                        res.findings.append(Finding(
                            f.path, f.line, f.rule,
                            f.message + " (suppression comment needs a "
                            "reason string — empty reasons do not suppress)",
                            f.snippet))
                        continue
                    sup.used = True
                    res.suppressed.append(f)
                elif (f.rule, f.path, f.fingerprint) in baseline:
                    res.baselined.append(f)
                else:
                    res.findings.append(f)
    res.findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return res


# ---------------------------------------------------------------- AST utils
def dotted(node: ast.AST) -> Optional[str]:
    """``self.fs.grant_lease`` for an Attribute/Name chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(call: ast.Call) -> Optional[str]:
    """Terminal name of a call: ``fs.grant_lease(...)`` → ``grant_lease``."""
    if isinstance(call.func, ast.Attribute):
        return call.func.attr
    if isinstance(call.func, ast.Name):
        return call.func.id
    return None


def own_nodes(fn_body: Sequence[ast.stmt]) -> Iterable[ast.AST]:
    """Every node in a statement list EXCLUDING nested function/class
    bodies — 'runs when this body runs', which is what lock regions and
    release-path analysis care about (a nested def is deferred work)."""
    stack: List[ast.AST] = list(fn_body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            continue  # do not descend into deferred/contained bodies
        stack.extend(ast.iter_child_nodes(node))


def function_bodies(tree: ast.Module):
    """Yield (name, body) for the module top level and every (nested)
    function — each body analyzed with ``own_nodes`` semantics."""
    yield "<module>", tree.body
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node.name, node.body

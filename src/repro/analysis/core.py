"""reprolint core: findings, suppressions, config, and the lint driver.

The repo's parity results (heap-vs-engine 3-sigma bands, the
executor/digital-twin contract of DESIGN.md Sec 10, batch-composition
invariance, bitwise cache transparency) rest on conventions that no unit
test can pin globally: dedicated RNG child streams are *spawned* — never
drawn — from parents, traced values never hit Python control flow inside
``lax.scan``/Pallas bodies, server I/O is billed per attempt, and the
canonical ``min_interval``/``max_interval`` spellings are used everywhere
outside the deprecation shims.  ``reprolint`` turns those conventions into
machine-checked law: a small AST rule framework (DESIGN.md Sec 12) run
over the whole tree by CI's ``lint`` job and by the tier-1 self-check in
``tests/test_reprolint.py``.

Suppressions
------------
A finding is silenced *only* by an inline comment carrying a
justification::

    foo = np.random.rand()  # reprolint: ignore[R001] -- demo of the legacy API

The comment may sit on the finding's line or alone on the line directly
above.  An ``ignore`` without the ``-- <why>`` tail does **not** suppress
anything and is itself reported (rule S000): an unexplained exemption is
exactly the silent convention-drift this tool exists to prevent.
"""
from __future__ import annotations

import ast
import dataclasses
import fnmatch
import re
import tomllib
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Finding", "Rule", "LintConfig", "LintReport", "RULES", "register_rule",
    "lint_source", "lint_paths", "parse_suppressions", "Suppression",
]


# --------------------------------------------------------------------------- #
# Findings and rules                                                          #
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    rule: str
    path: str          # POSIX-relative to the lint root
    line: int          # 1-based
    col: int           # 0-based (ast convention)
    message: str
    severity: str = "error"        # "error" gates; "info" is report-only
    suppressed: bool = False
    justification: str = ""

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        tag = " (suppressed)" if self.suppressed else ""
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}{tag}"


@dataclasses.dataclass(frozen=True)
class Rule:
    """A registered contract check.

    ``check(tree, source, relpath, config)`` returns raw findings; the
    driver applies suppressions, config disables, and report-only
    downgrades afterwards, so rules stay pure AST logic.
    """

    id: str
    summary: str
    invariant: str      # the repo invariant this rule guards (docs/DESIGN)
    check: Callable[[ast.AST, str, str, "LintConfig"], List[Finding]]
    severity: str = "error"


RULES: Dict[str, Rule] = {}


def register_rule(id: str, summary: str, invariant: str,
                  severity: str = "error"):
    """Decorator registering a rule's check function under ``id``."""
    def deco(fn):
        RULES[id] = Rule(id=id, summary=summary, invariant=invariant,
                         check=fn, severity=severity)
        return fn
    return deco


# --------------------------------------------------------------------------- #
# Configuration ([tool.reprolint] in pyproject.toml)                          #
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class LintConfig:
    """Per-repo knobs; one source of truth in ``[tool.reprolint]``.

    Path entries are POSIX-relative to the lint root; a directory entry
    covers everything beneath it.
    """

    exclude: Tuple[str, ...] = ("tests/lint_fixtures",)
    disable: Tuple[str, ...] = ()
    report_only: Tuple[str, ...] = ("B001",)
    # R003: virtual-time subsystems where wall-clock / stdlib-random calls
    # are forbidden, and the explicitly justified measurement sites.
    r003_paths: Tuple[str, ...] = (
        "src/repro/sim", "src/repro/exec", "src/repro/p2p",
        "src/repro/serve", "src/repro/runtime")
    r003_allow: Tuple[str, ...] = ()
    # A001: extra files allowed to use the deprecated spellings (the shim
    # *definitions* are recognized structurally and need no entry here).
    a001_allow: Tuple[str, ...] = ()
    # J003: files whose Pallas kernel bodies must stay out of float64.
    kernel_globs: Tuple[str, ...] = ("src/repro/kernels/*.py",)

    @staticmethod
    def from_pyproject(root: Path) -> "LintConfig":
        data = _read_pyproject_table(root / "pyproject.toml")
        if not data:
            return LintConfig()
        def tup(key, default):
            v = data.get(key)
            if v is None:
                return default
            if isinstance(v, str):
                v = [v]
            return tuple(str(x) for x in v)
        return LintConfig(
            exclude=tup("exclude", LintConfig.exclude),
            disable=tup("disable", ()),
            report_only=tup("report-only", LintConfig.report_only),
            r003_paths=tup("r003-paths", LintConfig.r003_paths),
            r003_allow=tup("r003-allow", ()),
            a001_allow=tup("a001-allow", ()),
            kernel_globs=tup("kernel-globs", LintConfig.kernel_globs),
        )


def _read_pyproject_table(path: Path) -> dict:
    if not path.is_file():
        return {}
    text = path.read_text(encoding="utf-8")
    return tomllib.loads(text).get("tool", {}).get("reprolint", {})


def path_matches(relpath: str, entries: Sequence[str]) -> bool:
    """True when ``relpath`` equals an entry, sits under a directory
    entry, or matches a glob entry."""
    for e in entries:
        e = e.rstrip("/")
        if relpath == e or relpath.startswith(e + "/"):
            return True
        if fnmatch.fnmatch(relpath, e):
            return True
    return False


# --------------------------------------------------------------------------- #
# Suppressions                                                                #
# --------------------------------------------------------------------------- #

_SUPPRESS_RE = re.compile(
    r"#\s*reprolint:\s*ignore\[([A-Za-z0-9,\s]+)\]\s*(?:--\s*(\S.*))?")


@dataclasses.dataclass(frozen=True)
class Suppression:
    line: int                  # line the comment physically sits on
    rules: Tuple[str, ...]
    justification: str
    standalone: bool           # comment-only line -> applies to next line


def parse_suppressions(source: str) -> List[Suppression]:
    out = []
    for i, text in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        rules = tuple(r.strip() for r in m.group(1).split(",") if r.strip())
        just = (m.group(2) or "").strip()
        standalone = text.strip().startswith("#")
        out.append(Suppression(line=i, rules=rules, justification=just,
                               standalone=standalone))
    return out


def _apply_suppressions(findings: List[Finding], sups: List[Suppression],
                        relpath: str) -> List[Finding]:
    """Mark suppressed findings; emit S000 for justification-free ignores."""
    by_line: Dict[int, List[Suppression]] = {}
    for s in sups:
        by_line.setdefault(s.line, []).append(s)
        if s.standalone:
            by_line.setdefault(s.line + 1, []).append(s)

    out = []
    for f in findings:
        matched = None
        for s in by_line.get(f.line, ()):
            if f.rule in s.rules or "ALL" in s.rules:
                matched = s
                break
        if matched is not None and matched.justification:
            f = dataclasses.replace(f, suppressed=True,
                                    justification=matched.justification)
        out.append(f)
    for s in sups:
        if not s.justification:
            out.append(Finding(
                rule="S000", path=relpath, line=s.line, col=0,
                message="suppression without a justification "
                        "(write `# reprolint: ignore[RULE] -- why`); "
                        "nothing is suppressed",
                severity="error"))
    return out


# --------------------------------------------------------------------------- #
# Driver                                                                      #
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class LintReport:
    findings: List[Finding]
    files_scanned: int
    config: LintConfig

    @property
    def gating(self) -> List[Finding]:
        """Findings that fail the lint gate (exit code 1)."""
        return [f for f in self.findings
                if not f.suppressed and f.severity == "error"
                and f.rule not in self.config.report_only]

    @property
    def exit_code(self) -> int:
        return 1 if self.gating else 0

    def to_dict(self) -> dict:
        return {
            "files_scanned": self.files_scanned,
            "n_findings": len(self.findings),
            "n_gating": len(self.gating),
            "exit_code": self.exit_code,
            "findings": [f.to_dict() for f in self.findings],
        }


def lint_source(source: str, relpath: str,
                config: Optional[LintConfig] = None,
                rules: Optional[Iterable[str]] = None) -> List[Finding]:
    """Lint one file's text as if it lived at ``relpath`` under the root.

    The path matters: R003's subsystem scoping and J003's kernel globs key
    off it — which is also what lets tests drive a fixture "as"
    ``src/repro/sim/whatever.py``.
    """
    config = config or LintConfig()
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(rule="E000", path=relpath, line=e.lineno or 1,
                        col=e.offset or 0,
                        message=f"syntax error: {e.msg}")]
    selected = rules if rules is not None else [
        rid for rid in RULES if rid not in config.disable]
    findings: List[Finding] = []
    for rid in selected:
        rule = RULES[rid]
        for f in rule.check(tree, source, relpath, config):
            if f.severity == "error" and rule.severity == "info":
                f = dataclasses.replace(f, severity="info")
            findings.append(f)
    findings = _apply_suppressions(findings, parse_suppressions(source),
                                   relpath)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def iter_py_files(paths: Sequence[str], root: Path,
                  config: LintConfig) -> List[Path]:
    files: List[Path] = []
    for p in paths:
        path = (root / p) if not Path(p).is_absolute() else Path(p)
        if path.is_file() and path.suffix == ".py":
            files.append(path)
        elif path.is_dir():
            files.extend(sorted(path.rglob("*.py")))
    seen = set()
    out = []
    for f in files:
        rel = _relpath(f, root)
        if rel in seen or path_matches(rel, config.exclude):
            continue
        seen.add(rel)
        out.append(f)
    return out


def _relpath(path: Path, root: Path) -> str:
    try:
        return path.resolve().relative_to(root.resolve()).as_posix()
    except ValueError:
        return path.as_posix()


def lint_paths(paths: Sequence[str], root: Path,
               config: Optional[LintConfig] = None) -> LintReport:
    """Lint every ``.py`` under ``paths`` (files or directories)."""
    # Import for side effect: rule registration.
    from repro.analysis import rules_accounting  # noqa: F401
    from repro.analysis import rules_api         # noqa: F401
    from repro.analysis import rules_jax         # noqa: F401
    from repro.analysis import rules_rng         # noqa: F401

    config = config or LintConfig.from_pyproject(root)
    findings: List[Finding] = []
    files = iter_py_files(paths, root, config)
    for f in files:
        src = f.read_text(encoding="utf-8")
        findings.extend(lint_source(src, _relpath(f, root), config))
    return LintReport(findings=findings, files_scanned=len(files),
                      config=config)

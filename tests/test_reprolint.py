"""reprolint: every rule against its bad/good fixture pair, the
suppression contract, the CLI gate, and the repo-wide self-check.

The self-check (`test_repo_is_violation_free`) is the tier-1 anchor: a
convention regression anywhere in src/tests/benchmarks/examples fails the
default lanes, not just the CI `lint` job.
"""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import (LintConfig, RULES, lint_paths, lint_source,
                            render_json)
from repro.analysis.core import parse_suppressions, path_matches

ROOT = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).parent / "lint_fixtures"

# rule id -> path each fixture pretends to live at (R003 is scoped to the
# virtual-time subsystems, J003 to the kernel files; the rest only need
# to escape the fixture-dir exclusion).
PRETEND = {
    "R003": "src/repro/sim/fixture.py",
    "J003": "src/repro/kernels/fixture.py",
}
RULE_IDS = ["R001", "R002", "R003", "J001", "J002", "J003",
            "A001", "A002", "B001", "S000"]


def _lint_fixture(rule_id: str, kind: str, config=None):
    name = f"{rule_id.lower()}_{kind}.py"
    src = (FIXTURES / name).read_text(encoding="utf-8")
    rel = PRETEND.get(rule_id, f"src/repro/{name}")
    return lint_source(src, rel, config or LintConfig())


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_bad_fixture_fails(rule_id):
    findings = [f for f in _lint_fixture(rule_id, "bad")
                if not f.suppressed and f.rule == rule_id]
    assert findings, f"{rule_id} bad fixture produced no {rule_id} finding"
    for f in findings:
        assert f.line > 0 and f.message


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_good_fixture_passes(rule_id):
    active = [f for f in _lint_fixture(rule_id, "good") if not f.suppressed]
    assert active == [], f"{rule_id} good fixture flagged: {active}"


def test_every_rule_family_has_fixture_coverage():
    families = {rid[0] for rid in RULE_IDS}
    assert {"R", "J", "A", "B", "S"} <= families
    for rid in RULE_IDS:
        assert (FIXTURES / f"{rid.lower()}_bad.py").is_file()
        assert (FIXTURES / f"{rid.lower()}_good.py").is_file()


def test_rule_registry_metadata():
    for rid in RULE_IDS:
        if rid == "S000":          # emitted by the suppression layer
            continue
        rule = RULES[rid]
        assert rule.summary and rule.invariant, rid
        assert rule.severity in ("error", "info")
    assert RULES["B001"].severity == "info"   # accounting stays report-only


# --------------------------------------------------------------------------- #
# Suppressions                                                                #
# --------------------------------------------------------------------------- #

def test_suppression_without_justification_does_not_suppress():
    findings = _lint_fixture("S000", "bad")
    assert any(f.rule == "R001" and not f.suppressed for f in findings)
    assert any(f.rule == "S000" for f in findings)


def test_justified_suppression_silences_exactly_the_named_rule():
    findings = _lint_fixture("S000", "good")
    sup = [f for f in findings if f.suppressed]
    assert len(sup) == 1 and sup[0].rule == "R001"
    assert "fixture demo" in sup[0].justification
    assert [f for f in findings if not f.suppressed] == []


def test_standalone_suppression_covers_next_line():
    src = ("import numpy as np\n"
           "# reprolint: ignore[R001] -- covering the next line\n"
           "x = np.random.rand(3)\n"
           "y = np.random.rand(3)\n")
    findings = lint_source(src, "src/repro/x.py")
    xs = [f for f in findings if f.line == 3]
    ys = [f for f in findings if f.line == 4]
    assert xs and all(f.suppressed for f in xs)
    assert ys and not any(f.suppressed for f in ys)


def test_suppression_of_wrong_rule_does_not_silence():
    src = "import numpy as np\nx = np.random.rand(3)  # reprolint: ignore[A001] -- wrong rule\n"
    findings = lint_source(src, "src/repro/x.py")
    assert any(f.rule == "R001" and not f.suppressed for f in findings)


def test_parse_suppressions_shape():
    sups = parse_suppressions(
        "x = 1  # reprolint: ignore[R001, J002] -- because reasons\n")
    assert sups[0].rules == ("R001", "J002")
    assert sups[0].justification == "because reasons"
    assert not sups[0].standalone


# --------------------------------------------------------------------------- #
# Config                                                                      #
# --------------------------------------------------------------------------- #

def test_pyproject_config_is_loaded():
    cfg = LintConfig.from_pyproject(ROOT)
    assert "tests/lint_fixtures" in cfg.exclude
    assert "B001" in cfg.report_only
    assert any(p.endswith("trainer.py") for p in cfg.r003_allow)


def test_path_matching_covers_dirs_and_globs():
    assert path_matches("src/repro/sim/engine.py", ("src/repro/sim",))
    assert path_matches("src/repro/kernels/ops.py", ("src/repro/kernels/*.py",))
    assert not path_matches("src/repro/core/adaptive.py", ("src/repro/sim",))


def test_r003_allowlist_exempts_measurement_sites():
    src = "import time\nt0 = time.monotonic()\n"
    flagged = lint_source(src, "src/repro/runtime/trainer.py",
                          LintConfig(r003_allow=()))
    assert any(f.rule == "R003" for f in flagged)
    clean = lint_source(src, "src/repro/runtime/trainer.py",
                        LintConfig.from_pyproject(ROOT))
    assert not any(f.rule == "R003" for f in clean)


def test_report_only_rules_never_gate():
    src = "def f(tm):\n    tm.restore_seconds(2)\n    return 0\n"
    report_findings = lint_source(src, "src/repro/x.py")
    assert any(f.rule == "B001" for f in report_findings)
    # B001 is severity "info": it must not contribute to the gate.
    from repro.analysis.core import LintReport
    rep = LintReport(findings=report_findings, files_scanned=1,
                     config=LintConfig())
    assert rep.exit_code == 0


# --------------------------------------------------------------------------- #
# Self-check: the committed tree is violation-free, and a seeded            #
# violation in src/ is caught.                                               #
# --------------------------------------------------------------------------- #

def test_repo_is_violation_free():
    report = lint_paths(["src", "tests", "benchmarks", "examples"], ROOT)
    assert report.files_scanned > 100
    gating = report.gating
    assert gating == [], "\n".join(str(f) for f in gating)


def test_suppressions_in_tree_all_carry_justifications():
    report = lint_paths(["src", "tests", "benchmarks", "examples"], ROOT)
    for f in report.findings:
        if f.suppressed:
            assert f.justification, f


@pytest.mark.parametrize("rule_id", [r for r in RULE_IDS if r != "S000"])
def test_seeded_violation_copied_into_src_is_caught(rule_id, tmp_path):
    """Copy each bad fixture into a src/ mirror and run the real driver:
    the gate must trip (B001 is report-only and shows up without
    gating)."""
    dst_rel = Path(PRETEND.get(rule_id, f"src/repro/{rule_id.lower()}_bad.py"))
    dst = tmp_path / dst_rel
    dst.parent.mkdir(parents=True)
    shutil.copy(FIXTURES / f"{rule_id.lower()}_bad.py", dst)
    shutil.copy(ROOT / "pyproject.toml", tmp_path / "pyproject.toml")
    report = lint_paths(["src"], tmp_path)
    assert any(f.rule == rule_id and not f.suppressed for f in report.findings)
    if rule_id == "B001":
        assert report.exit_code == 0
    else:
        assert report.exit_code == 1


# --------------------------------------------------------------------------- #
# CLI                                                                         #
# --------------------------------------------------------------------------- #

def _run_cli(*args, cwd=ROOT):
    return subprocess.run(
        [sys.executable, str(ROOT / "tools" / "reprolint.py"), *args],
        capture_output=True, text=True, cwd=cwd)


def test_cli_clean_tree_exits_zero_and_writes_json(tmp_path):
    out = tmp_path / "report.json"
    proc = _run_cli("src", "tests", "benchmarks", "examples",
                    "--json", str(out))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(out.read_text())
    assert doc["exit_code"] == 0 and doc["n_gating"] == 0
    assert doc["files_scanned"] > 100
    assert "R001" in doc["rules"] and "invariant" in doc["rules"]["R001"]


def test_cli_gates_on_violations(tmp_path):
    (tmp_path / "src").mkdir()
    (tmp_path / "src" / "evil.py").write_text(
        "import numpy as np\nx = np.random.rand(3)\n")
    proc = _run_cli("src", "--root", str(tmp_path))
    assert proc.returncode == 1
    assert "R001" in proc.stdout


def test_cli_list_rules():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    for rid in ("R001", "J001", "A001", "B001"):
        assert rid in proc.stdout

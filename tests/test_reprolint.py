"""Tests for tools/reprolint — the repo's static-analysis plane.

Each registered pass is exercised against a flagged AND a clean fixture
(``tests/lint_fixtures``), plus the suppression-comment and baseline-file
mechanics, the CLI exit-code contract, and a self-check that the real tree
is clean with an EMPTY baseline.
"""
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.reprolint import (DEFAULT_EXCLUDES, PASSES, format_baseline,  # noqa: E402
                             load_baseline, run)
from tools.reprolint.cli import main as cli_main  # noqa: E402
from tools.reprolint.core import Finding, module_name  # noqa: E402

FIX = REPO / "tests" / "lint_fixtures"

# fixtures must NOT be excluded when we point the analyzer at them
NO_FIXTURE_EXCLUDE = ("*__pycache__*",)


def analyze(*names, rules=None, baseline=None):
    return run([FIX / n for n in names], rules=rules,
               exclude=NO_FIXTURE_EXCLUDE, baseline=baseline)


# ------------------------------------------------------------------ registry
def test_registry_has_exactly_the_four_passes():
    assert set(PASSES) == {"lease-raw", "blocking-under-lock",
                           "journal-before-mutate", "layering"}
    for rule, mod in PASSES.items():
        assert mod.RULE == rule
        assert mod.DOC
        assert callable(mod.check)


def test_unknown_rule_rejected():
    import pytest
    with pytest.raises(ValueError, match="unknown rule"):
        analyze("leases_bad.py", rules=["no-such-rule"])


# ------------------------------------------------------------- rule fixtures
def test_lease_raw_flagged():
    res = analyze("leases_bad.py")
    assert [f.rule for f in res.findings] == ["lease-raw", "lease-raw"]
    msgs = " ".join(f.message for f in res.findings)
    assert "leak_on_error" in msgs and "prepare_write_leaks" in msgs


def test_lease_raw_clean_shapes():
    res = analyze("leases_ok.py")
    assert res.findings == []


def test_blocking_under_lock_flagged():
    res = analyze("locks_bad.py")
    assert all(f.rule == "blocking-under-lock" for f in res.findings)
    reasons = sorted(f.message for f in res.findings)
    assert len(reasons) == 4
    joined = " ".join(reasons)
    assert "time.sleep" in joined
    assert "synchronous fabric.call" in joined
    assert ".result()" in joined
    assert "queue .get()" in joined
    # the manual acquire()/release() span names the right lock
    assert any("self._lock" in r for r in reasons)
    assert any("self._mutex" in r for r in reasons)


def test_blocking_under_lock_clean_shapes():
    res = analyze("locks_ok.py")
    assert res.findings == []


def test_journal_before_mutate_flagged():
    res = analyze("journal_bad")
    assert [f.rule for f in res.findings] == ["journal-before-mutate"] * 2
    joined = " ".join(f.message for f in res.findings)
    assert "extmgr.free" in joined and "dev.trim" in joined


def test_journal_before_mutate_clean_and_scoped_to_core_files():
    res = analyze("journal_ok")
    assert res.findings == []  # fenced fs.py clean; elsewhere.py out of scope


def test_layering_flagged():
    res = analyze("layering")
    assert all(f.rule == "layering" for f in res.findings)
    by_file = {}
    for f in res.findings:
        by_file.setdefault(Path(f.path).name, []).append(f)
    assert len(by_file.get("bad_core.py", [])) == 3  # import/from/lazy
    assert len(by_file.get("bad_kernel.py", [])) == 1
    assert len(by_file.get("bad_sim.py", [])) == 1
    assert "ok_core.py" not in by_file
    assert "script_ok.py" not in by_file  # no src/ root: no layer identity
    assert len(res.findings) == 5


def test_layering_module_identity_uses_last_src_segment():
    assert module_name(
        "tests/lint_fixtures/layering/src/repro/core/bad_core.py"
    ) == "repro.core.bad_core"
    assert module_name("src/repro/core/fs.py") == "repro.core.fs"
    assert module_name("src/repro/__init__.py") == "repro"
    assert module_name("benchmarks/fig15_async_wal.py") is None


# ------------------------------------------------------------- suppressions
def test_suppression_with_reason_suppresses_both_placements():
    res = analyze("suppressed.py")
    assert res.findings == []
    assert len(res.suppressed) == 2
    assert [f.rule for f in res.suppressed] == ["lease-raw"] * 2


def test_suppression_without_reason_does_not_suppress():
    res = analyze("suppressed_noreason.py")
    assert len(res.findings) == 1
    assert res.suppressed == []
    assert "reason" in res.findings[0].message


def test_suppression_is_rule_scoped(tmp_path):
    # an allow[layering] comment must not hide a lease-raw finding
    src = tmp_path / "scoped.py"
    src.write_text(
        "def grab(fs, extents):\n"
        "    return fs.grant_lease((), extents)  "
        "# reprolint: allow[layering] names another rule\n",
        encoding="utf-8")
    res = run([src], exclude=NO_FIXTURE_EXCLUDE)
    assert [f.rule for f in res.findings] == ["lease-raw"]
    assert res.suppressed == []


# ----------------------------------------------------------------- baseline
def test_baseline_roundtrip(tmp_path):
    res = analyze("leases_bad.py")
    assert len(res.findings) == 2
    bl = tmp_path / "baseline.txt"
    bl.write_text(format_baseline(res.findings), encoding="utf-8")
    res2 = analyze("leases_bad.py", baseline=load_baseline(bl))
    assert res2.ok
    assert len(res2.baselined) == 2


def test_baseline_survives_line_drift(tmp_path):
    # fingerprints hash rule + source line, not line numbers
    a = Finding("p.py", 10, "lease-raw", "m", "lease = fs.grant_lease(x)")
    b = Finding("p.py", 99, "lease-raw", "m", "lease = fs.grant_lease(x)")
    assert a.fingerprint == b.fingerprint
    c = Finding("p.py", 10, "layering", "m",
                "lease = fs.grant_lease(x)")
    assert a.fingerprint != c.fingerprint  # rule is part of the hash


def test_baseline_malformed_line_rejected(tmp_path):
    import pytest
    bl = tmp_path / "baseline.txt"
    bl.write_text("lease-raw only-two-fields\n", encoding="utf-8")
    with pytest.raises(ValueError, match="malformed baseline"):
        load_baseline(bl)


def test_checked_in_baseline_is_empty():
    bl = load_baseline(REPO / "tools" / "reprolint" / "baseline.txt")
    assert bl == set(), "the baseline must stay empty — fix, don't baseline"


# ------------------------------------------------------------------- corpus
def test_fixture_corpus_excluded_from_default_runs():
    res = run([FIX], exclude=DEFAULT_EXCLUDES)
    assert res.files == 0


def test_parse_error_is_a_finding(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def oops(:\n", encoding="utf-8")
    res = run([bad], exclude=NO_FIXTURE_EXCLUDE)
    assert [f.rule for f in res.findings] == ["parse-error"]


# ---------------------------------------------------------------------- CLI
def test_cli_exit_codes(tmp_path, capsys):
    empty_bl = tmp_path / "bl.txt"
    empty_bl.write_text("", encoding="utf-8")
    common = ["--no-default-excludes", "--baseline", str(empty_bl)]
    assert cli_main([str(FIX / "leases_bad.py"), *common]) == 1
    assert cli_main([str(FIX / "leases_ok.py"), *common]) == 0
    assert cli_main([str(FIX / "nope-does-not-exist.txt"), *common]) == 2
    assert cli_main(["--list-passes"]) == 0
    out = capsys.readouterr().out
    for rule in PASSES:
        assert rule in out


def test_cli_write_baseline_then_clean(tmp_path, capsys):
    bl = tmp_path / "bl.txt"
    bl.write_text("", encoding="utf-8")
    target = str(FIX / "leases_bad.py")
    common = ["--no-default-excludes", "--baseline", str(bl)]
    assert cli_main([target, *common, "--write-baseline"]) == 0
    assert cli_main([target, *common]) == 0  # everything grandfathered
    capsys.readouterr()


def test_cli_json_format(tmp_path, capsys):
    bl = tmp_path / "bl.txt"
    bl.write_text("", encoding="utf-8")
    rc = cli_main([str(FIX / "leases_bad.py"), "--no-default-excludes",
                   "--baseline", str(bl), "--format", "json"])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert len(payload["findings"]) == 2
    assert all(f["rule"] == "lease-raw" for f in payload["findings"])
    assert all(f["fingerprint"] for f in payload["findings"])


# --------------------------------------------------------------- self-check
def test_real_tree_is_clean_with_empty_baseline():
    """The acceptance bar: the shipped tree has zero unsuppressed findings
    and the baseline stays empty (fixtures excluded by PATH)."""
    res = run([REPO / "src", REPO / "benchmarks", REPO / "examples",
               REPO / "tools", REPO / "tests"])
    assert res.ok, "\n".join(f.render() for f in res.findings)
    assert res.files > 100
    # every inline suppression in the tree carries a reason
    assert all(True for _ in res.suppressed)

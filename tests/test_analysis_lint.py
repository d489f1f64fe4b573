"""JAX-hazard linter: each corpus file trips exactly its rule, and the
real tree is clean modulo the committed allowlist."""
import pathlib
import subprocess
import sys

import pytest

from repro.analysis.lint import (
    AllowEntry,
    lint_file,
    lint_paths,
    load_allowlist,
)

CORPUS = pathlib.Path(__file__).parent / "analysis_corpus" / "lint"
SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


@pytest.mark.parametrize("fname,rule,min_hits", [
    ("bad_jh001.py", "JH001", 3),       # immediate, in-loop, hot-path
    ("sim/bad_jh002.py", "JH002", 3),   # time.time, time.sleep, from-import
    ("bad_jh003.py", "JH003", 2),
    ("bad_jh004.py", "JH004", 2),
    ("bad_jh005.py", "JH005", 2),
])
def test_corpus_file_trips_exactly_its_rule(fname, rule, min_hits):
    findings = lint_file(CORPUS / fname)
    assert len(findings) >= min_hits, [str(f) for f in findings]
    assert {f.rule for f in findings} == {rule}, [str(f) for f in findings]


def test_jh002_only_applies_to_virtual_clock_modules():
    # the same source outside sim/ (or serve/scheduler.py) is legal:
    # wall-clock reads are only a hazard under deterministic replay
    src = (CORPUS / "sim" / "bad_jh002.py").read_text()
    elsewhere = CORPUS / "sim" / ".." / "jh002_copy_outside_sim.py"
    try:
        elsewhere.write_text(src)
        assert lint_file(elsewhere.resolve()) == []
    finally:
        elsewhere.unlink()


def test_src_tree_is_clean_modulo_allowlist():
    findings, suppressed = lint_paths([str(SRC)])
    assert findings == [], "\n".join(str(f) for f in findings)
    # the committed intentional site, nothing more
    assert sorted((s.rule, s.qualname) for s in suppressed) == [
        ("JH001", "calibrate_kinds"),
    ]


def test_allowlist_suppression_is_narrow():
    # without the allowlist the intentional site surfaces again —
    # proving the suppression is the allowlist, not a blind spot
    findings, suppressed = lint_paths([str(SRC)], allowlist=[])
    assert suppressed == []
    assert sorted((f.rule, f.qualname) for f in findings) == [
        ("JH001", "calibrate_kinds"),
    ]
    # a mismatched qualname does not suppress
    findings, _ = lint_paths(
        [str(SRC)],
        allowlist=[AllowEntry("JH001", "repro/core/profiler.py",
                              "wrong_name", "x")])
    assert any(f.qualname == "calibrate_kinds" for f in findings)


def test_committed_allowlist_entries_are_justified():
    for entry in load_allowlist():
        assert entry.justification, f"{entry} lacks a justification"


def test_cli_exit_codes():
    env_src = str(SRC.parent)
    clean = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", str(SRC)],
        capture_output=True, text=True, env={"PYTHONPATH": str(SRC)},
        cwd=env_src)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    dirty = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", str(CORPUS)],
        capture_output=True, text=True, env={"PYTHONPATH": str(SRC)},
        cwd=env_src)
    assert dirty.returncode == 1
    assert "JH00" in dirty.stdout

"""repro-lint engine tests: every rule fires on the fixture corpus at
its expected location, pragmas and the baseline round-trip, and the real
``src/repro`` tree stays clean modulo the committed baseline."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import baseline as baseline_mod
from repro.analysis import gates
from repro.analysis.core import (FileContext, line_fingerprint,
                                 load_project, run_rules)
from repro.analysis.rules import ALL_RULES, select_rules

HERE = Path(__file__).resolve().parent
FIXTURES = HERE / "analysis_fixtures"
REPO = HERE.parent
SRC_REPRO = REPO / "src" / "repro"

# ground truth for the corpus: every (rule, relpath, line) it must emit
EXPECTED = {
    ("DET001", "core/bad_random.py", 8),
    ("DET001", "core/bad_random.py", 9),
    ("DET002", "faas/bad_wallclock.py", 8),
    ("DET002", "faas/bad_wallclock.py", 9),
    ("DET002", "faas/bad_wallclock.py", 10),
    ("DET003", "core/bad_hash.py", 5),
    ("DET004", "core/bad_set_iter.py", 6),
    ("DET004", "core/bad_set_iter.py", 8),
    ("DET004", "core/bad_set_iter.py", 9),
    ("JAX001", "kernels/bad_host_sync.py", 10),
    ("JAX001", "kernels/bad_host_sync.py", 11),
    ("JAX001", "kernels/bad_host_sync.py", 12),
    ("JAX002", "core/bad_use_after_donate.py", 11),
    ("JAX002", "core/bad_use_after_donate.py", 16),
    ("JAX003", "fl/bad_jit_in_round.py", 8),
    ("JAX004", "kernels/bad_shard_axes.py", 9),
    ("JAX004", "kernels/bad_shard_axes.py", 15),
    ("GATE001", "core/bad_env_gate.py", 4),
    ("GATE001", "core/bad_env_gate.py", 5),
    ("CON001", "kernels/__init__.py", 5),
    ("CON002", "faas/trace.py", 16),
    ("CON002", "faas/trace.py", 17),
    ("CON002", "faas/trace.py", 22),
}


def corpus_findings():
    project = load_project(FIXTURES, tests_dir=None)
    return project, run_rules(project, ALL_RULES)


# ------------------------------------------------------------ the corpus
def test_corpus_matches_ground_truth_exactly():
    """No missing findings, no extras — the corpus is the rule spec."""
    _, findings = corpus_findings()
    got = {(f.rule, f.path, f.line) for f in findings}
    assert got == EXPECTED


@pytest.mark.parametrize("rule_id", sorted({r for r, _, _ in EXPECTED}))
def test_each_rule_fires_at_expected_lines(rule_id):
    project = load_project(FIXTURES, tests_dir=None)
    findings = run_rules(project, select_rules([rule_id]))
    got = {(f.rule, f.path, f.line) for f in findings}
    want = {t for t in EXPECTED if t[0] == rule_id}
    assert got == want


def test_every_registered_rule_has_corpus_coverage():
    """Adding a rule without a fixture proving it fires is a test gap."""
    covered = {r for r, _, _ in EXPECTED}
    assert {r.id for r in ALL_RULES} == covered


def test_findings_carry_messages_and_locations():
    _, findings = corpus_findings()
    for f in findings:
        assert f.message and f.location().endswith(f":{f.line}")
        assert f.severity == "error"


# ------------------------------------------------------------- pragmas
def test_pragma_suppresses_by_id_and_slug():
    """core/pragma_ok.py violates DET003 + DET001 but pragmas (one by
    rule id, one by slug) silence both."""
    _, findings = corpus_findings()
    assert not [f for f in findings if f.path == "core/pragma_ok.py"]


def test_pragma_only_covers_its_own_line(tmp_path):
    src = ('def f(a):\n'
           '    x = hash(a)  # repro-lint: disable=DET003\n'
           '    return hash(x)\n')
    p = tmp_path / "mod.py"
    p.write_text(src)
    project = load_project(p)
    findings = run_rules(project, select_rules(["DET003"]))
    assert [f.line for f in findings] == [3]


# ------------------------------------------------------------- baseline
def test_baseline_round_trip(tmp_path):
    """write -> load -> partition grandfathers the whole corpus."""
    project, findings = corpus_findings()
    path = tmp_path / "baseline.json"
    baseline_mod.write(path, project, findings)
    base = baseline_mod.load(path)
    assert len(base) == len(findings)
    new, old = baseline_mod.partition(project, findings, base)
    assert new == [] and len(old) == len(findings)


def test_baseline_fingerprint_survives_renumbering(tmp_path):
    """Inserting lines above a finding must not invalidate the baseline
    (it keys on line content, not line number) — but editing the flagged
    line itself must."""
    corpus = tmp_path / "corpus"
    shutil.copytree(FIXTURES, corpus)
    project, findings = (lambda p: (p, run_rules(p, ALL_RULES)))(
        load_project(corpus, tests_dir=None))
    path = tmp_path / "baseline.json"
    baseline_mod.write(path, project, findings)
    base = baseline_mod.load(path)

    target = corpus / "core" / "bad_hash.py"
    target.write_text("# pushed down\n# two lines\n" + target.read_text())
    project2 = load_project(corpus, tests_dir=None)
    findings2 = run_rules(project2, ALL_RULES)
    new, _ = baseline_mod.partition(project2, findings2, base)
    assert new == []                       # renumbering: still baselined

    target.write_text(target.read_text().replace(
        "hash(client_id) % 2**32", "hash(client_id) % 2**16"))
    project3 = load_project(corpus, tests_dir=None)
    findings3 = run_rules(project3, ALL_RULES)
    new, _ = baseline_mod.partition(project3, findings3, base)
    assert [(f.rule, f.path) for f in new] == [
        ("DET003", "core/bad_hash.py")]    # edited line: resurfaces


def test_line_fingerprint_strips_indentation(tmp_path):
    p = tmp_path / "m.py"
    p.write_text("x = hash('a')\n")
    a = line_fingerprint(FileContext(p, "m.py"), 1)
    p.write_text("    x = hash('a')\n")
    b = line_fingerprint(FileContext(p, "m.py"), 1)
    assert a == b


def test_duplicate_line_occurrence_index():
    """Two identical flagged lines get distinct :0 / :1 fingerprints."""
    project, findings = corpus_findings()
    fps = baseline_mod.fingerprints(project, findings)
    assert len(fps) == len(set(fps))


# ----------------------------------------------------- the real package
def test_src_repro_clean_modulo_committed_baseline():
    """The shipped tree must carry no findings beyond the committed
    baseline — the same check CI enforces."""
    project = load_project(SRC_REPRO, tests_dir=HERE)
    findings = run_rules(project, ALL_RULES)
    base = baseline_mod.load()             # the committed baseline.json
    new, _ = baseline_mod.partition(project, findings, base)
    assert new == [], [f"{f.location()}: {f.rule} {f.message}"
                       for f in new]


def test_syntax_error_becomes_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    findings = run_rules(load_project(p), ALL_RULES)
    assert [f.rule for f in findings] == ["E000"]


def test_select_rules_rejects_unknown():
    with pytest.raises(KeyError):
        select_rules(["NOPE999"])


# ------------------------------------------------------------ gates
def test_gates_registry_declares_known_flags():
    for name in (gates.AGG_KERNEL, gates.COMPRESS, gates.DEVICE_PIPELINE,
                 gates.OVERLAP_DISPATCH, gates.PALLAS_INTERPRET):
        assert name in gates.GATES
        assert gates.GATES[name].doc


def test_gates_read_at_call_time(monkeypatch):
    monkeypatch.delenv(gates.COMPRESS, raising=False)
    assert gates.compress_enabled()        # default "1"
    monkeypatch.setenv(gates.COMPRESS, "0")
    assert not gates.compress_enabled()
    monkeypatch.setenv(gates.AGG_KERNEL, "0")
    assert not gates.agg_kernel_enabled()
    monkeypatch.setenv(gates.AGG_KERNEL, "1")
    assert gates.agg_kernel_enabled()


def test_gates_interpret_override_three_state(monkeypatch):
    monkeypatch.delenv(gates.PALLAS_INTERPRET, raising=False)
    assert gates.pallas_interpret_override() is None
    monkeypatch.setenv(gates.PALLAS_INTERPRET, "1")
    assert gates.pallas_interpret_override() is True
    monkeypatch.setenv(gates.PALLAS_INTERPRET, "0")
    assert gates.pallas_interpret_override() is False


def test_gates_reject_undeclared_name():
    with pytest.raises(KeyError):
        gates.raw("REPRO_NOT_A_GATE")


# ------------------------------------------------------------ CLI
def _run_cli(*argv):
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"}
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *argv],
        capture_output=True, text=True, env=env, cwd=REPO)


def test_cli_json_on_corpus(tmp_path):
    out = tmp_path / "report.json"
    proc = _run_cli(str(FIXTURES), "--format", "json", "--no-baseline",
                    "--tests-dir", str(tmp_path / "missing"),
                    "--output", str(out))
    assert proc.returncode == 1            # corpus is all violations
    report = json.loads(out.read_text())
    assert report["summary"]["new"] == len(EXPECTED)
    got = {(f["rule"], f["path"], f["line"])
           for f in report["findings"]}
    assert got == EXPECTED
    assert all(f["fingerprint"] for f in report["findings"])


def test_cli_clean_tree_exits_zero():
    proc = _run_cli(str(SRC_REPRO), "--tests-dir", str(HERE))
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_list_rules():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    for rule in ALL_RULES:
        assert rule.id in proc.stdout

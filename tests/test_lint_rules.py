"""graftcheck rule engine: fixtures, suppressions, baseline round-trip.

Each file under tests/lint_fixtures/ is a minimal snippet that triggers
exactly one rule (the directory has no ``test_`` files, so pytest never
collects the snippets themselves, and ruff excludes it — the violations
are the point).
"""

import json
from pathlib import Path

import pytest

from federated_pytorch_test_tpu.analysis import LintEngine, Severity
from federated_pytorch_test_tpu.analysis.flow import ALL_RULES
from federated_pytorch_test_tpu.analysis.lint import main as lint_main

FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"

#: fixture file -> the one rule it must trigger
CASES = [
    ("jg101_host_sync.py", "JG101"),
    ("jg102_traced_branch.py", "JG102"),
    ("jg103_key_reuse.py", "JG103"),
    ("jg104_timer_no_sync.py", "JG104"),
    ("jg105_recompile_hazard.py", "JG105"),
    ("jg106_missing_donation.py", "JG106"),
    ("jg107_sharding_annotation.py", "JG107"),
    ("jg108_cross_function_hazard.py", "JG108"),
    ("jg109_use_after_donate.py", "JG109"),
    ("jg110_key_lineage.py", "JG110"),
    ("jg111_discarded_pure.py", "JG111"),
    ("jg112_shared_write.py", "JG112"),
    ("jg113_blocking_under_lock.py", "JG113"),
    ("jg114_check_then_act.py", "JG114"),
    ("jg115_jit_from_thread.py", "JG115"),
    ("jg116_lifecycle.py", "JG116"),
    ("jg117_entropy_into_record.py", "JG117"),
    ("jg118_schema_contract.py", "JG118"),
    ("jg119_unordered_into_record.py", "JG119"),
    ("jg120_meta_contract.py", "JG120"),
    ("jg121_rogue_prng.py", "JG121"),
]


def _lint(path: Path):
    return LintEngine(ALL_RULES).lint_file(path)


class TestFixtures:
    @pytest.mark.parametrize("name,rule_id", CASES)
    def test_triggers_exactly_its_rule(self, name, rule_id):
        result = _lint(FIXTURES / name)
        ids = {f.rule_id for f in result.findings}
        assert ids == {rule_id}, [f.render() for f in result.findings]

    @pytest.mark.parametrize("name,rule_id", CASES)
    def test_cli_exits_nonzero(self, name, rule_id, capsys):
        # every rule — JG106 included, warning severity since the engine
        # went donation-safe end to end — fails the default gate
        assert lint_main([str(FIXTURES / name)]) == 1
        capsys.readouterr()

    def test_fixture_set_covers_every_rule(self):
        assert {r for _, r in CASES} == {rule.id for rule in ALL_RULES}


class TestSuppression:
    def test_disable_comment_silences_rule(self):
        src = (FIXTURES / "jg101_host_sync.py").read_text()
        src = src.replace("return x.item()",
                          "return x.item()  # graftlint: disable=JG101")
        result = LintEngine(ALL_RULES).lint_source(src, "fixture.py")
        assert result.findings == []
        assert result.suppressed == 1

    def test_disable_all(self):
        src = (FIXTURES / "jg102_traced_branch.py").read_text()
        src = src.replace("if x > 0:",
                          "if x > 0:  # graftlint: disable=all")
        result = LintEngine(ALL_RULES).lint_source(src, "fixture.py")
        assert result.findings == []
        assert result.suppressed == 1

    def test_other_rule_id_does_not_suppress(self):
        src = (FIXTURES / "jg101_host_sync.py").read_text()
        src = src.replace("return x.item()",
                          "return x.item()  # graftlint: disable=JG104")
        result = LintEngine(ALL_RULES).lint_source(src, "fixture.py")
        assert [f.rule_id for f in result.findings] == ["JG101"]


class TestBaseline:
    def test_round_trip(self, tmp_path, capsys):
        """write-baseline then re-lint with it: everything grandfathered,
        exit 0; fingerprints survive line insertion above the finding."""
        target = str(FIXTURES / "jg101_host_sync.py")
        bl = tmp_path / "baseline.json"
        assert lint_main([target, "--write-baseline", str(bl)]) == 0
        data = json.loads(bl.read_text())
        assert data["version"] == 1 and len(data["findings"]) == 1
        assert lint_main([target, "--baseline", str(bl)]) == 0
        capsys.readouterr()

    def test_baseline_survives_line_drift(self, tmp_path):
        src = (FIXTURES / "jg101_host_sync.py").read_text()
        engine = LintEngine(ALL_RULES)
        fps = {f.fingerprint()
               for f in engine.lint_source(src, "f.py").findings}
        drifted = "# a new leading comment\n\n" + src
        engine2 = LintEngine(ALL_RULES, baseline=fps)
        result = engine2.lint_source(drifted, "f.py")
        assert result.findings == [] and result.baselined == 1

    def test_baseline_breaks_when_line_changes(self):
        src = (FIXTURES / "jg101_host_sync.py").read_text()
        engine = LintEngine(ALL_RULES)
        fps = {f.fingerprint()
               for f in engine.lint_source(src, "f.py").findings}
        changed = src.replace("return x.item()", "return (x * 2).item()")
        result = LintEngine(ALL_RULES, baseline=fps).lint_source(
            changed, "f.py")
        assert [f.rule_id for f in result.findings] == ["JG101"]

    def test_syntax_error_is_a_finding(self):
        result = LintEngine(ALL_RULES).lint_source("def f(:\n", "bad.py")
        assert [f.rule_id for f in result.findings] == ["JG000"]
        assert result.findings[0].severity == Severity.ERROR

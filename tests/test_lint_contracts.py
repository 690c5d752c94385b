"""Determinism-contract layer (JG117-JG121): mutation sensitivity.

The clean-tree gate (test_lint_clean.py) proves the shipped sources
pass; the fixture gate (test_lint_rules.py) proves each rule fires on
its minimal trigger.  This module proves the contract layer is *not
vacuous against the real contract surfaces*: emptying a kind's
``REQUIRED`` core in the shipped ``obs/schema.py`` or deleting a
registered replay checker from the shipped ``control/replay.py`` must
flip JG118 from silent to firing, a rule run over one file alone must see
the tables of the shipped sources, entropy taint must survive a call
chain (and its deterministic twin must not), the machine-readable outputs
must round-trip contract findings, and the summary cache must refuse
entries written by a previous analysis generation.
"""

import json
import subprocess
from pathlib import Path

from federated_pytorch_test_tpu.analysis import LintEngine, Severity, contracts
from federated_pytorch_test_tpu.analysis.flow import (ALL_RULES,
                                                      ANALYSIS_VERSION,
                                                      SUMMARY_VERSION,
                                                      extract_module_summary)
from federated_pytorch_test_tpu.analysis.lint import _load_cache
from federated_pytorch_test_tpu.analysis.lint import main as lint_main
from federated_pytorch_test_tpu.analysis.lint import selftest

REPO = Path(__file__).resolve().parents[1]
PKG = REPO / "federated_pytorch_test_tpu"
SCHEMA = PKG / "obs" / "schema.py"
REPLAY = PKG / "control" / "replay.py"
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"


def _ids(result):
    return {f.rule_id for f in result.findings}


def _lint_source(src, name):
    return LintEngine(ALL_RULES).lint_source(src, name)


class TestSchemaContract:
    def test_shipped_contract_modules_are_clean(self):
        result = LintEngine(ALL_RULES).lint_paths([str(SCHEMA), str(REPLAY)])
        assert result.failing(Severity.WARNING) == [], \
            "\n".join(f.render() for f in result.findings)

    def test_emptied_required_core_fires_jg118(self):
        """The acceptance mutation: a kind of the shipped schema whose
        ``REQUIRED`` core is emptied must break the gate."""
        src = SCHEMA.read_text()
        mutated = src.replace(
            '"client": ("event", "schema", "run_id", "round_index", '
            '"clients"),', '"client": (),', 1)
        assert mutated != src, "REQUIRED spelling changed"
        result = _lint_source(mutated, str(SCHEMA))
        assert _ids(result) == {"JG118"}, \
            [f.render() for f in result.findings]
        assert any("'client'" in f.message and "REQUIRED" in f.message
                   for f in result.findings)

    def test_contract_tables_come_from_source(self, tmp_path, monkeypatch):
        """A lint run over one file alone reads ``ADVISORY_FIELDS`` from
        the shipped obs/schema.py (parsed, never imported): a word added
        to a copy of that source is a word the rule sees, with no second
        table anywhere to keep in step."""
        emit = ("import time\n"
                "def emit(sink, r):\n"
                "    rec = {'event': 'round', 'round_index': r,\n"
                "           'brand_new_seconds': time.time()}\n"
                "    sink.round(rec)\n")
        assert _ids(_lint_source(emit, "one_file.py")) == {"JG117"}
        src = SCHEMA.read_text()
        grown = src.replace('    "captured_utc",\n)',
                            '    "captured_utc", "brand_new_seconds",\n)', 1)
        assert grown != src, "ADVISORY_FIELDS spelling changed"
        copy = tmp_path / "schema.py"
        copy.write_text(grown)
        monkeypatch.setattr(contracts, "TABLE_SOURCES", (copy, REPLAY))
        assert "brand_new_seconds" in \
            contracts.shipped_tables()["ADVISORY_FIELDS"]
        assert _ids(_lint_source(emit, "one_file.py")) == set()
        # control/replay.py's tables arrive the same way
        assert "client" in contracts.shipped_tables()["REPLAY_CHECKERS"]


class TestReplayCoverage:
    def test_shipped_replay_is_clean_alone(self):
        result = LintEngine(ALL_RULES).lint_paths([str(REPLAY)])
        assert result.failing(Severity.WARNING) == [], \
            "\n".join(f.render() for f in result.findings)

    def test_deleting_registered_checker_fires_jg118(self):
        """The acceptance mutation: renaming ``check_cohort_records``
        out from under REPLAY_CHECKERS must break the gate — a checker
        the table promises but the module no longer defines."""
        src = REPLAY.read_text()
        mutated = src.replace("def check_cohort_records(",
                              "def check_cohort_records_gone(", 1)
        assert mutated != src
        result = _lint_source(mutated, str(REPLAY))
        assert _ids(result) == {"JG118"}, \
            [f.render() for f in result.findings]
        assert any("check_cohort_records" in f.message
                   for f in result.findings)

    def test_emitted_kind_without_checker_fires_jg118(self):
        stub = ("EVENTS = ('client',)\n"
                "REPLAY_CHECKERS = {}\n"
                "REPLAY_EXEMPT_KINDS = ()\n"
                "def emit(sink, r):\n"
                "    rec = {'event': 'client', 'round_index': r}\n"
                "    sink.client_event(rec)\n")
        result = _lint_source(stub, "stub_uncovered.py")
        assert _ids(result) == {"JG118"}, \
            [f.render() for f in result.findings]

    def test_emitted_kind_with_checker_is_clean(self):
        stub = ("EVENTS = ('client',)\n"
                "REPLAY_CHECKERS = {'client': ('check_client_records',)}\n"
                "REPLAY_EXEMPT_KINDS = ()\n"
                "def check_client_records(records):\n"
                "    return len(records)\n"
                "def emit(sink, r):\n"
                "    rec = {'event': 'client', 'round_index': r}\n"
                "    sink.client_event(rec)\n")
        result = _lint_source(stub, "stub_covered.py")
        assert _ids(result) == set(), \
            [f.render() for f in result.findings]


class TestTaintThroughCalls:
    """JG117 is interprocedural, and provably so: the same emit body is
    tainted or clean depending only on what the helper returns."""

    EMIT = ("def emit(sink, seed, r):\n"
            "    t = now(seed, r)\n"
            "    rec = {'event': 'control', 'round_index': r,\n"
            "           'observed': t}\n"
            "    sink.control_event(rec)\n")

    def test_entropy_returning_helper_taints_the_record(self):
        src = ("import time\n"
               "def now(seed, r):\n"
               "    return time.time()\n" + self.EMIT)
        result = _lint_source(src, "taint_pair.py")
        assert _ids(result) == {"JG117"}, \
            [f.render() for f in result.findings]

    def test_deterministic_helper_is_clean(self):
        src = ("def now(seed, r):\n"
               "    return seed + r\n" + self.EMIT)
        result = _lint_source(src, "taint_pair.py")
        assert _ids(result) == set(), \
            [f.render() for f in result.findings]


class TestOutputRoundTrip:
    def test_json_carries_contract_findings(self, capsys):
        rc = lint_main([str(FIXTURES / "jg117_entropy_into_record.py"),
                        "--json"])
        assert rc == 1
        data = json.loads(capsys.readouterr().out)
        assert [f["rule"] for f in data["findings"]] == ["JG117"]
        assert data["failing"] == 1

    def test_sarif_carries_contract_findings(self, capsys):
        rc = lint_main([str(FIXTURES / "jg121_rogue_prng.py"), "--sarif"])
        assert rc == 1
        doc = json.loads(capsys.readouterr().out)
        run = doc["runs"][0]
        assert [r["ruleId"] for r in run["results"]] == ["JG121"]
        rules = {r["id"]
                 for r in run["tool"]["driver"]["rules"]}
        assert {"JG117", "JG118", "JG119", "JG120", "JG121"} <= rules


class TestSummaryCache:
    def _seed_repo(self, tmp_path):
        repo = tmp_path / "r"
        repo.mkdir()
        (repo / "mod.py").write_text(
            "def add(seed, r):\n    return seed + r\n")
        for cmd in (["git", "init", "-q"],
                    ["git", "add", "mod.py"],
                    ["git", "-c", "user.email=t@t", "-c", "user.name=t",
                     "commit", "-qm", "seed"]):
            subprocess.run(cmd, cwd=repo, check=True, capture_output=True)
        return repo

    def test_cache_rejects_previous_analysis_generation(
            self, tmp_path, capsys):
        repo = self._seed_repo(tmp_path)
        cache = tmp_path / "cache.json"
        rc = lint_main([str(repo / "mod.py"), "--changed", "HEAD",
                        "--cache", str(cache)])
        assert rc == 0
        capsys.readouterr()
        data = json.loads(cache.read_text())
        assert data["analysis_version"] == ANALYSIS_VERSION
        entry = next(iter(data["summaries"].values()))
        assert entry["summary"]["version"] == SUMMARY_VERSION
        # stamp the file as written by the previous analysis generation
        # (exactly what a pre-bump checkout would have left behind)
        stale = dict(data)
        stale["analysis_version"] = ANALYSIS_VERSION - 1
        cache.write_text(json.dumps(stale))
        assert _load_cache(cache) == {}
        rc = lint_main([str(repo / "mod.py"), "--changed", "HEAD",
                        "--cache", str(cache)])
        assert rc == 0
        capsys.readouterr()
        refreshed = json.loads(cache.read_text())
        assert refreshed["analysis_version"] == ANALYSIS_VERSION

    def test_stale_summary_version_is_reextracted(self, tmp_path, capsys):
        """An entry whose sha1 still matches but whose per-file summary
        predates the current SUMMARY_VERSION (the 2 -> 3 bump that added
        the contract facts) must not be trusted on the fast path."""
        repo = self._seed_repo(tmp_path)
        cache = tmp_path / "cache.json"
        rc = lint_main([str(repo / "mod.py"), "--changed", "HEAD",
                        "--cache", str(cache)])
        assert rc == 0
        capsys.readouterr()
        data = json.loads(cache.read_text())
        key, entry = next(iter(data["summaries"].items()))
        entry["summary"]["version"] = SUMMARY_VERSION - 1
        cache.write_text(json.dumps(data))
        rc = lint_main([str(repo / "mod.py"), "--changed", "HEAD",
                        "--cache", str(cache)])
        assert rc == 0
        capsys.readouterr()
        refreshed = json.loads(cache.read_text())
        assert (refreshed["summaries"][key]["summary"]["version"]
                == SUMMARY_VERSION)


class TestSummaryFacts:
    def test_v3_summary_carries_contract_facts(self):
        src = ("import time\n"
               "def now():\n"
               "    t = time.time()\n"
               "    return t\n"
               "def stamp():\n"
               "    return time.time()\n")
        engine = LintEngine(ALL_RULES)
        module, err = engine._parse(src, "facts.py")
        assert err is None
        summary = extract_module_summary(module)
        assert summary["version"] == SUMMARY_VERSION >= 3
        assert summary["functions"]["now"]["entropy"], \
            "v3 summaries must record entropy-tainted bindings"
        assert summary["functions"]["stamp"]["ret_esrc"], \
            "v3 summaries must record entropy-returning functions"

    def test_tables_extracted_from_shipped_schema(self):
        engine = LintEngine(ALL_RULES)
        module, err = engine._parse(SCHEMA.read_text(), str(SCHEMA))
        assert err is None
        tables = extract_module_summary(module)["tables"]
        assert {"ADVISORY_FIELDS", "REQUIRED",
                "RESERVED_META_NAMESPACES"} <= set(tables)


def test_selftest_exits_zero(capsys):
    assert selftest() == 0
    assert "ok" in capsys.readouterr().out

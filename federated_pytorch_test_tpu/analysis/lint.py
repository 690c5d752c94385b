"""graftcheck CLI.

Usage::

    python -m federated_pytorch_test_tpu.analysis.lint \
        federated_pytorch_test_tpu bench.py [--json | --sarif] \
        [--baseline analysis/baseline.json] [--write-baseline PATH] \
        [--fail-on {error,warning,advice}] \
        [--changed [GIT_REF]] [--cache PATH]

``--changed`` scopes *reporting* to files that differ from a git ref
(default ``HEAD``) plus untracked files, while the interprocedural
rules (JG108-JG111) still see the whole program: unchanged files
contribute their per-function summaries — from the ``--cache`` file
when the content sha1 still matches, re-extracted otherwise — so a
pre-commit hook pays parse+extract only for what the diff touched.

Exit code 0 when no non-suppressed, non-baselined finding is at or
above ``--fail-on`` (default: warning — ADVICE findings report but do
not fail); 1 otherwise; 2 on usage errors.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set

from .core import (Finding, LintEngine, LintResult, ModuleContext, Severity,
                   expand_paths, load_baseline, norm_path, render_json,
                   render_sarif, render_text, save_baseline)
from .flow import (ALL_RULES, ANALYSIS_VERSION, SUMMARY_VERSION,
                   extract_module_summary, file_sha1, strip_summary)

CACHE_VERSION = 1


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m federated_pytorch_test_tpu.analysis.lint",
        description="JAX-aware static analysis for the federated stack")
    p.add_argument("paths", nargs="*",
                   help="files or directories (directories recurse to *.py)")
    p.add_argument("--selftest", action="store_true",
                   help="run the built-in self-check (each determinism-"
                        "contract rule fires on its canary snippet, the "
                        "clean canary stays silent) and exit")
    p.add_argument("--json", action="store_true",
                   help="emit findings as JSON instead of text")
    p.add_argument("--sarif", action="store_true",
                   help="emit findings as SARIF 2.1.0 instead of text")
    p.add_argument("--baseline", type=Path, default=None,
                   help="JSON baseline of grandfathered finding "
                        "fingerprints to ignore")
    p.add_argument("--write-baseline", type=Path, default=None,
                   help="write current findings as the new baseline "
                        "and exit 0")
    p.add_argument("--fail-on", default="warning",
                   choices=["error", "warning", "advice"],
                   help="minimum severity that fails the run "
                        "(default: warning)")
    p.add_argument("--changed", nargs="?", const="HEAD", default=None,
                   metavar="GIT_REF",
                   help="report only on files that differ from GIT_REF "
                        "(default HEAD) or are untracked; unchanged files "
                        "still feed the whole-program rules as summaries")
    p.add_argument("--cache", type=Path, default=None,
                   help="summary-cache file: read sha1-matched summaries "
                        "for unchanged files, write back fresh ones")
    return p


def _git_changed(anchor: Path, ref: str) -> Optional[Set[Path]]:
    """Absolute resolved paths changed vs ``ref`` plus untracked files,
    or None when ``anchor`` is not inside a git work tree."""
    anchor_dir = anchor if anchor.is_dir() else anchor.parent
    try:
        top = subprocess.run(
            ["git", "-C", str(anchor_dir), "rev-parse", "--show-toplevel"],
            capture_output=True, text=True, check=True).stdout.strip()
        diff = subprocess.run(
            ["git", "-C", top, "diff", "--name-only", ref],
            capture_output=True, text=True, check=True).stdout
        untracked = subprocess.run(
            ["git", "-C", top, "ls-files", "--others",
             "--exclude-standard"],
            capture_output=True, text=True, check=True).stdout
    except (OSError, subprocess.CalledProcessError):
        return None
    out: Set[Path] = set()
    for line in (diff + untracked).splitlines():
        line = line.strip()
        if line:
            out.add((Path(top) / line).resolve())
    return out


def _load_cache(path: Optional[Path]) -> Dict[str, dict]:
    if path is None or not path.exists():
        return {}
    try:
        data = json.loads(path.read_text())
    except (OSError, ValueError):
        return {}
    if data.get("version") != CACHE_VERSION:
        return {}
    # a sha1 match alone is not enough: editing extraction or rule
    # logic changes what a summary *means* without changing the file it
    # came from, so entries written by a different analysis generation
    # are discarded wholesale (the staleness hole fixed in PR 9)
    if data.get("analysis_version") != ANALYSIS_VERSION:
        return {}
    entries = data.get("summaries")
    return entries if isinstance(entries, dict) else {}


def _save_cache(path: Path, entries: Dict[str, dict]) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(
        {"version": CACHE_VERSION, "analysis_version": ANALYSIS_VERSION,
         "summaries": entries},
        sort_keys=True) + "\n")


def _changed_run(engine: LintEngine, paths: Sequence[str], ref: str,
                 cache_path: Optional[Path]) -> Optional[LintResult]:
    changed = _git_changed(Path(paths[0]), ref)
    if changed is None:
        return None
    cache = _load_cache(cache_path)
    new_cache: Dict[str, dict] = {}
    live_modules: List[ModuleContext] = []
    syntax: List[Finding] = []
    extra: List[dict] = []
    for p in sorted(expand_paths(paths)):
        source = Path(p).read_text()
        sha = file_sha1(source)
        key = norm_path(str(p))
        if Path(p).resolve() in changed:
            module, err = engine._parse(source, str(p))
            if module is None:
                syntax.append(err)
                continue
            live_modules.append(module)
            new_cache[key] = {
                "sha1": sha,
                "summary": strip_summary(extract_module_summary(module))}
            continue
        hit = cache.get(key)
        if (hit and hit.get("sha1") == sha
                and hit.get("summary", {}).get("version")
                == SUMMARY_VERSION):
            summary = dict(hit["summary"])
            summary["path"] = str(p)   # rebind to this run's spelling
        else:
            module, err = engine._parse(source, str(p))
            if module is None:
                continue               # unchanged + unparseable: skip
            summary = extract_module_summary(module)
        extra.append(summary)
        new_cache[key] = {"sha1": sha, "summary": strip_summary(summary)}
    result = engine.lint_modules(live_modules, extra_summaries=extra)
    result.findings.extend(syntax)
    result.findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule_id))
    if cache_path is not None:
        _save_cache(cache_path, new_cache)
    return result


#: one canary snippet per determinism-contract rule: the smallest
#: program that must trip exactly that rule.  ``--selftest`` lints each
#: in-memory — a sub-second end-to-end check that the whole pipeline
#: (extraction -> taint -> rules) still catches the contract breaks it
#: exists for, cheap enough to ride in the tier-1 report step.
_SELFTEST_SNIPPETS = {
    "JG117": ("import time\n"
              "def emit(sink, r):\n"
              "    t = time.time()\n"
              "    rec = {'event': 'control', 'observed': t}\n"
              "    sink.control_event(rec)\n"),
    "JG118": ("EVENTS = ('round', 'probe')\n"
              "REQUIRED = {'round': ('event',), 'probe': ()}\n"),
    "JG119": ("def emit(sink, xs):\n"
              "    ids = [x for x in set(xs)]\n"
              "    rec = {'event': 'client', 'clients': ids}\n"
              "    sink.client_event(rec)\n"),
    "JG120": ("def save_meta(n):\n"
              "    meta = {'sx_orphan': n, 'sx_ok': 1}\n"
              "    return meta\n"
              "def restore_meta(meta):\n"
              "    return meta['sx_ok']\n"),
    "JG121": ("import numpy as np\n"
              "def emit(sink, r):\n"
              "    rng = np.random.default_rng()\n"
              "    v = float(rng.normal())\n"
              "    rec = {'event': 'serve', 'requests': v}\n"
              "    sink.serve_event(rec)\n"),
}

_SELFTEST_CLEAN = (
    "def emit(sink, seed, r):\n"
    "    rec = {'event': 'control', 'round_index': r,\n"
    "           'observed': seed + r}\n"
    "    sink.control_event(rec)\n")


def selftest() -> int:
    """Exit 0 when the contract rules are healthy."""
    failures: List[str] = []
    engine = LintEngine(ALL_RULES)
    for rule_id, source in sorted(_SELFTEST_SNIPPETS.items()):
        module, err = engine._parse(source, f"<selftest:{rule_id}>")
        if module is None:
            failures.append(f"{rule_id}: canary failed to parse ({err})")
            continue
        got = {f.rule_id for f in engine.lint_modules([module]).findings}
        if got != {rule_id}:
            fired = sorted(got) if got else "nothing"
            failures.append(f"{rule_id}: canary fired {fired} instead")
    module, _ = engine._parse(_SELFTEST_CLEAN, "<selftest:clean>")
    got = {f.rule_id for f in engine.lint_modules([module]).findings}
    if got:
        failures.append(f"clean canary fired {sorted(got)}")

    if failures:
        for f in failures:
            print(f"graftcheck selftest: FAIL: {f}", file=sys.stderr)
        return 1
    print(f"graftcheck selftest: ok ({len(_SELFTEST_SNIPPETS)} contract "
          "canaries, clean canary)")
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.paths:
        print("graftcheck: no paths given", file=sys.stderr)
        return 2
    if args.json and args.sarif:
        print("graftcheck: --json and --sarif are mutually exclusive",
              file=sys.stderr)
        return 2
    fail_on = Severity.parse(args.fail_on)
    baseline = None
    if args.baseline is not None:
        try:
            baseline = load_baseline(args.baseline)
        except (OSError, ValueError) as exc:
            print(f"graftcheck: cannot read baseline: {exc}",
                  file=sys.stderr)
            return 2
    missing = [p for p in args.paths if not Path(p).exists()]
    if missing:
        print(f"graftcheck: no such path(s): {', '.join(missing)}",
              file=sys.stderr)
        return 2
    engine = LintEngine(ALL_RULES, baseline=baseline)
    if args.changed is not None:
        result = _changed_run(engine, args.paths, args.changed, args.cache)
        if result is None:
            print(f"graftcheck: --changed {args.changed}: not inside a "
                  "git work tree (or the ref is unknown)", file=sys.stderr)
            return 2
    else:
        result = engine.lint_paths(args.paths)
        if args.cache is not None:
            entries: Dict[str, dict] = {}
            for p in sorted(expand_paths(args.paths)):
                source = Path(p).read_text()
                module, _err = engine._parse(source, str(p))
                if module is not None:
                    entries[norm_path(str(p))] = {
                        "sha1": file_sha1(source),
                        "summary": strip_summary(
                            extract_module_summary(module))}
            _save_cache(args.cache, entries)
    if args.write_baseline is not None:
        save_baseline(args.write_baseline, result.findings)
        print(f"graftcheck: wrote {len(result.findings)} fingerprint(s) "
              f"to {args.write_baseline}")
        return 0
    if args.sarif:
        out = render_sarif(result, ALL_RULES)
    elif args.json:
        out = render_json(result, fail_on)
    else:
        out = render_text(result, fail_on)
    print(out)
    return 1 if result.failing(fail_on) else 0


if __name__ == "__main__":
    sys.exit(main())

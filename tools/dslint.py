#!/usr/bin/env python
"""graft-lint CLI: contract-enforcing static analysis (docs/ANALYSIS.md).

Usage::

    python tools/dslint.py deepspeed_tpu/              # human output
    python tools/dslint.py deepspeed_tpu/ --json out.json
    python tools/dslint.py deepspeed_tpu/ --write-baseline
    python tools/dslint.py deepspeed_tpu/ --no-baseline   # full inventory

Exit status: 0 when every finding is suppressed or baselined, 1 when
NEW findings exist, 2 on usage errors.  ``--json`` carries per-rule
counts.

Pure stdlib + AST — no jax import, so it runs anywhere the repo checks
out (pre-push hooks, doc builds, CI shards without accelerators).
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_analysis():
    """Load ``deepspeed_tpu/analysis`` as a standalone package so the
    CLI never executes ``deepspeed_tpu/__init__.py`` (which imports the
    full jax stack — the linter must run on accelerator-less hosts and
    in pre-push hooks in milliseconds).  Registered under a private
    name; the in-package import (tests, programmatic use) is untouched."""
    name = "_dslint_analysis"
    if name in sys.modules:
        return sys.modules[name]
    pkg_dir = os.path.join(_REPO_ROOT, "deepspeed_tpu", "analysis")
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


_analysis = _load_analysis()
build_default_rules = _analysis.build_default_rules
load_baseline = _analysis.load_baseline
run_analysis = _analysis.run_analysis
save_baseline = _analysis.save_baseline

DEFAULT_BASELINE = os.path.join(_REPO_ROOT, "tools", "dslint_baseline.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="dslint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    default=[os.path.join(_REPO_ROOT, "deepspeed_tpu")],
                    help="files/dirs to analyze (default: deepspeed_tpu/)")
    ap.add_argument("--root", default=_REPO_ROOT,
                    help="repo root for relative paths + docs registries")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline JSON (default: tools/dslint_baseline"
                         ".json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline: report every finding")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline from this run and exit 0")
    ap.add_argument("--json", metavar="FILE", default=None,
                    help="also write a JSON report (counts per rule + "
                         "findings)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="summary only, no per-finding lines")
    args = ap.parse_args(argv)

    rules = build_default_rules()
    if args.list_rules:
        for r in rules:
            print(f"{r.id:22s} {r.description}")
        return 0

    for p in args.paths:
        if not os.path.exists(p):
            print(f"dslint: no such path: {p}", file=sys.stderr)
            return 2

    baseline = ({} if (args.no_baseline or args.write_baseline)
                else load_baseline(args.baseline))
    res = run_analysis(args.paths, args.root, rules=rules,
                       baseline=baseline)

    if args.write_baseline:
        # the shared baseline describes the WHOLE tree: regenerating it
        # from a partial path set would silently drop every grandfathered
        # finding outside that subtree and fail the next full run
        default_tree = os.path.abspath(os.path.join(_REPO_ROOT,
                                                    "deepspeed_tpu"))
        covers_tree = any(
            os.path.abspath(p) == default_tree
            or default_tree.startswith(os.path.abspath(p) + os.sep)
            for p in args.paths)
        if not covers_tree and os.path.abspath(args.baseline) \
                == os.path.abspath(DEFAULT_BASELINE):
            print("dslint: refusing to overwrite the shared baseline "
                  f"({DEFAULT_BASELINE}) from a partial path set — "
                  "analyze deepspeed_tpu/ (the whole tree), or pass "
                  "--baseline <other-file> for a scoped baseline",
                  file=sys.stderr)
            return 2
        save_baseline(args.baseline, res.findings)
        print(f"dslint: baseline written to {args.baseline} "
              f"({len(res.findings)} finding(s) grandfathered)")
        return 0

    new_ids = {id(f) for f in res.new_findings}
    if not args.quiet:
        for f in res.findings:
            mark = "" if id(f) in new_ids else "  [baselined]"
            print(f.render() + mark)

    by_rule = res.by_rule()
    print(f"dslint: {res.files} file(s), "
          f"{len(res.findings)} finding(s) "
          f"({len(res.new_findings)} new, "
          f"{len(res.findings) - len(res.new_findings)} baselined, "
          f"{res.suppressed} suppressed inline)")
    for rid in sorted(by_rule):
        row = by_rule[rid]
        print(f"  {rid:22s} findings={row['findings']:<4d} "
              f"new={row['new']:<4d} baselined={row['baselined']}")

    if args.json:
        report = {
            "files": res.files,
            "total": len(res.findings),
            "new": len(res.new_findings),
            "baselined": len(res.findings) - len(res.new_findings),
            "suppressed_inline": res.suppressed,
            "rules": {r.id: by_rule.get(r.id, {"findings": 0, "new": 0,
                                               "baselined": 0})
                      for r in rules},
            "new_findings": [
                {"rule": f.rule, "path": f.path, "line": f.line,
                 "message": f.message, "key": f.key}
                for f in res.new_findings],
        }
        tmp = args.json + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=1)
            fh.write("\n")
        os.replace(tmp, args.json)
        print(f"dslint: JSON report -> {args.json}")

    return 1 if res.new_findings else 0


if __name__ == "__main__":
    sys.exit(main())

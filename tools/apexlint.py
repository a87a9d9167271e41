#!/usr/bin/env python
"""apexlint — run the repo's AST invariant analyzer (ISSUE 19).

Sweeps ``apex_tpu/``, ``tools/``, ``tests/`` and ``chip_smoke.py`` with the
rule registry in :mod:`apex_tpu.analysis.staticcheck`: the repo's own
bug classes (wall clock in deterministic paths, unseeded RNG,
non-atomic JSON writes, unregistered/undocumented env knobs, clock
forwarding into flightrec, use-after-donate, unsorted filesystem
walks, ``record(kind=...)`` misuse) plus the cross-artifact
env-registry ↔ README drift gate.  Exits nonzero on any violation.

Deliberately jax-free: ``staticcheck`` and the env registry are loaded
straight from their file paths, so this runs anywhere python runs —
it is the ``apexlint`` lint_graphs check and the tier-1 ``APEXLINT=``
banner without paying a single import of the package.

::

    python tools/apexlint.py              # sweep, exit 1 on violations
    python tools/apexlint.py --json       # machine-readable report
    python tools/apexlint.py --summary    # one APEXLINT= line, exit 0
    python tools/apexlint.py --root DIR   # sweep another tree
    python tools/apexlint.py --readme F   # drift-check against F
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)


def _load_staticcheck():
    """Import staticcheck by file path — no apex_tpu package import,
    no jax."""
    path = os.path.join(_REPO, "apex_tpu", "analysis", "staticcheck.py")
    spec = importlib.util.spec_from_file_location(
        "_apexlint_staticcheck", path
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve __module__
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="AST invariant analyzer over the repo's own bug "
                    "classes"
    )
    ap.add_argument("--root", default=_REPO,
                    help="tree to sweep (default: this repo)")
    ap.add_argument("--readme", default=None,
                    help="README.md to drift-check the env registry "
                         "against (default: <root>/README.md)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as JSON")
    ap.add_argument("--summary", action="store_true",
                    help="print one APEXLINT= line and always exit 0 "
                         "(the tier-1 banner mode)")
    ap.add_argument("--rules", action="store_true",
                    help="list the rule registry and exit")
    args = ap.parse_args(argv)

    sc = _load_staticcheck()

    if args.rules:
        for r in sc.RULES:
            print(f"{r.name:28s} [{r.scope}] {r.doc}")
            print(f"{'':28s} origin: {r.origin}")
        return 0

    report = sc.scan_repo(root=args.root, readme=args.readme)
    c = report.census()

    if args.summary:
        verdict = "pass" if c["violations"] == 0 else "FAIL"
        print(f"APEXLINT={verdict} rules={c['rules']} "
              f"files={c['files']} violations={c['violations']} "
              f"suppressions={c['suppressions']}")
        return 0

    if args.json:
        doc = {
            "schema": "apex_tpu.apexlint.v1",
            "census": c,
            "violations": [
                {"rule": f.rule, "path": f.path, "line": f.line,
                 "message": f.message}
                for f in report.findings
            ],
            "suppressions": [
                {"rule": s.rule, "path": s.path, "line": s.line,
                 "reason": s.reason, "used": s.used}
                for s in report.suppressions
            ],
        }
        print(json.dumps(doc, indent=1, sort_keys=True))
    else:
        print(report.render())
    return 1 if c["violations"] else 0


if __name__ == "__main__":
    sys.exit(main())

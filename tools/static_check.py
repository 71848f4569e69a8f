#!/usr/bin/env python3
"""Unified static-analysis driver: one command, one exit code.

Runs every registered pass from tools/lint/ against the repo and prints
per-pass timings. Exit 0 only when every pass is clean; any violation or
crashing pass exits 1. Wired into the tier-1 tests via tests/test_lint.py.

    python tools/static_check.py              # all passes
    python tools/static_check.py --list       # show passes
    python tools/static_check.py --only jit-purity --only conf-keys

Adding a pass: drop a module in tools/lint/ that decorates a
``fn(root) -> list[str]`` with ``@core.register(name, description)`` and
add it to the import list below (import order is run order). See
docs/static_analysis.md.
"""

from __future__ import annotations

import argparse
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from tools.lint import core  # noqa: E402
# importing a pass module registers it; import order is run order
from tools.lint import gauge_catalog  # noqa: E402,F401
from tools.lint import span_catalog  # noqa: E402,F401
from tools.lint import cache_keys  # noqa: E402,F401
from tools.lint import pallas_fallback  # noqa: E402,F401
from tools.lint import type_support  # noqa: E402,F401
from tools.lint import jit_purity  # noqa: E402,F401
from tools.lint import conf_keys  # noqa: E402,F401
from tools.lint import doc_drift  # noqa: E402,F401


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=_ROOT,
                    help="repo root to check (default: this repo)")
    ap.add_argument("--only", action="append", default=None,
                    metavar="PASS", help="run only the named pass(es)")
    ap.add_argument("--list", action="store_true",
                    help="list registered passes and exit")
    args = ap.parse_args(argv)

    if args.list:
        for p in core.PASSES:
            print(f"{p.name:14s} {p.description}")
        return 0

    if args.only:
        known = {p.name for p in core.PASSES}
        unknown = [n for n in args.only if n not in known]
        if unknown:
            print(f"unknown pass(es): {unknown}; have {sorted(known)}",
                  file=sys.stderr)
            return 2

    results = core.run(args.root, args.only)
    failed = False
    for r in results:
        status = "OK  " if not r.violations else "FAIL"
        print(f"[{status}] {r.name:14s} {r.seconds * 1e3:8.1f} ms"
              + (f"  ({len(r.violations)} violation"
                 f"{'s' if len(r.violations) != 1 else ''})"
                 if r.violations else ""))
        for v in r.violations:
            failed = True
            print(f"    {v}", file=sys.stderr)
    total = sum(r.seconds for r in results)
    print(f"static_check: {len(results)} passes in {total * 1e3:.0f} ms: "
          + ("FAILED" if failed else "all clean"))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""dpwalint — run the repo's static-analysis checkers.

Usage::

    python tools/dpwalint.py                    # lint dpwa_tpu/ tools/
    python tools/dpwalint.py path [...]         # lint specific files/dirs
    python tools/dpwalint.py --json             # machine-readable output
    python tools/dpwalint.py --list-rules       # enumerate rule ids
    python tools/dpwalint.py --update-baseline  # ratchet: rewrite the
                                                #   baseline to the current
                                                #   findings (carries reasons)

Exit status is the number of non-baselined findings plus stale baseline
entries (clamped to 125) — 0 means the tree is clean.  See
docs/static-analysis.md for the annotation grammar and the rule list.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)

from dpwa_tpu import analysis  # noqa: E402
from dpwa_tpu.analysis.rules import RULE_DESCRIPTIONS  # noqa: E402

DEFAULT_BASELINE = os.path.join(_HERE, "dpwalint_baseline.json")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the dpwalint static-analysis checkers."
    )
    ap.add_argument(
        "paths", nargs="*",
        help="files/dirs to lint (default: dpwa_tpu/ tools/)",
    )
    ap.add_argument(
        "--json", action="store_true", help="machine-readable output"
    )
    ap.add_argument(
        "--baseline", default=DEFAULT_BASELINE,
        help=f"ratchet baseline path (default: {DEFAULT_BASELINE})",
    )
    ap.add_argument(
        "--no-baseline", action="store_true",
        help="report every finding, ignoring the baseline",
    )
    ap.add_argument(
        "--update-baseline", action="store_true",
        help="rewrite the baseline to the current findings "
        "(existing reasons are carried forward)",
    )
    ap.add_argument(
        "--list-rules", action="store_true",
        help="list rule ids and exit",
    )
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule, desc in sorted(RULE_DESCRIPTIONS.items()):
            print(f"{rule}: {desc}")
        return 0

    from dpwa_tpu.analysis.core import DEFAULT_TARGETS
    targets = args.paths or [
        os.path.join(_ROOT, t) for t in DEFAULT_TARGETS
    ]
    files = analysis.load_files(analysis.iter_py_files(targets))
    baseline = (
        {} if args.no_baseline else analysis.load_baseline(args.baseline)
    )
    result = analysis.run_checkers(analysis.all_checkers(), files, baseline)

    if args.update_baseline:
        analysis.save_baseline(
            args.baseline, result.errors + result.baselined, baseline
        )
        print(
            f"baseline rewritten: {args.baseline} "
            f"({len(result.errors) + len(result.baselined)} entries)"
        )
        return 0

    if args.json:
        json.dump(
            {
                "error_count": len(result.errors),
                "errors": [f.to_dict() for f in result.errors],
                "baselined": [f.to_dict() for f in result.baselined],
                "suppressed": [
                    {**f.to_dict(), "reason": reason}
                    for f, reason in result.suppressed
                ],
                "stale_baseline": result.stale_baseline,
            },
            sys.stdout, indent=2,
        )
        print()
        return result.exit_code

    for f in result.errors:
        print(f"{f.path}:{f.line}: [{f.rule}] {f.message}")
    for key in result.stale_baseline:
        print(
            f"STALE baseline entry {key!r} — the finding no longer "
            f"fires; remove it from {args.baseline}"
        )
    status = "FAIL" if result.exit_code else "OK"
    print(
        f"{status}: {len(result.errors)} finding(s), "
        f"{len(result.stale_baseline)} stale baseline entr(ies), "
        f"{len(result.baselined)} baselined, "
        f"{len(result.suppressed)} suppressed"
    )
    return result.exit_code


if __name__ == "__main__":
    sys.exit(main())

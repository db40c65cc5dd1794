#!/usr/bin/env python
"""Back-compat shim: the emit-site lint now lives in dpwalint.

The pass itself moved to :mod:`dpwa_tpu.analysis.emit_kinds` (the
``emit-kind`` rule), sharing the dpwalint runner, suppression grammar,
and ratchet baseline with the other repo checkers — run
``python tools/dpwalint.py`` for the full suite.  This module keeps the
old entry points (``lint``/``lint_file``/``main``, the schema_check
registry re-exports) so existing callers and tests keep working.
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

try:
    from tools.schema_check import EVENT_KINDS, RECORD_KINDS
except ImportError:  # run as a loose script outside the repo root
    sys.path.insert(0, _HERE)
    from schema_check import EVENT_KINDS, RECORD_KINDS  # noqa: F401

from dpwa_tpu.analysis.core import iter_py_files, load_files  # noqa: E402
from dpwa_tpu.analysis.emit_kinds import EmitKindsChecker  # noqa: E402

DEFAULT_TARGETS = ("dpwa_tpu", "tools")


def _to_legacy(findings) -> List[dict]:
    return [
        {"file": f.path, "line": f.line, "error": f.message}
        for f in findings
    ]


def lint_file(path: str) -> List[dict]:
    return lint([path])


def lint(targets) -> List[dict]:
    files = load_files(iter_py_files(targets))
    errors = _to_legacy(EmitKindsChecker().check(files))
    for f in files:
        if f.parse_error is not None:
            errors.append({
                "file": f.path,
                "line": f.parse_error.line,
                "error": f"unparseable: {f.parse_error.message}",
            })
    return errors


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Lint JSONL emit sites against the registered "
        "record/event kinds (shim over tools/dpwalint.py)."
    )
    ap.add_argument(
        "paths", nargs="*",
        help="files/dirs to lint (default: dpwa_tpu/ tools/)",
    )
    ap.add_argument(
        "--json", action="store_true", help="machine-readable output"
    )
    args = ap.parse_args(argv)
    targets = args.paths or [
        os.path.join(_ROOT, t) for t in DEFAULT_TARGETS
    ]
    errors = lint(targets)
    if args.json:
        json.dump(
            {"error_count": len(errors), "errors": errors},
            sys.stdout, indent=2,
        )
        print()
    else:
        for e in errors:
            print(f"{e['file']}:{e['line']}: {e['error']}")
        status = "FAIL" if errors else "OK"
        print(f"{status}: {len(errors)} unregistered emit site(s)")
    return min(len(errors), 125)


if __name__ == "__main__":
    sys.exit(main())

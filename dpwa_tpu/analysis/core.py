"""Shared core for the dpwalint static-analysis framework.

Everything the individual checkers have in common lives here: the
parsed-file model, the ``# dpwalint:`` annotation grammar, the
suppression rules, and the ratchet baseline.  Checkers are plain
classes with a ``rules`` tuple and a ``check(files) -> [Finding]``
method; the runner (``tools/dpwalint.py``) and the tier-1 test both go
through :func:`run_checkers` so there is exactly one definition of
"clean tree".

Annotation grammar (one directive per comment, reasons after ``--``):

- ``# dpwalint: ignore[rule-a,rule-b] -- reason`` — suppress those
  rules on this line (or, when the comment stands alone on its line, on
  the next code line).  The reason is mandatory: an unexplained
  suppression is itself a finding.
- ``# dpwalint: ignore-file[rule] -- reason`` — suppress a rule for the
  whole file (must appear in the first 30 lines).
- ``# dpwalint: guarded_by(lock)`` — on an attribute access, or on a
  ``def`` line to cover the whole function: these accesses are
  protected by ``lock`` even though no lexical ``with`` shows it
  (e.g. a helper only ever called with the lock held).
- ``# dpwalint: double_buffered(attr) -- reason`` — registers ``attr``
  of the enclosing class as a deliberate unsynchronized handoff
  (thread-join ordering, swap-on-publish, …).  Reason mandatory.
- ``# dpwalint: thread_root(domain)`` — on a ``def`` line: this
  function is ALSO entered from the named thread domain (an entry
  point the intra-module call graph cannot see, e.g. a cross-object
  hook).

The ratchet baseline (``tools/dpwalint_baseline.json``) freezes
pre-existing debt by stable key (rule:path:symbol — line numbers are
deliberately not part of the key).  A finding whose key is baselined
is reported as suppressed; a baselined key that no longer fires is a
STALE entry and fails the run, so the baseline only ever shrinks.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import json
import os
import re
import tokenize
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

from dpwa_tpu.analysis.rules import RULE_IDS

DEFAULT_TARGETS = ("dpwa_tpu", "tools")
_PRUNE_DIRS = {"__pycache__", ".git", "artifacts", "fixtures"}


@dataclasses.dataclass
class Finding:
    """One violation: where, which rule, and a stable identity.

    ``symbol`` is the rule-specific stable name of the violating thing
    (an attribute, a config key, a magic literal…), chosen so the
    baseline key survives unrelated line shifts."""

    rule: str
    path: str
    line: int
    symbol: str
    message: str

    @property
    def key(self) -> str:
        return f"{self.rule}:{self.path}:{self.symbol}"

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "symbol": self.symbol,
            "message": self.message,
            "key": self.key,
        }


_DIRECTIVE_RE = re.compile(r"#\s*dpwalint:\s*(.+?)\s*$")
_IGNORE_RE = re.compile(
    r"^(ignore|ignore-file)\[([\w\-, ]+)\]\s*(?:--|—)?\s*(.*)$"
)
_GUARDED_RE = re.compile(r"^guarded_by\(([A-Za-z_][\w.]*)\)\s*$")
_DOUBLE_BUF_RE = re.compile(
    r"^double_buffered\(([A-Za-z_]\w*)\)\s*(?:--|—)\s*(.+)$"
)
_THREAD_ROOT_RE = re.compile(r"^thread_root\(([\w\-]+)\)\s*$")


@dataclasses.dataclass
class Annotations:
    """Parsed ``# dpwalint:`` directives of one file."""

    # line -> set of rule ids suppressed on that line
    ignores: Dict[int, Dict[str, str]]
    # rule -> reason, file-wide
    file_ignores: Dict[str, str]
    # line -> lock name
    guarded_by: Dict[int, str]
    # line -> (attr, reason); class resolution happens in the checker
    double_buffered: Dict[int, Tuple[str, str]]
    # line -> domain name
    thread_roots: Dict[int, str]
    # malformed directives, reported under the dpwalint-annotation rule
    errors: List[Finding]


def _iter_comments(text: str) -> Iterator[Tuple[int, str]]:
    """(line, comment-text) for every real COMMENT token — directives
    quoted inside docstrings are grammar documentation, not directives."""
    try:
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type == tokenize.COMMENT:
                yield tok.start[0], tok.string
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return  # unparseable file: SourceFile reports it separately


def _parse_annotations(path: str, text: str) -> Annotations:
    ann = Annotations({}, {}, {}, {}, {}, [])
    for i, raw in _iter_comments(text):
        m = _DIRECTIVE_RE.search(raw)
        if not m:
            continue
        body = m.group(1)
        im = _IGNORE_RE.match(body)
        if im:
            kind, rule_list, reason = im.groups()
            rules = [r.strip() for r in rule_list.split(",") if r.strip()]
            bad = [r for r in rules if r not in RULE_IDS]
            if bad:
                ann.errors.append(Finding(
                    "dpwalint-annotation", path, i, f"unknown-rule:{bad[0]}",
                    f"suppression names unknown rule(s) {bad}",
                ))
                continue
            if not reason.strip():
                ann.errors.append(Finding(
                    "dpwalint-annotation", path, i, f"no-reason:{rules[0]}",
                    "suppression has no reason — write"
                    " `# dpwalint: ignore[rule] -- why`",
                ))
                continue
            if kind == "ignore-file":
                if i > 30:
                    ann.errors.append(Finding(
                        "dpwalint-annotation", path, i,
                        f"late-ignore-file:{rules[0]}",
                        "ignore-file must appear in the first 30 lines",
                    ))
                    continue
                for r in rules:
                    ann.file_ignores[r] = reason.strip()
            else:
                tgt = dict(ann.ignores.get(i, {}))
                for r in rules:
                    tgt[r] = reason.strip()
                ann.ignores[i] = tgt
            continue
        gm = _GUARDED_RE.match(body)
        if gm:
            ann.guarded_by[i] = gm.group(1)
            continue
        dm = _DOUBLE_BUF_RE.match(body)
        if dm:
            ann.double_buffered[i] = (dm.group(1), dm.group(2).strip())
            continue
        tm = _THREAD_ROOT_RE.match(body)
        if tm:
            ann.thread_roots[i] = tm.group(1)
            continue
        ann.errors.append(Finding(
            "dpwalint-annotation", path, i, "malformed",
            f"malformed dpwalint directive: {body!r}"
            " (a double_buffered/ignore without a `-- reason`?)",
        ))
    return ann


class SourceFile:
    """One parsed python file: text, AST, and its annotations."""

    def __init__(self, path: str, text: str):
        self.path = path
        self.text = text
        self.lines = text.splitlines()
        self.tree: Optional[ast.AST] = None
        self.parse_error: Optional[Finding] = None
        try:
            self.tree = ast.parse(text, filename=path)
        except SyntaxError as e:
            self.parse_error = Finding(
                "dpwalint-annotation", path, e.lineno or 0, "syntax-error",
                f"file does not parse: {e.msg}",
            )
        self.annotations = _parse_annotations(path, text)

    def line_is_blank_comment(self, line: int) -> bool:
        """True when ``line`` holds nothing but a comment."""
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].lstrip().startswith("#")
        return False

    def suppression_for(self, rule: str, line: int) -> Optional[str]:
        """Reason string if ``rule`` at ``line`` is suppressed, else None.

        A standalone-comment ignore covers the next code line, so both
        the annotation's own line and the line above are consulted."""
        if rule in self.annotations.file_ignores:
            return self.annotations.file_ignores[rule]
        on_line = self.annotations.ignores.get(line, {})
        if rule in on_line:
            return on_line[rule]
        above = self.annotations.ignores.get(line - 1, {})
        if rule in above and self.line_is_blank_comment(line - 1):
            return above[rule]
        return None


def iter_py_files(targets: Iterable[str]) -> List[str]:
    """All .py files under ``targets`` (dirs walked, files taken as-is),
    pruning caches, VCS internals, artifacts, and test fixtures."""
    out: List[str] = []
    for target in targets:
        if os.path.isfile(target):
            out.append(target)
            continue
        for dirpath, dirnames, filenames in os.walk(target):
            dirnames[:] = sorted(
                d for d in dirnames if d not in _PRUNE_DIRS
            )
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return out


def load_files(paths: Iterable[str]) -> List[SourceFile]:
    files = []
    for p in paths:
        with open(p, "r", encoding="utf-8") as fh:
            files.append(SourceFile(p, fh.read()))
    return files


# --- baseline ratchet ---


def load_baseline(path: str) -> Dict[str, str]:
    """key -> reason.  Missing file = empty baseline."""
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    out: Dict[str, str] = {}
    for entry in data.get("entries", []):
        out[entry["key"]] = entry.get("reason", "")
    return out


def save_baseline(
    path: str, findings: Sequence[Finding], old: Dict[str, str]
) -> None:
    """Write the current findings as the new baseline, carrying forward
    reasons already written for keys that persist."""
    entries = []
    seen = set()
    for f in findings:
        if f.key in seen:
            continue
        seen.add(f.key)
        entries.append({
            "key": f.key,
            "reason": old.get(
                f.key, "pre-existing debt (auto-added; document why)"
            ),
            "message": f.message,
        })
    entries.sort(key=lambda e: e["key"])
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"entries": entries}, fh, indent=2, sort_keys=True)
        fh.write("\n")


@dataclasses.dataclass
class RunResult:
    """Outcome of one lint run, pre-split for reporting."""

    errors: List[Finding]  # fail the run
    baselined: List[Finding]  # matched a baseline entry
    suppressed: List[Tuple[Finding, str]]  # inline-ignored, with reason
    stale_baseline: List[str]  # baseline keys that no longer fire

    @property
    def exit_code(self) -> int:
        n = len(self.errors) + len(self.stale_baseline)
        return min(n, 125)


def run_checkers(
    checkers,
    files: Sequence[SourceFile],
    baseline: Optional[Dict[str, str]] = None,
) -> RunResult:
    """Run every checker, then apply suppressions and the baseline."""
    raw: List[Finding] = []
    for f in files:
        if f.parse_error is not None:
            raw.append(f.parse_error)
        raw.extend(f.annotations.errors)
    by_path = {f.path: f for f in files}
    for checker in checkers:
        raw.extend(checker.check(files))
    errors: List[Finding] = []
    suppressed: List[Tuple[Finding, str]] = []
    baselined: List[Finding] = []
    baseline = baseline or {}
    fired_keys = set()
    for finding in raw:
        if finding.rule not in RULE_IDS:
            raise AssertionError(
                f"checker emitted unregistered rule {finding.rule!r} — "
                "register it in dpwa_tpu/analysis/rules.py first"
            )
        src = by_path.get(finding.path)
        reason = (
            src.suppression_for(finding.rule, finding.line)
            if src is not None
            else None
        )
        if reason is not None:
            suppressed.append((finding, reason))
            continue
        fired_keys.add(finding.key)
        if finding.key in baseline:
            baselined.append(finding)
        else:
            errors.append(finding)
    stale = sorted(k for k in baseline if k not in fired_keys)
    errors.sort(key=lambda f: (f.path, f.line, f.rule))
    return RunResult(errors, baselined, suppressed, stale)

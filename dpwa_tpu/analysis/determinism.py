"""Determinism checker for replica-identical decision paths.

The gossip algebra only converges when every node makes the SAME
partner/merge/trust decision at the same step, so the decision modules
(schedules, trust, membership, interpolation, the async round loop) must
be pure functions of ``(seed, step, structured state)``:

- ``det-random``: no ambient randomness — ``random.*`` and unseeded
  ``np.random.*`` are forbidden; ``np.random.default_rng(seed)`` with an
  explicit seed argument is fine.
- ``det-time``: wall-clock reads may feed telemetry, but not branch
  conditions or comparisons — two replicas never read the same clock.
- ``det-dict-order``: bare ``.items()/.keys()/.values()`` iteration is
  insertion-order dependent; wrap in ``sorted()`` unless the consumer is
  an order-insensitive aggregate (``sum``/``min``/``max``/``set``/…).
- ``det-tag-literal`` (repo-wide, not just decision modules): the tag
  argument of ``_pair_key`` / ``chaos_draw`` must be a named constant
  from ``dpwa_tpu/utils/tags.py`` — a raw int literal can silently
  collide with another subsystem's stream and correlate draws that the
  paper requires to be independent.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence

from dpwa_tpu.analysis.core import Finding, SourceFile

# modules whose control flow is part of the replicated decision function
_DECISION_MARKERS = (
    "parallel/schedules.py",
    "trust/",
    "membership/",
    "dpwa_tpu/interpolation.py",
    "parallel/async_loop.py",
    "run/",
    "tune/",
)

# consumers for which iteration order genuinely does not matter
_ORDER_INSENSITIVE = {
    "sorted", "min", "max", "sum", "all", "any", "set", "frozenset",
    "len", "dict", "Counter", "update",
}

_TIME_FNS = {"time", "monotonic", "perf_counter", "process_time"}

_TAG_TAKING_FNS = {"_pair_key", "chaos_draw"}
_TAG_ARG_INDEX = 3  # (seed, step, pair_id, tag)


def _dotted(node: ast.expr) -> Optional[str]:
    """``a.b.c`` -> "a.b.c" for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_decision_path(path: str) -> bool:
    p = path.replace("\\", "/")
    return any(m in p for m in _DECISION_MARKERS)


def _parents(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    out: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            out[child] = node
    return out


class DeterminismChecker:
    name = "determinism"
    rules = ("det-random", "det-time", "det-dict-order", "det-tag-literal")

    def check(self, files: Sequence[SourceFile]) -> List[Finding]:
        out: List[Finding] = []
        for src in files:
            if src.tree is None:
                continue
            out.extend(self._check_tags(src))
            if _is_decision_path(src.path):
                out.extend(self._check_decision_module(src))
        return out

    # --- det-tag-literal (repo-wide) ---

    def _check_tags(self, src: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            fn_name = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None
            )
            if fn_name not in _TAG_TAKING_FNS:
                continue
            tag_expr: Optional[ast.expr] = None
            if len(node.args) > _TAG_ARG_INDEX:
                tag_expr = node.args[_TAG_ARG_INDEX]
            for kw in node.keywords:
                if kw.arg == "tag":
                    tag_expr = kw.value
            if tag_expr is None:
                continue
            if self._is_literal_tag(tag_expr):
                out.append(Finding(
                    "det-tag-literal", src.path, node.lineno,
                    f"{fn_name}:{ast.unparse(tag_expr)}",
                    f"raw tag {ast.unparse(tag_expr)!r} passed to "
                    f"{fn_name}() — use a named TAG_* / CHAOS_* constant "
                    "from dpwa_tpu/utils/tags.py so collisions are "
                    "caught at import time",
                ))
        return out

    @staticmethod
    def _is_literal_tag(expr: ast.expr) -> bool:
        """True when the tag is built purely from int literals."""
        if isinstance(expr, ast.Constant):
            return isinstance(expr.value, int)
        if isinstance(expr, ast.BinOp):
            return (
                DeterminismChecker._is_literal_tag(expr.left)
                and DeterminismChecker._is_literal_tag(expr.right)
            )
        return False

    # --- decision-module rules ---

    def _check_decision_module(self, src: SourceFile) -> List[Finding]:
        out: List[Finding] = []
        parents = _parents(src.tree)
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call):
                out.extend(self._rand_call(src, node))
                out.extend(self._dict_order(src, node, parents))
            elif isinstance(node, (ast.If, ast.While)):
                out.extend(self._time_in_test(src, node.test))
            elif isinstance(node, ast.Compare):
                out.extend(self._time_in_compare(src, node))
        # a compare inside an if-test is seen by both probes: dedupe
        seen = set()
        deduped = []
        for f in out:
            ident = (f.rule, f.line, f.symbol)
            if ident not in seen:
                seen.add(ident)
                deduped.append(f)
        return deduped

    def _rand_call(self, src: SourceFile, node: ast.Call) -> List[Finding]:
        dotted = _dotted(node.func)
        if dotted is None:
            return []
        is_np_rand = dotted.startswith(("np.random.", "numpy.random."))
        is_py_rand = dotted.startswith("random.")
        if not (is_np_rand or is_py_rand):
            return []
        if dotted.endswith(".default_rng") and (node.args or node.keywords):
            return []  # explicitly seeded generator: replica-identical
        return [Finding(
            "det-random", src.path, node.lineno, dotted,
            f"{dotted}() draws from ambient process randomness on a "
            "decision path — derive draws from the threefry schedule "
            "(participation_draw/_pair_key) or a seeded default_rng",
        )]

    def _time_findings(self, src: SourceFile, sub: ast.AST) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(sub):
            if isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                if dotted and dotted.startswith("time.") and (
                    dotted.split(".")[-1] in _TIME_FNS
                ):
                    out.append(Finding(
                        "det-time", src.path, node.lineno, dotted,
                        f"{dotted}() feeds a branch/comparison on a "
                        "decision path — replicas read different clocks; "
                        "pass the decision deadline in as data",
                    ))
        return out

    def _time_in_test(self, src: SourceFile, test: ast.expr) -> List[Finding]:
        return self._time_findings(src, test)

    def _time_in_compare(
        self, src: SourceFile, node: ast.Compare
    ) -> List[Finding]:
        return self._time_findings(src, node)

    def _dict_order(
        self,
        src: SourceFile,
        node: ast.Call,
        parents: Dict[ast.AST, ast.AST],
    ) -> List[Finding]:
        fn = node.func
        if not (
            isinstance(fn, ast.Attribute)
            and fn.attr in ("items", "keys", "values")
            and not node.args
            and not node.keywords
        ):
            return []
        # walk ancestors within the statement: exempt when feeding an
        # order-insensitive aggregate or a set comprehension
        cur: Optional[ast.AST] = node
        while cur is not None and not isinstance(cur, ast.stmt):
            parent = parents.get(cur)
            if isinstance(parent, ast.Call) and cur in (
                list(parent.args) + [kw.value for kw in parent.keywords]
            ):
                pfn = parent.func
                pname = pfn.attr if isinstance(pfn, ast.Attribute) else (
                    pfn.id if isinstance(pfn, ast.Name) else None
                )
                if pname in _ORDER_INSENSITIVE:
                    return []
            if isinstance(parent, ast.SetComp):
                return []
            cur = parent
        base = _dotted(fn.value) or "<expr>"
        return [Finding(
            "det-dict-order", src.path, node.lineno,
            f"{base}.{fn.attr}",
            f"bare {base}.{fn.attr}() iteration on a decision path "
            "depends on dict insertion order — wrap in sorted(...) or "
            "feed an order-insensitive aggregate",
        )]

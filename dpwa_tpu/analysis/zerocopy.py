"""Zero-copy frame-path checker.

The frame hot path (docs/transport.md "The zero-copy landing zone")
moves payload bytes from socket to merge as memoryviews over ring
buffers; one stray ``.tobytes()`` or ``bytes(...)`` silently
reintroduces a payload-sized copy per frame and the perf regression is
invisible until someone times the path.  ``zerocopy-tobytes`` makes the copy
discipline structural: on the frame-path modules listed below, every
``.tobytes()`` attribute call and every ``bytes(...)`` constructor call
is an error unless annotated with the standard suppression grammar and
a reason (``# dpwalint: ignore[zerocopy-tobytes] -- why this copy is
the contract``) — publish-time snapshots and owning-bytes API returns
are legitimate, but each one is a reviewed, justified exception.

``bytearray(n)`` allocation is deliberately NOT flagged: buffers must
come from somewhere; the rule targets copies OUT of existing buffers.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Sequence

from dpwa_tpu.analysis.core import Finding, SourceFile

# Modules whose socket->decode->serve path carries frame payloads.
# chaos.py deliberately absent: fault injection copies frames by design.
_FRAME_PATH_MARKERS = (
    "ops/quantize.py",
    "ops/shard.py",
    "parallel/tcp.py",
    "parallel/reactor.py",
    "parallel/ingest.py",
)


def _norm(path: str) -> str:
    return path.replace("\\", "/")


def _enclosing_functions(tree: ast.AST) -> Dict[int, str]:
    """line -> name of the innermost def containing it (module-level
    lines are absent).  Later (deeper) defs overwrite their enclosing
    def's lines, so the innermost name wins."""
    spans: Dict[int, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            end = getattr(node, "end_lineno", node.lineno)
            for line in range(node.lineno, end + 1):
                spans[line] = node.name
    return spans


class ZeroCopyChecker:
    name = "zerocopy"
    rules = ("zerocopy-tobytes",)

    def check(self, files: Sequence[SourceFile]) -> List[Finding]:
        out: List[Finding] = []
        for src in files:
            if src.tree is None:
                continue
            if not any(
                m in _norm(src.path) for m in _FRAME_PATH_MARKERS
            ):
                continue
            owners = _enclosing_functions(src.tree)
            for node in ast.walk(src.tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                if isinstance(fn, ast.Attribute) and fn.attr == "tobytes":
                    what = ".tobytes()"
                elif isinstance(fn, ast.Name) and fn.id == "bytes":
                    what = "bytes(...)"
                else:
                    continue
                sym = owners.get(node.lineno, "<module>")
                out.append(Finding(
                    "zerocopy-tobytes", src.path, node.lineno,
                    f"{sym}:{what}",
                    f"{what} on a frame-path module copies payload "
                    "bytes out of the receive/serve path — decode and "
                    "serve through memoryviews/np views (see "
                    "dpwa_tpu/parallel/ingest.py), or justify the copy "
                    "with an inline ignore and a reason",
                ))
        return out

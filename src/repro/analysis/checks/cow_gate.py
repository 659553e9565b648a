"""cow-gate: arena writers must be reachable only behind the COW gate.

KV pages are refcounted and shared across requests (prefix cache, COW
forks).  Writing a shared or pinned page in place corrupts every other
reader, so each write path must first pass ``KVBlockPool.ensure_writable``
(or the engine's chunk-level ``_cow_chunk_pages`` wrapper), which forks
the page when its refcount > 1 or it is pinned.

The pass flags any function in ``serving/`` or ``models/`` that calls a
known arena-writing entry point without also calling a gate in the same
function body.  Call sites that are safe by construction — e.g. decode
appending into a tail page the request owns exclusively — carry a
``# saralint: ok[cow-gate] <reason>`` pragma documenting the ownership
argument.
"""

from __future__ import annotations

import ast
from typing import Iterable

from ..core import Context, ERROR, Finding, register

CHECK = "cow-gate"

#: entry points that mutate arena page storage
WRITERS = {
    "_arena_write_chunk",       # models/attention.py chunk scatter
    "_paged_write",             # engine jit wrapper: bucketed prefill write
    "_chunk_prefill",           # engine jit wrapper: ragged chunk prefill
    "_paged_decode",            # engine jit wrapper: decode append + attend
    "paged_prefill_write",      # model-level bucketed KV scatter
    "copy_page",                # raw arena page copy
    "apply_moves",              # raw arena defrag gather
    "_spec_verify",             # engine jit wrapper: spec-decode verify chunk
    "_draft_prefill",           # spec_decode jit wrapper: draft catch-up
    "_draft_loop",              # spec_decode jit wrapper: fused draft rounds
}

#: calls that establish copy-on-write protection for the writes that follow
GATES = {"ensure_writable", "_cow_chunk_pages"}


def _call_name(node: ast.Call) -> str:
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    if isinstance(node.func, ast.Name):
        return node.func.id
    return ""


@register("cow-gate",
          "arena writers reachable without ensure_writable protection")
def check(ctx: Context) -> Iterable[Finding]:
    for sf in ctx.files:
        if not (sf.rel.startswith(("serving/", "models/"))
                or "/serving/" in sf.rel or "/models/" in sf.rel):
            continue
        for fn in ast.walk(sf.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if fn.name in GATES:
                continue                    # this *is* the gate
            calls = [n for n in ast.walk(fn) if isinstance(n, ast.Call)]
            names = {_call_name(c) for c in calls}
            if names & GATES:
                continue                    # gated in this body
            seen = set()
            for c in calls:
                name = _call_name(c)
                if name in WRITERS and name not in seen:
                    seen.add(name)
                    yield Finding(
                        check=CHECK, severity=ERROR, path=sf.rel,
                        line=c.lineno,
                        message=(f"'{fn.name}' calls arena writer '{name}' "
                                 "with no ensure_writable/_cow_chunk_pages "
                                 "gate in scope — shared or pinned pages "
                                 "would be mutated in place"))

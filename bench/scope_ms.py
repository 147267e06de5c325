"""Device time per program call in the ops of one named scope: what the
scope metrics under `bench/metrics/` share.

The mean, over the traced slice's calls of a program, of the self time
of the ops whose scope path has a given component (`bench/xscope.py`),
nested scopes included (`attn` holds its `kv_write`).  A trace whose ops
carry no such scope reads nothing.
"""
from __future__ import annotations

from run import TRACE_DIR
from xscope import ScopedTrace, with_scopes
from xtrace import find_xplane


def scope_ms(ctx, program: str, component: str):
    calls = ctx.trace.program_calls(program, ctx.lo, ctx.hi)
    if not calls:
        return None
    tr = ctx.trace
    if not isinstance(tr, ScopedTrace):
        try:
            tr = with_scopes(tr, find_xplane(TRACE_DIR))
        except FileNotFoundError:
            return None
    by_path = tr.scope_ns(ctx.lo, ctx.hi, program)
    if not any(component in path for path in by_path):
        return None
    ns = sum(v for path, v in by_path.items() if component in path)
    return ns / len(calls) * 1e-6

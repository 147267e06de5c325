"""Device time per decode step that the scanned layer stack spends
outside its own layers: the mean, over the slice's `serve_decode` calls,
of the self time of the ops whose scope path has a `layers` component
and no `layer` component (`bench/xscope.py`).  That is the scan slicing
each layer's weights and cache out of the stacked arrays, and writing
them back.  A program whose ops carry no `layers` scope reads nothing."""
from run import TRACE_DIR
from xscope import ScopedTrace, with_scopes
from xtrace import find_xplane

NAME = "layer_stack_ms"
UNIT = "ms"
LAYER = "model"
MOVES = "tpot_p95_ms"
PROGRAM = "serve_decode"


def read(ctx):
    calls = ctx.trace.program_calls(PROGRAM, ctx.lo, ctx.hi)
    if not calls:
        return None
    tr = ctx.trace
    if not isinstance(tr, ScopedTrace):
        try:
            tr = with_scopes(tr, find_xplane(TRACE_DIR))
        except FileNotFoundError:
            return None
    by_path = tr.scope_ns(ctx.lo, ctx.hi, PROGRAM)
    if not any("layers" in path for path in by_path):
        return None
    ns = sum(v for path, v in by_path.items()
             if "layers" in path and "layer" not in path)
    return ns / len(calls) * 1e-6

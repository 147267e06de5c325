"""Device time of one call of the prefill program (`serve_prefill`): the
mean duration of its program events in the traced slice."""
NAME = "prefill_ms"
UNIT = "ms"
LAYER = "serving step programs"
MOVES = "ttft_p95_ms"


def read(ctx):
    calls = ctx.trace.program_calls("serve_prefill", ctx.lo, ctx.hi)
    if not calls:
        return None
    return sum(e.dur for e in calls) / len(calls) * 1e-6

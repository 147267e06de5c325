"""Device time of one call of the decode program (`serve_decode`): the
mean duration of its program events in the traced slice."""
NAME = "decode_ms"
UNIT = "ms"
LAYER = "serving step programs"
MOVES = "tpot_p95_ms"


def read(ctx):
    calls = ctx.trace.program_calls("serve_decode", ctx.lo, ctx.hi)
    if not calls:
        return None
    return sum(e.dur for e in calls) / len(calls) * 1e-6

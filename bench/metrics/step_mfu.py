"""The whole serving step's share of the chip's bf16 peak: the model
FLOPs that the traced slice's calls require (the configuration's own
counter, `bench/counters/<family>.py`) over the slice's seconds times the
peak.  It bounds every kernel's roofline share from above in what it can
claim end to end."""
NAME = "step_mfu"
UNIT = "%"
LAYER = "model"
MOVES = "output_tokens_per_s"


def read(ctx):
    if not ctx.calls:
        return None
    counter = ctx.cell.counter()
    arch = ctx.cell.config["arch"]
    flops = sum(counter.flops(arch, ctx.gen, prog, pos)
                for prog, pos in ctx.calls)
    seconds = (ctx.hi - ctx.lo) * 1e-9
    return 100.0 * flops / (seconds * ctx.peaks["bf16_flops_per_s"])

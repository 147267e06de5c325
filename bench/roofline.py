"""The reduction every `<kernel>_roofline` metric shares.

Share of the roofline = the least time the chip could take for the work
of the kernel's calls in the traced slice (per call the larger of FLOPs
over peak FLOP/s and bytes over peak bandwidth, from the kernel's own
work count in `bench/kernels/<kernel>.py`) over the device time those
calls took.  A kernel with no call in the slice reads nothing.
"""
from __future__ import annotations

from xtrace import program_name


def kernel_roofline(ctx, kernel: str):
    k = ctx.cell.kernel(kernel)
    ops = ctx.trace.kernel_ops(k.TRACE_NAMES, ctx.lo, ctx.hi)
    if not ops:
        return None
    arch, plan = ctx.cell.config["arch"], ctx.cell.config["plan"]
    least = spent = 0.0
    for op in ops:
        shape = k.call(arch, plan, ctx.gen, program_name(op.program))
        if shape is None:
            raise ValueError(f"{kernel}: a call in {op.program!r}, which "
                             f"its work count does not know")
        flops, nbytes = k.work(**shape)
        least += max(flops / ctx.peaks["bf16_flops_per_s"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
        spent += op.dur * 1e-9
    return 100.0 * least / spent

"""Work of one call of the fused swiglu kernel (the 'pallas' MLP
destination, in prefill and in every decode step).

FLOPs: three T×d×F matmuls, 6·T·d·F.  Bytes: the three weight panels
once, the input and the output once, in the compute dtype.  The (T, F)
intermediate is not counted: the fused kernel keeps it on chip.
"""
from __future__ import annotations

#: the jitted function whose `pallas_call` is the kernel: its device
#: ops in the trace are named after it
TRACE_NAMES = ("swiglu_pallas",)


def work(t: int, d: int, f: int, itemsize: int):
    """(FLOPs, bytes) of one call over t tokens."""
    return 6 * t * d * f, itemsize * (3 * d * f + 2 * t * d)


def call(arch: dict, plan: dict, gen, program: str):
    """Shapes of the kernel's calls in that program; None where it has none."""
    tokens = {"serve_prefill": gen.batch * gen.prompt_tokens,
              "serve_decode": gen.batch}.get(program)
    if tokens is None:
        return None
    return dict(t=tokens, d=arch["d_model"], f=arch["d_ff"],
                itemsize=2 if plan["compute_dtype"] == "bfloat16" else 4)

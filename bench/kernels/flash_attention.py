"""Work of one call of the flash_attention kernel (the 'pallas' attention
destination of prefill).

FLOPs: QK^T and PV over the causal lower triangle, diagonal included:
4·B·Hq·D·S(S+1)/2.  Bytes: q and the output once, k and v once per kv
head, in the compute dtype.  Masked tiles, the softmax and the rescaling
are not counted, so an implementation that skips them reads the same
work.
"""
from __future__ import annotations

#: the jitted function whose `pallas_call` is the kernel: its device
#: ops in the trace are named after it
TRACE_NAMES = ("flash_attention",)


def work(b: int, s: int, hq: int, hkv: int, d: int, itemsize: int):
    """(FLOPs, bytes) of one causal self-attention call over s positions."""
    flops = 4 * b * hq * d * s * (s + 1) // 2
    nbytes = itemsize * b * s * d * (2 * hq + 2 * hkv)
    return flops, nbytes


def call(arch: dict, plan: dict, gen, program: str):
    """Shapes of the kernel's calls in that program; None where it has none."""
    if program != "serve_prefill":
        return None
    return dict(b=gen.batch, s=gen.prompt_tokens, hq=arch["n_heads"],
                hkv=arch["n_kv_heads"], d=arch["d_head"],
                itemsize=2 if plan["compute_dtype"] == "bfloat16" else 4)

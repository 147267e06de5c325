"""Work of one call of the ssd kernel (Mamba-2's chunked state-space
scan, the 'pallas' SSM destination of prefill).

FLOPs, the chunked form of arXiv:2405.21060 per chunk of Q positions:
C·B^T over the causal triangle once per chunk (B and C are shared by the
heads of the one group), and per head the triangle times x, the carried
state's contribution C·h, and the chunk's state x^T·B.  Bytes: x, the
output, B and C in the compute dtype; dt and the final state in float32.
"""
from __future__ import annotations

#: the jitted function whose `pallas_call` is the kernel: its device
#: ops in the trace are named after it
TRACE_NAMES = ("ssd_pallas",)


def ssd_flops(b: int, s: int, h: int, p: int, n: int, chunk: int) -> int:
    tri = chunk * (chunk + 1) // 2
    chunks = b * (s // chunk)
    return chunks * (2 * n * tri + h * (2 * p * tri + 4 * chunk * n * p))


def work(b: int, s: int, h: int, p: int, n: int, chunk: int, itemsize: int):
    """(FLOPs, bytes) of one call over b sequences of s positions."""
    nbytes = (itemsize * b * s * (2 * h * p + 2 * n)
              + 4 * b * s * h + 4 * b * h * p * n)
    return ssd_flops(b, s, h, p, n, chunk), nbytes


def call(arch: dict, plan: dict, gen, program: str):
    """Shapes of the kernel's calls in that program; None where it has none."""
    if program != "serve_prefill":
        return None
    di = arch["ssm_expand"] * arch["d_model"]
    s = gen.prompt_tokens
    return dict(b=gen.batch, s=s, h=di // arch["ssm_headdim"],
                p=arch["ssm_headdim"], n=arch["ssm_state"],
                chunk=min(arch["ssm_chunk"], s),
                itemsize=2 if plan["compute_dtype"] == "bfloat16" else 4)

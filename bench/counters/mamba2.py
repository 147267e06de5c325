"""Model FLOPs one call of a Mamba-2 serving program requires.

Counted: the in and out projections and the tied head as matmuls (2 FLOPs
a multiply-add), the depthwise conv, and the state-space mixing.  Prefill
counts the chunked (SSD) form the paper computes, shared with the ssd
kernel's count; a decode step counts the recurrence, 5·P·N per head
(decay, input outer product, add, and the C contraction).  Prefill
computes logits for the last position only.  Norms, gates and the
embedding gather are not counted.
"""
from __future__ import annotations

from kernels.ssd import ssd_flops


def _dims(a: dict):
    d = a["d_model"]
    di = a["ssm_expand"] * d
    return d, di, a["ssm_state"], di // a["ssm_headdim"], a["ssm_headdim"]


def _per_token(a: dict) -> int:
    d, di, n, h, _ = _dims(a)
    proj = 2 * d * (2 * di + 2 * n + h) + 2 * di * d
    conv = 2 * a["ssm_conv"] * (di + 2 * n)
    return proj + conv


def flops(arch: dict, gen, program: str, pos: int) -> float:
    """FLOPs of one call: `program` is "prefill" (pos = prompt length) or
    "decode" (pos = the position of the token it feeds)."""
    layers, b = arch["n_layers"], gen.batch
    d, _, n, h, p = _dims(arch)
    head = 2 * d * arch["vocab_size"]
    if program == "prefill":
        s = pos
        chunk = min(arch["ssm_chunk"], s)
        mix = ssd_flops(b, s, h, p, n, chunk)
        return layers * (b * s * _per_token(arch) + mix) + b * head
    if program == "decode":
        return b * (layers * (_per_token(arch) + 5 * h * p * n) + head)
    raise ValueError(program)

"""Model FLOPs one call of a Granite-4.0-H serving program requires.

Counted, as 2 FLOPs a multiply-add: every layer's MLP; the Mamba-2
layers' in and out projections, their depthwise conv and their
state-space mixing (prefill: the chunked SSD form, shared with the ssd
kernel's count; a decode step: the recurrence, 5·P·N per head); the
attention layers' projections and attention over the causal lower
triangle (QK^T and PV; a decode step at position `pos` attends over
pos + 1 keys); the tied head at the last position only.  Norms, gates,
softmax and the embedding gather are not counted.
"""
from __future__ import annotations

from kernels.ssd import ssd_flops


def _kinds(a: dict) -> list:
    unit = a["layer_pattern"]
    return [unit[i % len(unit)] for i in range(a["n_layers"])]


def _per_token(a: dict, kind: str) -> int:
    d = a["d_model"]
    mlp = 6 * d * a["d_ff"]
    if kind == "attn":
        hq, hkv, dh = a["n_heads"], a["n_kv_heads"], a["d_head"]
        return mlp + 2 * (2 * d * hq * dh + 2 * d * hkv * dh)
    di, n = a["ssm_expand"] * d, a["ssm_state"]
    h = di // a["ssm_headdim"]
    proj = 2 * d * (2 * di + 2 * n + h) + 2 * di * d
    return mlp + proj + 2 * a["ssm_conv"] * (di + 2 * n)


def flops(arch: dict, gen, program: str, pos: int) -> float:
    """FLOPs of one call: `program` is "prefill" (pos = prompt length) or
    "decode" (pos = the position of the token it feeds)."""
    b = gen.batch
    d, hq, dh = arch["d_model"], arch["n_heads"], arch["d_head"]
    di, n, p = arch["ssm_expand"] * d, arch["ssm_state"], arch["ssm_headdim"]
    h = di // p
    head = 2 * d * arch["vocab_size"]
    if program not in ("prefill", "decode"):
        raise ValueError(program)
    total = 0
    for kind in _kinds(arch):
        if program == "prefill":
            s = pos
            if kind == "attn":
                mix = b * 4 * hq * dh * s * (s + 1) // 2
            else:
                mix = ssd_flops(b, s, h, p, n, min(arch["ssm_chunk"], s))
            total += b * s * _per_token(arch, kind) + mix
        else:
            mix = 4 * hq * dh * (pos + 1) if kind == "attn" else 5 * h * p * n
            total += b * (_per_token(arch, kind) + mix)
    return total + b * head

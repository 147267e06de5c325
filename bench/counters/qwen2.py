"""Model FLOPs one call of a Qwen2 serving program requires.

Counted: every matmul with its bias-free multiply-adds as 2 FLOPs, and
attention over the causal lower triangle (QK^T and PV).  Prefill computes
logits for the last position only; a decode step at position `pos`
attends over pos + 1 keys.  Norms, RoPE, softmax and the embedding
gather are not counted.
"""
from __future__ import annotations


def _per_token(a: dict) -> int:
    d, hq, hkv, dh, f = (a["d_model"], a["n_heads"], a["n_kv_heads"],
                         a["d_head"], a["d_ff"])
    return 2 * (d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * f)


def flops(arch: dict, gen, program: str, pos: int) -> float:
    """FLOPs of one call: `program` is "prefill" (pos = prompt length) or
    "decode" (pos = the position of the token it feeds)."""
    layers, b = arch["n_layers"], gen.batch
    hq, dh, d, v = arch["n_heads"], arch["d_head"], arch["d_model"], \
        arch["vocab_size"]
    head = 2 * d * v
    if program == "prefill":
        s = pos
        attn = 4 * hq * dh * s * (s + 1) // 2
        return b * (layers * (s * _per_token(arch) + attn) + head)
    if program == "decode":
        attn = 4 * hq * dh * (pos + 1)
        return b * (layers * (_per_token(arch) + attn) + head)
    raise ValueError(program)

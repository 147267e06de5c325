"""Granite-4.0-H-Micro [hybrid] — Mamba-2 and GQA attention, 9:1.

40L d_model=2048 vocab=100352, tied embedding [ibm-granite/granite-4.0-h-micro
config.json, model_type granitemoehybrid].  The stack is four periods of
the unit (M, M, M, M, M, A, M, M, M, M): Mamba-2 mixers of 64 heads x 64,
state 128, one group, conv 4 with bias, chunk 256, silu on all of xBC;
attention of 32 query and 8 KV heads x 64 with no positional encoding.
Every layer of either kind is followed by a SwiGLU MLP of width 8192.
Granite's muP multipliers: embedding x12, each sublayer's output x0.22,
softmax scale 1/64, logits /8; RMSNorm eps 1e-5.  Four full-attention
layers hold a KV cache as long as the context, so long_500k (past the
131072 positions the config declares) is skipped.
"""
from repro.configs.base import ArchConfig, PlanConfig, register

UNIT = ("ssm", "ssm", "ssm", "ssm", "ssm", "attn", "ssm", "ssm", "ssm", "ssm")

GRANITE = dict(
    family="hybrid",
    layer_pattern=UNIT,
    tie_embeddings=True,
    rope=False,
    norm_eps=1e-5,
    embed_scale=12.0,
    residual_scale=0.22,
    attn_scale=0.015625,
    logit_scale=0.125,
    ssm_act_bc=True,
    skip_shapes={"long_500k": (
        "524288 positions exceed the 131072 the config declares; its four "
        "full-attention layers would each hold a KV cache that long")},
)

FULL = ArchConfig(
    name="granite-4.0-h-micro",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab_size=100352,
    ssm_state=128,
    ssm_expand=2,
    ssm_headdim=64,
    ssm_conv=4,
    ssm_chunk=256,
    plan=PlanConfig(remat="full", microbatches=4),
    **GRANITE,
)

REDUCED = ArchConfig(
    name="granite-4.0-h-micro",
    n_layers=10,         # one unit
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    vocab_size=128,
    ssm_state=16,
    ssm_expand=2,
    ssm_headdim=16,
    ssm_conv=4,
    ssm_chunk=16,
    plan=PlanConfig(remat="none", attn_chunk=32),
    **GRANITE,
)

register(FULL, REDUCED)

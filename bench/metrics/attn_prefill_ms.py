"""Device time per prefill call (`serve_prefill`) in the attention
mixers: the self time of the ops whose scope path has `attn`, the KV
cache's writes included (`bench/scope_ms.py`).  Beside `ssm_prefill_ms`
it says which kind of mixer sets the pace of a hybrid's prefill."""
from scope_ms import scope_ms

NAME = "attn_prefill_ms"
UNIT = "ms"
LAYER = "model"
MOVES = "ttft_p95_ms"


def read(ctx):
    return scope_ms(ctx, "serve_prefill", "attn")

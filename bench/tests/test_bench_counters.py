"""Kernel work counts and model FLOP counters against hand-worked calls."""
import pytest

from registry import BENCH_DIR, load_module
from traffic import LockstepWaves


def kernel(name):
    return load_module(BENCH_DIR / "kernels" / f"{name}.py")


@pytest.mark.parametrize("name, shape, want", [
    # 4·B·Hq·D·S(S+1)/2 = 4·1·2·8·10; bytes 2·B·S·D·(2Hq + 2Hkv) = 2·32·6
    ("flash_attention", dict(b=1, s=4, hq=2, hkv=1, d=8, itemsize=2),
     (640, 384)),
    # 6·T·d·F = 6·2·4·8; bytes 2·(3·d·F + 2·T·d) = 2·(96 + 16)
    ("swiglu", dict(t=2, d=4, f=8, itemsize=2), (384, 224)),
    # 2 chunks of 4 (triangle 10): 2·(2·N·10 + H·(2·P·10 + 4·Q·N·P))
    #   = 2·(60 + 2·(80 + 192)); bytes 2·B·S·(2HP + 2N) + 4·B·S·H + 4·B·H·P·N
    ("ssd", dict(b=1, s=8, h=2, p=4, n=3, chunk=4, itemsize=2),
     (1208, 512)),
])
def test_kernel_work(name, shape, want):
    assert kernel(name).work(**shape) == want


def gen(batch=1, prompt=3, out=2):
    return LockstepWaves(batch=batch, prompt_tokens=prompt,
                         output_tokens=out, token_ids_below=10, seed=0)


QWEN = dict(n_layers=1, d_model=4, n_heads=2, n_kv_heads=1, d_head=2,
            d_ff=8, vocab_size=10)


def test_qwen2_counter():
    c = load_module(BENCH_DIR / "counters" / "qwen2.py")
    # per token 2·(4·2·2 + 2·4·1·2 + 2·2·4 + 3·4·8) = 288; causal attention
    # over 3 positions 4·2·2·6 = 96; head for the last position 2·4·10 = 80
    assert c.flops(QWEN, gen(), "prefill", 3) == 3 * 288 + 96 + 80
    # a decode step at position 3 attends over 4 keys: 4·2·2·4 = 64
    assert c.flops(QWEN, gen(batch=2), "decode", 3) == 2 * (288 + 64 + 80)


def test_mamba2_counter_shares_the_ssd_count():
    c = load_module(BENCH_DIR / "counters" / "mamba2.py")
    arch = dict(n_layers=1, d_model=2, ssm_expand=2, ssm_state=3,
                ssm_headdim=2, ssm_conv=4, ssm_chunk=4, vocab_size=10)
    # d=2, Di=4, N=3, H=2, P=2: projections 2·2·(8+6+2) + 2·4·2 = 80,
    # conv 2·4·(4+6) = 80, head 2·2·10 = 40
    ssd = kernel("ssd").ssd_flops(1, 8, 2, 2, 3, 4)
    assert c.flops(arch, gen(prompt=8), "prefill", 8) == 8 * 160 + ssd + 40
    assert c.flops(arch, gen(), "decode", 8) == 160 + 5 * 2 * 2 * 3 + 40

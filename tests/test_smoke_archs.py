"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
assert output shapes + no NaNs (deliverable f)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.models.model import Model
from repro.models import transformer as T
from repro.train.step import make_opt_init, make_train_step

ARCHS = [a for a in list_archs() if not a.startswith("tiny")]


def _batch(cfg, key, b=2, s=32):
    ks = jax.random.split(key, 3)
    batch = {"targets": jax.random.randint(ks[0], (b, s), 0, cfg.vocab_size)}
    if cfg.frontend == "audio_frames":
        batch["features"] = jax.random.normal(ks[1], (b, s, cfg.d_model),
                                              jnp.bfloat16)
    else:
        batch["tokens"] = jax.random.randint(ks[1], (b, s), 0,
                                             cfg.vocab_size)
    if cfg.frontend == "vision_patches":
        batch["patch_embeds"] = jax.random.normal(
            ks[2], (b, cfg.n_patches, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch, rng_key):
    cfg = get_config(arch, reduced=True)
    model = Model(cfg)
    params = model.init(rng_key)
    b, s = 2, 32
    batch = _batch(cfg, rng_key, b, s)
    logits, _, aux = T.forward(params, batch, cfg, cfg.plan)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step(arch, rng_key):
    cfg = get_config(arch, reduced=True)
    cfg = dataclasses.replace(cfg, plan=cfg.plan.replace(microbatches=1))
    model = Model(cfg)
    params = model.init(rng_key)
    step = jax.jit(make_train_step(model))
    opt = make_opt_init(model)(params)
    batch = _batch(cfg, rng_key)
    p2, o2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), params, p2)
    assert max(jax.tree.leaves(d)) > 0.0


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_registered(arch):
    cfg = get_config(arch)
    # exact published numbers spot-checks
    table = {
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
        "qwen2-7b": (28, 3584, 28, 4, 18944, 152064),
        "granite-20b": (52, 6144, 48, 1, 24576, 49152),
        "llama3-405b": (126, 16384, 128, 8, 53248, 128256),
        "stablelm-12b": (40, 5120, 32, 8, 13824, 100352),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "moonshot-v1-16b-a3b": (48, 2048, 16, 16, 1408, 163840),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "mamba2-1.3b": (48, 2048, 0, 0, 0, 50280),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
    }
    L, d, h, kv, ff, v = table[arch]
    assert cfg.n_layers == L and cfg.d_model == d
    assert cfg.n_heads == h and cfg.n_kv_heads == kv
    assert cfg.d_ff == ff and cfg.vocab_size == v


def test_moe_configs():
    m1 = get_config("moonshot-v1-16b-a3b").moe
    assert (m1.n_experts, m1.top_k) == (64, 6)
    m2 = get_config("granite-moe-1b-a400m").moe
    assert (m2.n_experts, m2.top_k) == (32, 8)


def test_param_counts_plausible():
    # llama3-405b should be ~405B params; moonshot ~16B total / ~3B active
    n = get_config("llama3-405b").param_count()
    assert 3.8e11 < n < 4.3e11, n
    # the assigned config numbers (64e x 1408 ff x 48L) yield ~28B total;
    # the "A3B" active count is the anchor: ~4B active
    cfg = get_config("moonshot-v1-16b-a3b")
    assert 1.0e10 < cfg.param_count() < 3.2e10
    assert 2.5e9 < cfg.active_param_count() < 5.5e9
    assert cfg.active_param_count() < 0.2 * cfg.param_count()

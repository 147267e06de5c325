"""Prefill + decode must match the teacher-forced forward pass (f32)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import MoEConfig
from repro.models.model import Model
from repro.models import transformer as T

DECODABLE = ["qwen2-7b", "granite-20b", "llama3-405b", "stablelm-12b",
             "internvl2-76b", "recurrentgemma-9b", "mamba2-1.3b",
             "moonshot-v1-16b-a3b", "granite-moe-1b-a400m",
             "granite-4.0-h-micro"]


def _f32(cfg):
    plan = cfg.plan.replace(compute_dtype="float32",
                            kv_cache_dtype="float32")
    cfg = dataclasses.replace(cfg, plan=plan)
    if cfg.moe is not None:
        # raise capacity so no tokens drop (drops legitimately break
        # teacher-forced equivalence)
        cfg = dataclasses.replace(
            cfg, moe=MoEConfig(cfg.moe.n_experts, cfg.moe.top_k,
                               cfg.moe.d_ff_expert, capacity_factor=16.0))
    return cfg


@pytest.mark.parametrize("arch", DECODABLE)
def test_prefill_then_decode_matches_forward(arch, rng_key):
    cfg = _f32(get_config(arch, reduced=True))
    model = Model(cfg)
    params = model.init(rng_key)
    b, s, split = 2, 16, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.frontend == "vision_patches":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (b, cfg.n_patches, cfg.d_model),
            jnp.float32)
    full, _, _ = T.forward(params, batch, cfg, cfg.plan)

    cache = model.init_cache(b, s)
    pb = dict(batch)
    pb["tokens"] = toks[:, :split]
    last, cache = model.prefill(params, pb, cache)
    assert float(jnp.max(jnp.abs(last - full[:, split - 1]))) < 1e-3

    outs = []
    for t in range(split, s):
        lg, cache = model.decode_step(
            params, {"tokens": toks[:, t:t + 1],
                     "pos": jnp.asarray(t, jnp.int32)}, cache)
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    err = float(jnp.max(jnp.abs(dec - full[:, split:])))
    assert err < 1e-3, err


def test_sliding_window_cache_rolls(rng_key):
    """recurrentgemma decode beyond the window must match full forward
    (local attention window smaller than the sequence)."""
    cfg = get_config("recurrentgemma-9b", reduced=True)
    cfg = dataclasses.replace(
        cfg, local_window=8,
        plan=cfg.plan.replace(compute_dtype="float32",
                              kv_cache_dtype="float32"))
    model = Model(cfg)
    params = model.init(rng_key)
    b, s = 1, 24
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0,
                              cfg.vocab_size)
    full, _, _ = T.forward(params, {"tokens": toks}, cfg, cfg.plan)
    cache = model.init_cache(b, s)   # window-sized kv cache inside
    outs = []
    for t in range(s):
        lg, cache = model.decode_step(
            params, {"tokens": toks[:, t:t + 1],
                     "pos": jnp.asarray(t, jnp.int32)}, cache)
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    err = float(jnp.max(jnp.abs(dec[:, 1:] - full[:, 1:])))
    assert err < 1e-3, err

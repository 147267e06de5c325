"""The Granite-4.0-H hybrid in the program: its stack of Mamba-2 and
attention layers, each with an MLP, and the fields its block adds to
`ArchConfig` (no RoPE, a norm eps, silu on all of xBC, the muP
multipliers).  Each field at its default leaves the program as it was; at
another value it changes the logits."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.models.model import Model

NAME = "granite-4.0-h-micro"

#: the block's fields, each at its non-default Granite value
GRANITE = dict(rope=False, norm_eps=1e-5, embed_scale=12.0,
               residual_scale=0.22, attn_scale=0.015625, logit_scale=0.125,
               ssm_act_bc=True)


def _f32(cfg, **plan):
    return dataclasses.replace(cfg, plan=cfg.plan.replace(
        compute_dtype="float32", param_dtype="float32",
        kv_cache_dtype="float32", **plan))


def _defaults() -> dict:
    return {f.name: f.default for f in dataclasses.fields(ArchConfig)
            if f.name in GRANITE}


def _logits(cfg, params):
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    logits, _, _ = T.forward(params, {"tokens": toks}, cfg, cfg.plan)
    return np.asarray(logits)


def test_registered_with_the_published_block():
    cfg = get_config(NAME)
    assert {k: getattr(cfg, k) for k in GRANITE} == GRANITE
    assert cfg.layer_kinds().count("attn") == 4
    assert [i for i, k in enumerate(cfg.layer_kinds()) if k == "attn"] == [
        5, 15, 25, 35]
    assert (cfg.d_head, cfg.ssm_nheads, cfg.d_inner) == (64, 64, 4096)
    assert T.unit_structure(cfg) == (cfg.layer_pattern, 4, ())


def test_param_count_is_three_point_one_nine_billion():
    assert abs(get_config(NAME).param_count() - 3.19e9) <= 0.01e9


@pytest.mark.parametrize("arch, reduced", [
    (NAME, False), (NAME, True), ("mamba2-1.3b", False),
    ("mamba2-1.3b", True)])
def test_param_count_is_what_init_makes(arch, reduced):
    """The analytic count, a hybrid SSM layer's norm2 and MLP and each
    Mamba-2 conv bias and dt bias included, against the weights the
    program initialises (shapes only)."""
    cfg = get_config(arch, reduced=reduced)
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == cfg.param_count()


def test_every_layer_carries_an_mlp():
    cfg = get_config(NAME, reduced=True)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    for slot in params["scan"].values():
        assert set(slot) == {"norm1", "norm2", "mixer", "mlp"}
    # a pure Mamba-2 stack keeps its layers without
    m = get_config("mamba2-1.3b", reduced=True)
    assert set(Model(m).init(jax.random.PRNGKey(0))["scan"]["l0"]) == {
        "norm1", "mixer"}


@pytest.mark.parametrize("field", sorted(GRANITE))
def test_each_field_moves_the_logits(field):
    """From a hybrid with every field at its default, each field set to
    Granite's value alone changes the logits."""
    base = _f32(dataclasses.replace(get_config(NAME, reduced=True),
                                    **_defaults()))
    params = Model(base).init(jax.random.PRNGKey(0))
    want = _logits(base, params)
    got = _logits(dataclasses.replace(base, **{field: GRANITE[field]}),
                  params)
    if field == "logit_scale":
        # 1/8 divides the logits, exactly: a power of two
        np.testing.assert_array_equal(got, want / 8)
    assert np.abs(got - want).max() > 1e-3 * np.abs(want).max()


def test_default_attention_scale_is_one_over_root_d_head():
    base = _f32(dataclasses.replace(get_config(NAME, reduced=True),
                                    **_defaults()))
    params = Model(base).init(jax.random.PRNGKey(0))
    same = dataclasses.replace(base, attn_scale=base.d_head ** -0.5)
    np.testing.assert_array_equal(_logits(same, params),
                                  _logits(base, params))


def test_serving_reads_every_slots_mlp_from_the_stacks():
    """With the Pallas MLP, each of the unit's ten MLPs, the Mamba-2
    slots' too, is read out of the stacked weights in prefill and in
    decode: ten stacked swiglu calls in each program."""
    cfg = _f32(get_config(NAME, reduced=True), mlp_impl="pallas")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(8, 24)
    assert set(T.stacked_mlps(params["scan"], cfg, cfg.plan, cache,
                              None)) == {f"l{i}" for i in range(10)}
    reg = obs.set_metrics(obs.MetricsRegistry())
    try:
        toks = jnp.zeros((8, 16), jnp.int32)
        _, cache = jax.jit(model.prefill)(params, {"tokens": toks}, cache)
        _, cache = jax.jit(model.decode_step)(
            params, {"tokens": toks[:, :1], "pos": jnp.int32(16)}, cache)
        counts = {k: v.get("value") for k, v in reg.to_json().items()}
    finally:
        obs.set_metrics(None)
    assert counts == {"kernel_stacked_swiglu": 20.0}

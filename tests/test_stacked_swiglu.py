"""The swiglu kernel reading a layer's panels straight out of the stacked
scan weights: the kernel against itself on sliced panels, the serving
programs against the sliced and the XLA paths, training's gradient, and
the counter that says when the stacked path was taken."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import obs
from repro.configs.base import get_config
from repro.kernels import ops, ref
from repro.kernels.swiglu import swiglu_pallas
from repro.models import transformer as T
from repro.models.model import Model
from repro.parallel.sharding import make_rules

# batch (a decode step's 8 rows are the kernel's least block), prefill
# length, cache length
B, PROMPT, TOTAL = 8, 8, 12


def _stacks(n_layers, t, d, f, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k[0], (t, d))
    wi = jax.random.normal(k[1], (n_layers, d, f)) * 0.2
    wg = jax.random.normal(k[2], (n_layers, d, f)) * 0.2
    wo = jax.random.normal(k[3], (n_layers, f, d)) * 0.2
    return x, wi, wg, wo


@pytest.mark.parametrize("block_f", [16, 64])      # splits d_ff, or not
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_stacked_kernel_equals_kernel_on_sliced_panels(layer, block_f):
    x, wi, wg, wo = _stacks(3, 32, 16, 64)
    y = swiglu_pallas(x, wi, wg, wo, jnp.int32(layer), block_t=16,
                      block_f=block_f)
    y0 = swiglu_pallas(x, wi[layer], wg[layer], wo[layer], block_t=16,
                       block_f=block_f)
    np.testing.assert_array_equal(y, y0)


@pytest.mark.parametrize("layer", [0, 2])
def test_stacked_op_falls_back_on_the_layer_it_names(layer):
    # 4 tokens tile no block of 8: the oracle runs, on that layer
    x, wi, wg, wo = _stacks(3, 4, 16, 64)
    y = ops.fused_swiglu(x, wi, wg, wo, jnp.int32(layer))
    y0 = ref.swiglu_ref(x, wi[layer], wg[layer], wo[layer])
    np.testing.assert_array_equal(y, y0)


def _config(name, **plan):
    """Tiny float32 configs on the Pallas MLP: dense (its d_ff split into
    two kernel blocks) and a hybrid unit of two recurrent layers and one
    attention layer, each with a swiglu MLP."""
    if name == "dense":
        cfg = dataclasses.replace(get_config("qwen2-7b", reduced=True),
                                  d_ff=384)
    else:
        cfg = dataclasses.replace(get_config("recurrentgemma-9b",
                                             reduced=True), act="swiglu")
    return dataclasses.replace(cfg, plan=cfg.plan.replace(
        mlp_impl="pallas", compute_dtype="float32", param_dtype="float32",
        kv_cache_dtype="float32", **plan))


def _serve(cfg, params):
    """Prefill logits, then decode logits, then the cache."""
    model = Model(cfg)
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode_step)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, TOTAL), 0,
                              cfg.vocab_size)
    last, cache = prefill(params, {"tokens": toks[:, :PROMPT]},
                          model.init_cache(B, TOTAL))
    outs = [last]
    for t in range(PROMPT, TOTAL):
        lg, cache = decode(params, {"tokens": toks[:, t:t + 1],
                                    "pos": jnp.asarray(t, jnp.int32)}, cache)
        outs.append(lg)
    return jnp.stack(outs, axis=1), cache


@pytest.mark.parametrize("name", ["dense", "hybrid"])
def test_serving_on_the_stacked_path(name, monkeypatch):
    cfg = _config(name)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    reg = obs.set_metrics(obs.MetricsRegistry())
    try:
        logits, cache = _serve(cfg, params)
        assert reg.counter("kernel_stacked_swiglu").value > 0
    finally:
        obs.set_metrics(None)

    # the XLA MLP, within the decode-consistency tolerance
    xla = dataclasses.replace(cfg, plan=cfg.plan.replace(mlp_impl="xla"))
    logits_x, cache_x = _serve(xla, params)
    np.testing.assert_allclose(logits, logits_x, atol=1e-3, rtol=1e-3)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_x)):
        np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)

    # the same kernel on panels the scan slices: the same bits
    monkeypatch.setattr(T, "stacked_mlps", lambda *a: {})
    logits_s, cache_s = _serve(cfg, params)
    np.testing.assert_array_equal(logits, logits_s)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_s)):
        np.testing.assert_array_equal(a, b)


def test_training_gradient_matches_the_oracle():
    cfg = _config("dense")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 16), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}

    def grads(m):
        return jax.jit(jax.grad(lambda p: m.loss(p, batch)[0]))(params)
    xla = model.with_plan(cfg.plan.replace(mlp_impl="xla"))
    for a, b in zip(jax.tree.leaves(grads(model)), jax.tree.leaves(grads(xla))):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)


def _traces(cfg, rules=None):
    """`kernel_stacked_swiglu` after tracing one prefill, one decode step
    and one training forward."""
    model = Model(cfg)
    params = model.abstract_params()
    cache = model.abstract_cache(B, TOTAL)
    tokens = jax.ShapeDtypeStruct((B, PROMPT), jnp.int32)
    counts = {}
    reg = obs.set_metrics(obs.MetricsRegistry())
    try:
        jax.eval_shape(lambda p, t, c: model.prefill(
            p, {"tokens": t}, c, rules), params, tokens, cache)
        counts["prefill"] = reg.counter("kernel_stacked_swiglu").value
        jax.eval_shape(lambda p, t, c: model.decode_step(
            p, {"tokens": t[:, :1], "pos": jnp.int32(PROMPT)}, c, rules),
            params, tokens, cache)
        counts["decode"] = reg.counter("kernel_stacked_swiglu").value
        jax.eval_shape(lambda p, t: model.loss(
            p, {"tokens": t, "targets": t}, rules), params, tokens)
        counts["train"] = reg.counter("kernel_stacked_swiglu").value
    finally:
        obs.set_metrics(None)
    return counts


def test_counter_counts_each_serving_program_and_nothing_else():
    # one MLP in the dense unit: one count a traced serving program
    assert _traces(_config("dense")) == {"prefill": 1, "decode": 2,
                                         "train": 2}
    # three in the hybrid unit; the two of its unrolled tail are sliced
    assert _traces(_config("hybrid")) == {"prefill": 3, "decode": 6,
                                          "train": 6}


@pytest.mark.parametrize("case", ["sharded", "param_dtype", "xla_mlp",
                                  "unrolled"])
def test_counter_stays_zero_off_the_stacked_path(case):
    cfg, rules = _config("dense"), None
    if case == "sharded":
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))
        rules = make_rules(cfg, mesh, cfg.plan)
    elif case == "param_dtype":
        cfg = dataclasses.replace(cfg, plan=cfg.plan.replace(
            param_dtype="bfloat16"))
    elif case == "xla_mlp":
        cfg = dataclasses.replace(cfg, plan=cfg.plan.replace(mlp_impl="xla"))
    else:
        cfg = dataclasses.replace(cfg, plan=cfg.plan.replace(
            scan_layers=False))
    assert _traces(cfg, rules) == {"prefill": 0, "decode": 0, "train": 0}

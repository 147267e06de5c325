"""The plain references against the program's forward pass, on the CPU at
reduced widths: a wrong yardstick fails here, not on the chip.  Each
family's reference gives its widths as `SMALL_ARCH`; every configuration
of `BENCHMARK.json` is checked, through its first cell."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from registry import ROOT, load_cell, load_json
from repro.models import transformer as T


def one_cell_a_config() -> list:
    first = {}
    for w in load_json(ROOT / "BENCHMARK.json")["workloads"]:
        first.setdefault(w["config"], w["name"])
    return sorted(first.values())


def small_arch(cell_name: str) -> dict:
    cell = load_cell(cell_name)
    ref = cell.reference()
    assert hasattr(ref, "SMALL_ARCH"), (
        f"bench/reference/{cell.config['reference']}.py has no SMALL_ARCH: "
        "add a dict of small arch widths at which the CPU tests check it "
        "against the program's forward pass")
    return ref.SMALL_ARCH


def small_f32(cell_name: str, **departures) -> dict:
    config = dict(load_cell(cell_name).config)
    config["arch"] = dict(config["arch"], **small_arch(cell_name))
    config["plan"] = dict(config["plan"], compute_dtype="float32",
                          param_dtype="float32", kv_cache_dtype="float32",
                          attn_impl="xla", mlp_impl="xla", ssm_impl="xla")
    config["departures"] = {k: dict(v, **departures.get(k, {}))
                            for k, v in config["departures"].items()}
    return config


def logits_both(cell_name: str, config: dict, seq: int = 24):
    ref = load_cell(cell_name).reference()
    params = jax.jit(lambda k: ref.make_params(config, k))(
        jax.random.PRNGKey(7))
    model = harness.build_model(config)
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, seq), 0,
                              config["arch"]["vocab_size"])
    with jax.default_matmul_precision("highest"):
        prog, _, _ = T.forward(params, {"tokens": toks}, model.cfg,
                               model.plan)
    want = ref.last_logits(config, params, np.asarray(toks), n_last=seq)
    return np.asarray(prog, np.float32), np.asarray(want)


@pytest.mark.parametrize("cell_name", one_cell_a_config())
def test_reference_matches_program_forward(cell_name):
    config = small_f32(cell_name)
    got, want = logits_both(cell_name, config)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale


def test_mamba2_departure_is_visible():
    """The published silu on B and C moves the logits a hundred times
    further than rounding does: the departure the configuration records
    is real, and the reference follows what is run."""
    name = "mamba2-1.3b.prompt_heavy"
    got, want = logits_both(name, small_f32(name))
    sound = np.abs(got - want).max()
    got, want = logits_both(name, small_f32(name,
                                            silu_on_BC={"as_run": True}))
    assert np.abs(got - want).max() > 100 * sound


def test_mamba2_published_equations_stay_available():
    """`published=True` computes the published block: the same logits as
    a configuration whose program ran it."""
    name = "mamba2-1.3b.prompt_heavy"
    config = small_f32(name)
    fixed = small_f32(name, silu_on_BC={"as_run": True},
                      norm_eps={"as_run": 1e-5})
    ref = load_cell(name).reference()
    params = jax.jit(lambda k: ref.make_params(config, k))(
        jax.random.PRNGKey(5))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (1, 12), 0,
                                         config["arch"]["vocab_size"]))
    pub = np.asarray(ref.last_logits(config, params, toks, 12,
                                     published=True))
    as_run = np.asarray(ref.last_logits(config, params, toks, 12))
    np.testing.assert_array_equal(
        pub, np.asarray(ref.last_logits(fixed, params, toks, 12)))
    assert np.abs(pub - as_run).max() > 1e-3 * np.abs(pub).max()


def test_fp8_control_departs_from_reference():
    """The control computes the same logits with float8 operands: close,
    but far past float32 rounding."""
    name = "qwen2-7b.prompt_heavy"
    config = small_f32(name)
    ref = load_cell(name).reference()
    params = jax.jit(lambda k: ref.make_params(config, k))(
        jax.random.PRNGKey(3))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (1, 16), 0,
                                         config["arch"]["vocab_size"]))
    hi = np.asarray(ref.last_logits(config, params, toks, 16))
    lo = np.asarray(ref.last_logits(config, params, toks, 16, mode="fp8"))
    err = np.abs(hi - lo).max() / np.abs(hi).max()
    assert 1e-3 < err < 0.5


def test_served_gap_reads_the_reference_logit():
    from reference.common import served_gaps
    logits = jnp.asarray([[[0.0, 2.0, 1.5], [3.0, 1.0, 0.0]]])
    gaps = np.asarray(served_gaps(logits, jnp.asarray([[2, 0]])))
    np.testing.assert_allclose(gaps, [[0.5, 0.0]])

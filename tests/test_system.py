"""End-to-end behaviour of the paper's system (the full pipeline wired up).

These are the top-level invariants: offload search improves the incumbent,
the selected plan actually runs (train step executes under it), the MRI-Q
pipeline selects an offload pattern that wins on both time and energy, and
the narrowing funnel's verdicts are consistent with measurements.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_config
from repro.core import (GAConfig, Verifier, narrow_candidates, run_ga,
                        select_destination)
from repro.core.destinations import Requirement
from repro.core.fitness import fitness
from repro.core.plan import PlanGenome
from repro.models.model import Model
from repro.train.step import make_opt_init, make_train_step


def test_offload_search_end_to_end_improves_and_runs(rng_key):
    """GA-search a plan on the production-scale config, then execute a real
    train step under the found plan on the reduced config."""
    cfg_full = get_config("qwen2-7b")
    v = Verifier(cfg_full, "train_4k", n_chips=256, mode="analytic")
    incumbent = v.measure(PlanGenome.from_plan(cfg_full, "train",
                                               cfg_full.plan))
    res = run_ga(cfg_full, "train", v,
                 GAConfig(population=8, generations=4, seed=11))
    assert res.best_measurement.fitness() >= incumbent.fitness()

    # the found plan must be executable: run it on the reduced config
    plan = res.best.to_plan().replace(microbatches=1)
    cfg_small = dataclasses.replace(get_config("qwen2-7b", reduced=True),
                                    plan=plan)
    model = Model(cfg_small)
    params = model.init(rng_key)
    step = jax.jit(make_train_step(model))
    opt = make_opt_init(model)(params)
    batch = {"tokens": jnp.ones((2, 32), jnp.int32),
             "targets": jnp.ones((2, 32), jnp.int32)}
    _, _, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_mriq_pipeline_selects_offload():
    """Paper §4 logic: with the paper's measured node watts, the offloaded
    pattern must dominate CPU-only on the fitness value."""
    f_cpu = fitness(14.0, 121.0)          # paper's CPU-only measurement
    f_off = fitness(2.0, 111.0)           # paper's FPGA measurement
    assert f_off > f_cpu
    # energy ordering too (1690 -> 223 W*s)
    assert 2.0 * 111.0 < 14.0 * 121.0


def test_narrowing_verdicts_are_measurement_consistent():
    """Patterns surviving the static funnel must not be measurement
    disasters: each measured candidate stays within 3x of the incumbent
    fitness (the funnel's job is to pre-filter the losers)."""
    cfg = get_config("recurrentgemma-9b")
    shape = SHAPES["train_4k"]
    v = Verifier(cfg, "train_4k", n_chips=256, mode="analytic")
    base = v.measure(PlanGenome.from_plan(cfg, "train", cfg.plan))
    rep = narrow_candidates(cfg, shape)
    assert rep.candidates
    for cand in rep.candidates:
        plan = dataclasses.replace(cfg.plan, **cand.overrides)
        m = v.measure_plan(plan, "train")
        assert m.fitness() > base.fitness() / 3.0, cand.name


def test_destination_selection_respects_cost_ordering():
    """Cheapest-first verification (paper §3.3): early exit avoids the
    expensive rungs entirely and saves verification trials."""
    cfg = get_config("stablelm-12b")
    v1 = Verifier(cfg, "train_4k", n_chips=256, mode="analytic")
    sel_loose = select_destination(cfg, "train", v1,
                                   Requirement(max_seconds=1e9),
                                   GAConfig(population=4, generations=2))
    v2 = Verifier(cfg, "train_4k", n_chips=256, mode="analytic")
    sel_tight = select_destination(cfg, "train", v2,
                                   Requirement(max_seconds=1e-9),
                                   GAConfig(population=4, generations=2))
    assert v1.n_trials < v2.n_trials          # early exit saved trials
    assert sel_loose.early_exit and not sel_tight.early_exit


def test_plan_genome_covers_all_assigned_families():
    """Every assigned arch has a non-empty, family-appropriate gene space."""
    from repro.configs import list_archs
    for arch in [a for a in list_archs() if not a.startswith("tiny")]:
        cfg = get_config(arch)
        genes = PlanGenome.gene_names(cfg, "train")
        assert genes, arch
        kinds = set(cfg.layer_kinds())
        if cfg.family == "ssm":
            assert "ssm_impl" in genes and "attn_impl" not in genes
        if cfg.family == "hybrid":
            assert "attn_impl" in genes
        # a recurrent site is a gene wherever a layer of its kind runs
        assert ("rglru_impl" in genes) == ("rec" in kinds), arch
        assert ("ssm_impl" in genes) == ("ssm" in kinds), arch
        if cfg.moe is not None:
            assert "mlp_impl" in genes

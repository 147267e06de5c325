"""Compile rehearsal: every Pallas kernel, compiled for a described TPU
v5e at published widths, with the blocks ``repro.kernels.ops`` picks.

Nothing runs; the chip's compiler refuses here what it would refuse on
the chip (unaligned blocks, VMEM overflow, unsupported lowering).  The
topology is described inside a fixture, so only the worker that runs
these tests loads the TPU compiler.
"""
import dataclasses
import importlib.util
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import sites as sites_mod
from repro.kernels.sites import sites

SITES = {s.name: s for s in sites()}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(SITES))
def test_kernel_compiles_for_v5e(name, one_chip):
    site = SITES[name]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in site.operands]
    # chip_smoke.py checks the kernels at the highest matmul precision,
    # which needs more VMEM than the default
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(site.kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- names on the device trace ---------------------------------------------

#: the name each kernel's `pallas_call` gives its HLO instruction (and so
#: its op on the device trace), whatever function wraps the call
KERNEL_NAMES = {"flash_attention": "flash_attention",
                "swiglu": "swiglu_pallas", "ssd": "ssd_pallas",
                "rglru": "rglru_pallas", "mriq": "mriq_pallas"}
BENCH_KERNELS = Path(__file__).resolve().parents[1] / "bench" / "kernels"


def _trace_names(kernel: str):
    """`TRACE_NAMES` of the benchmark's work count for the kernel, if any."""
    path = BENCH_KERNELS / f"{kernel}.py"
    if not path.is_file():
        return None
    spec = importlib.util.spec_from_file_location(f"work_{kernel}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.TRACE_NAMES


@pytest.mark.parametrize("name", sorted(SITES))
def test_kernel_instruction_takes_the_kernel_name(name, one_chip,
                                                  monkeypatch):
    # the kernel's function without its jitted wrapper, called from a
    # wrapper of another name: the instruction still takes the kernel's
    jitted = getattr(sites_mod, KERNEL_NAMES[name])
    monkeypatch.setattr(sites_mod, KERNEL_NAMES[name], jitted.__wrapped__)
    site = SITES[name]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in site.operands]

    def wrapper(*a):
        return site.kernel(*a)
    text = jax.jit(wrapper).lower(*args).compile().as_text()
    calls = re.findall(r"%([\w.-]+) = [^\n]* custom-call\([^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert calls and all(re.fullmatch(rf"{KERNEL_NAMES[name]}\.\d+", c)
                         for c in calls), calls
    names = _trace_names(name)
    if names is not None:
        assert calls[0].split(".")[0] in names


#: the program's scopes (`jax.named_scope` in `repro.models`)
SCOPES = ("embed", "layers", "layer", "attn", "kv_write", "mlp", "moe",
          "ssm", "rec", "head")
PLAN = dict(attn_impl="pallas", mlp_impl="pallas", ssm_impl="pallas",
            rglru_impl="pallas", remat="none", scan_layers=True,
            compute_dtype="bfloat16", param_dtype="bfloat16",
            kv_cache_dtype="bfloat16")
BATCH = 8                   # rows of a decode step: a whole sublane


def _tiny(name: str):
    from repro.configs.base import ArchConfig, PlanConfig, get_config
    plan = PlanConfig(**PLAN)
    if name == "dense":     # lane-wide, so that swiglu compiles
        return ArchConfig(name="tiny-dense", family="dense", n_layers=3,
                          d_model=256, n_heads=2, n_kv_heads=1, d_head=128,
                          d_ff=512, vocab_size=1024, qkv_bias=True,
                          plan=plan)
    arch = {"ssm": "mamba2-1.3b", "moe": "granite-moe-1b-a400m",
            "hybrid": "recurrentgemma-9b"}[name]
    return dataclasses.replace(get_config(arch, reduced=True), plan=plan)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Trace the kernels as the chip would (compiled, aligned blocks),
    though the default backend here is the CPU."""
    import repro.kernels as kernels
    for mod in [ops] + [getattr(kernels, m) for m in
                        ("flash_attention", "swiglu", "ssd", "rglru")]:
        monkeypatch.setattr(mod, "resolve_interpret", lambda i: False)


def _decode_hlo(cfg, one_chip) -> str:
    """The decode step of `cfg`, compiled for one v5e chip."""
    from repro.models.model import Model
    from repro.serve.engine import make_decode_step
    model = Model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((BATCH, 1), jnp.int32),
                     "pos": jax.ShapeDtypeStruct((), jnp.int32)})
    step = jax.jit(make_decode_step(model))
    return step.lower(on_chip(model.abstract_params()), batch,
                      on_chip(model.abstract_cache(BATCH, 64))
                      ).compile().as_text()


def _scopes(op_name: str) -> list:
    return [c for c in op_name.split("/") if c in SCOPES]


@pytest.mark.parametrize("name,want", [
    ("dense", {"embed", "layers", "layer", "attn", "kv_write", "mlp",
               "head"}),
    ("ssm", {"embed", "layers", "layer", "ssm", "head"}),
    ("moe", {"embed", "layers", "layer", "attn", "kv_write", "moe",
             "head"}),
    ("hybrid", {"embed", "layers", "layer", "attn", "kv_write", "rec",
                "mlp", "head"}),
])
def test_decode_step_names_its_scopes(name, want, one_chip,
                                      compiled_kernels):
    text = _decode_hlo(_tiny(name), one_chip)
    found = {s for op in re.findall(r'op_name="([^"]*)"', text)
             for s in _scopes(op)}
    assert found == want


def test_decode_swiglu_reads_the_stacked_weights(one_chip,
                                                 compiled_kernels):
    """The compiled decode step hands the swiglu kernel the stacked
    weights as the scan's loop carries them, and the layer's index: the
    panels are neither sliced nor copied out of the stacks.  The index
    itself is a 4-byte slice of the scanned layer numbers.  d_ff is wide
    enough that the compiler does not move whole stacks into VMEM, as at
    every published width."""
    cfg = dataclasses.replace(_tiny("dense"), d_ff=8192)
    text = _decode_hlo(cfg, one_chip)
    shapes = dict(re.findall(r"%([\w.-]+) = (\w+\[[\d,]*\])", text))
    call = re.search(r"%swiglu_pallas\.\d+ = [^\n]* custom-call\(([^)]*)\)",
                     text)
    assert call, "no compiled swiglu kernel in the decode step"
    operands = [o.strip().lstrip("%") for o in call.group(1).split(",")]
    index, x, *weights = operands
    n, d, f = cfg.n_layers, cfg.d_model, cfg.d_ff
    assert shapes[index] == "s32[1]", operands
    assert [shapes[w] for w in weights] == [f"bf16[{n},{d},{f}]"] * 2 + [
        f"bf16[{n},{f},{d}]"], operands
    assert all(w.startswith("get-tuple-element") for w in weights), operands
    assert not any(o.startswith(("dynamic-slice", "copy"))
                   for o in operands), operands

"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py <cell> [<cell> ...]
        [--layers N]

For each cell: the prefill and decode programs exactly as `bench/run.py`
jits them (weights, cache and inputs as shapes on one described chip),
compiled by the TPU's compiler, which refuses here what it would refuse
on the chip.  Prints each program's `memory_analysis()`, whether the
compiled prefill holds logits for every position, and the Pallas kernels
in it.  `--layers` overrides the configuration's depth, to find the
deepest cut that fits.  Nothing runs, so nothing here is a time.
"""
from __future__ import annotations

import argparse
import importlib
import os
import pkgutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))


def kernel_modules() -> list:
    """`repro.kernels` and each of its modules that binds
    `resolve_interpret`, a kernel added later among them."""
    import repro.kernels as K
    mods = [K] + [importlib.import_module(f"{K.__name__}.{m.name}")
                  for m in pkgutil.iter_modules(K.__path__)]
    return [m for m in mods if hasattr(m, "resolve_interpret")]


def compile_for_chip():
    """Make the program's kernels compile for the chip although the
    process runs on the CPU (they choose interpret mode from the
    platform when traced)."""
    def resolve(interpret):
        return False if interpret is None else interpret
    for mod in kernel_modules():
        mod.resolve_interpret = resolve


def rehearse(cell_name: str, layers: int | None, one_chip) -> None:
    import jax
    import jax.numpy as jnp

    import harness
    import traffic
    from registry import load_cell

    cell = load_cell(cell_name)
    config = dict(cell.config)
    if layers:
        config["arch"] = dict(config["arch"], n_layers=layers)
    ref = cell.reference()
    gen = traffic.generator(cell.traffic, config["token_ids_below"], 0)
    model = harness.build_model(config)
    steps = harness.make_steps(model, gen.batch, gen.cache_tokens)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda k: ref.make_params(config, k),
                                    jax.random.PRNGKey(0)))
    cache = on_chip(model.abstract_cache(
        gen.batch, harness.cache_length(model.plan, gen.cache_tokens)))
    tokens = on_chip(jax.ShapeDtypeStruct((gen.batch, gen.prompt_tokens),
                                          jnp.int32))
    tok = on_chip(jax.ShapeDtypeStruct((gen.batch,), jnp.int32))
    pos = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    n_params = sum(a.size for a in jax.tree.leaves(params))
    n_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    print(f"== {cell_name} layers={config['arch']['n_layers']}: "
          f"{n_params / 1e9:.3f}B parameters, {n_bytes / 1e9:.3f} GB",
          flush=True)
    for name, fn, args in (
            ("prefill", steps.prefill, (params, tokens, cache)),
            ("decode", steps.decode, (params, tok, pos, cache))):
        compiled = fn.lower(*args).compile()
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        v = config["arch"]["vocab_size"]
        full_logits = f"{gen.batch},{gen.prompt_tokens},{v}]" in text
        kernels = sorted({w for w in ("_attn_kernel", "_swiglu_kernel",
                                      "_ssd_kernel") if w in text})
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(f"{name}: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, "
              f"outputs {ma.output_size_in_bytes / 1e9:.3f} GB, "
              f"aliased {ma.alias_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {ma.temp_size_in_bytes / 1e9:.3f} GB, "
              f"total {total / 1e9:.3f} GB; logits at every position: "
              f"{full_logits}; kernels: {kernels}; "
              f"tpu_custom_call: {text.count('tpu_custom_call')}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    compile_for_chip()
    for c in args.cells:
        rehearse(c, args.layers, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())

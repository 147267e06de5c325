"""Readings that a cell's limit is set from, on the chip, in one process.

    python bench/calibrate.py --workload <cell> --seeds 101,102,... \
        [--control-seeds 101,102,103] [--out FILE]

For each seed: the weights from that seed, one wave of the cell's traffic
through the timed programs (the same jitted prefill and decode a run
drives, at the cell's batch and lengths), and the widest served-token gap
under the plain reference over a sample the size a run compares: the
lower reading.  For each control seed also the control: the reference
computed with float8 (e4m3) matmul operands in the program's place, the
gap of the token it puts first at the same positions: the upper reading.
Prints one JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    import harness
    from registry import load_cell

    cell = load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    config = cell.config
    ref = cell.reference()
    model = harness.build_model(config)
    make = jax.jit(lambda k: ref.make_params(config, k))
    with open(args.out or os.devnull, "w") as out:
        _readings(cell, config, ref, model, make, seeds, control, out)
    return 0


def _readings(cell, config, ref, model, make, seeds, control, out) -> None:
    import check
    import harness
    import traffic

    steps = None
    for seed in seeds:
        t0 = time.perf_counter()
        gen = traffic.generator(cell.traffic, config["token_ids_below"], seed)
        params = make(harness.seed_key(seed))
        if steps is None:
            steps = harness.make_steps(model, gen.batch, gen.cache_tokens)
            harness.warm_up(steps, params, gen)
        win = harness.Window(start=0.0, end=float("inf"), waves=[])
        wave = harness.Wave(index=0, t_due=time.perf_counter(),
                            want=gen.output_tokens)
        win.waves.append(wave)
        harness.run_wave(steps, params, gen, wave, float("inf"))
        t1 = time.perf_counter()
        sample = check.draw_sample(win, gen, seed)
        row = {"workload": cell.name, "seed": seed,
               "requests": int(sample.served.shape[0]),
               "served_tokens": int(sample.served.size),
               "in_vocab": check.tokens_in_vocab(
                   win, config["arch"]["vocab_size"]),
               check.GAP: check.program_gap(ref, config, params, sample),
               "wave_s": t1 - t0, "check_s": time.perf_counter() - t1}
        if seed in control:
            t2 = time.perf_counter()
            row["control_" + check.GAP] = check.control_gap(
                ref, config, params, sample)
            row["control_s"] = time.perf_counter() - t2
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()
        del params


if __name__ == "__main__":
    sys.exit(main())

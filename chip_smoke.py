"""Bring-up check on one TPU chip: the five Pallas kernels, compiled, and
mamba2-1.3b served at its published widths through the serving CLI's path.

    python chip_smoke.py

Phases, all in this one process (a chip belongs to one process):

1. device  - jax must find a TPU; there is no CPU fallback.
2. kernels - each kernel through ``repro.kernels.ops`` at published widths
             (``repro.kernels.sites``), compiled (``tpu_custom_call`` in
             the HLO), within its stated tolerance of the float32 oracle.
3. serve   - ``repro.launch.serve``'s object fleet for mamba2-1.3b: one
             node, 4 slots, max_seq 256, 8 requests of 8-32 prompt tokens,
             16 new tokens each; every request finishes with in-vocabulary
             tokens, every step's logits are finite, and the fleet ledger is
             positive and equals the node meters.

Times printed here are smoke timings, not a benchmark.  The last line of
standard output is ``{"ok": true, "device": {...}}``; it is printed only
when every phase passed.  Any failure exits non-zero.
"""
from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SERVE_ARGS = ["--arch", "mamba2-1.3b", "--fleet", "1", "--slots", "4",
              "--max-seq", "256", "--requests", "8", "--max-new", "16"]
PROMPT_LEN = (8, 33)        # [low, high) prompt tokens


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def phase_device():
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        print(f"chip_smoke: jax found no TPU (platform {d.platform!r}); "
              f"this check runs on the chip only", file=sys.stderr)
        sys.exit(2)
    return d


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.sites import rel_err, sites

    key = jax.random.PRNGKey(0)
    for site in sites():
        key, sub = jax.random.split(key)
        args = jax.jit(site.make)(sub)
        with jax.default_matmul_precision("highest"):
            t0 = time.perf_counter()
            compiled = jax.jit(site.op).lower(*args).compile()
            compile_s = time.perf_counter() - t0
            check("tpu_custom_call" in compiled.as_text(),
                  f"{site.name}: no Pallas kernel in the compiled HLO")
            out = jax.block_until_ready(compiled(*args))
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*args))
            call_ms = 1e3 * (time.perf_counter() - t0)
            t0 = time.perf_counter()
            want = jax.jit(lambda *a, f=site.oracle: f(
                *(x.astype(jnp.float32) for x in a)))(*args)
            err = float(rel_err(out, want))
            oracle_s = time.perf_counter() - t0
        print(f"kernel {site.name} ({site.source}): max_err={err:.3e} "
              f"(tol {site.tol:g} of max |oracle|) compile_s={compile_s:.2f} "
              f"call_ms={call_ms:.3f} oracle_s={oracle_s:.2f} "
              f"[smoke timing, not a benchmark]", flush=True)
        check(err <= site.tol, f"{site.name}: error {err:.3e} over "
                               f"tolerance {site.tol:g}")
        del args, out, want, compiled


def phase_serve(dev) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch import serve

    args = serve.build_parser().parse_args(SERVE_ARGS)
    cfg = get_config(args.arch)
    t0 = time.perf_counter()
    nodes, sched, _, _ = serve.build_fleet(args, cfg)
    jax.block_until_ready(nodes[0].loop.params)
    init_s = time.perf_counter() - t0

    loop = nodes[0].loop
    decode = loop._decode
    batch = {"tokens": jnp.zeros((args.slots, 1), jnp.int32),
             "pos": jnp.asarray(0, jnp.int32)}
    t0 = time.perf_counter()
    jax.block_until_ready(decode(loop.params, batch, loop.cache))
    compile_s = time.perf_counter() - t0

    finite = []

    def checked(params, batch, cache):
        logits, cache = decode(params, batch, cache)
        finite.append(jnp.isfinite(logits).all())
        return logits, cache
    loop._decode = checked

    make_request = serve.request_maker(args, cfg, prompt_len=PROMPT_LEN)
    reqs = [make_request(i) for i in range(args.requests)]
    for r in reqs:
        sched.submit(r)
    t0 = time.perf_counter()
    finished = sched.run()
    wall = time.perf_counter() - t0

    tokens = sum(len(r.out) for r in finished)
    steps = sum(n.loop.steps_done for n in nodes)
    total = sched.ledger.total_ws
    meters = sum(n.meter.ledger.total_ws for n in nodes)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"serve {cfg.name}: {len(finished)}/{len(reqs)} requests, "
          f"prompts {min(len(r.prompt) for r in reqs)}-"
          f"{max(len(r.prompt) for r in reqs)} tokens, {tokens} new tokens, "
          f"{steps} decode steps in {wall:.2f}s ({tokens / wall:.1f} tok/s) "
          f"[smoke timing, not a benchmark]", flush=True)
    print(f"serve init_s={init_s:.2f} decode_compile_s={compile_s:.2f} "
          f"(first call included) peak_bytes_in_use={peak}", flush=True)
    print(f"serve ledger total_ws={total:.6f} node meters={meters:.6f} "
          f"(modeled watts x measured seconds)", flush=True)

    check(len(finished) == len(reqs) and all(r.done for r in finished),
          f"{len(finished)} of {len(reqs)} requests finished")
    check(all(0 <= t < cfg.vocab_size for r in finished for t in r.out),
          "a token id outside the vocabulary")
    check(bool(jnp.all(jnp.stack(finite))), "non-finite logits")
    check(total > 0 and math.isclose(total, meters, rel_tol=1e-9),
          f"fleet ledger {total} vs node meters {meters}")


def main() -> int:
    enable_compile_cache()
    import jax

    from repro import obs

    # fallbacks to the oracle count here; phases 2 and 3 must see none
    obs.set_metrics(obs.MetricsRegistry())
    t_all = time.perf_counter()
    dev = phase_device()

    for name, phase in (("kernels", phase_kernels),
                        ("serve", lambda: phase_serve(dev))):
        t0 = time.perf_counter()
        phase()
        fallbacks = {k: v["value"] for k, v in obs.METRICS.to_json().items()
                     if k.startswith("kernel_fallback_") and v["value"]}
        check(not fallbacks, f"{name}: kernels fell back to the oracle "
                             f"{fallbacks}")
        print(f"phase {name}: {time.perf_counter() - t0:.2f}s", flush=True)
    print(f"total: {time.perf_counter() - t_all:.2f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

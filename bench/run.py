"""Run one benchmark cell once on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

Set-up: find the chips, make the weights from the seed on the device in
one jitted call, compile (or read from JAX's persistent cache in
`artifacts/jax_cache/`) and run once every program the cell's traffic
uses.  Then `--seconds` of lockstep waves, then the comparison with the
plain reference that decides `correct`.  With `--trace 0` the result
carries the cell's end-to-end metrics; with `--trace 1` the first wave is
traced with `jax.profiler` and the result carries the per-layer metrics,
the device's busy seconds and a breakdown.

The last line of standard output is one JSON object; the last lines of
standard error are the compared numbers beside their limits.  Without a
TPU, or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

TRACE_DIR = ROOT / "artifacts" / "bench_trace"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts programs traced and backend compiles, from JAX's own events."""
    TRACED = "/jax/core/compile/jaxpr_trace_duration"
    COMPILED = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.traced = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == self.TRACED:
            self.traced += 1
        elif name == self.COMPILED:
            self.compiled += 1

    def snapshot(self) -> tuple:
        return self.traced, self.compiled


def find_device(chips: int):
    """The chips, or exit 2 where JAX finds no TPU or too few."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu" or len(devs) < chips:
        log(f"bench: needs {chips} TPU chip(s); jax found {len(devs)} "
            f"{d.platform!r} device(s)")
        sys.exit(2)
    return devs


def run_cell(cell, seed: int, seconds: float, trace: bool, devs, peaks: dict,
             t_start: float, trace_dir: Path = TRACE_DIR) -> dict:
    """Everything after the device check; returns the result object."""
    import jax

    import check
    import harness
    import traffic
    from repro import obs
    from xtrace import find_xplane, from_xplane

    obs.set_metrics(obs.MetricsRegistry())
    compiles = CompileCounter()
    config = cell.config
    ref = cell.reference()
    gen = traffic.generator(cell.traffic, config["token_ids_below"], seed)
    model = harness.build_model(config)
    params = jax.jit(lambda k: ref.make_params(config, k))(
        harness.seed_key(seed))
    steps = harness.make_steps(model, gen.batch, gen.cache_tokens)
    harness.warm_up(steps, params, gen)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.3f} (process start to the first timed call; "
        f"compiles so far: {compiles.snapshot()})")

    tracer = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = harness.Tracer(str(trace_dir))
    before = compiles.snapshot()
    pauses = harness.GcPauses()
    win = harness.run_window(steps, params, gen, seconds, tracer)
    pauses.close()
    gc.unfreeze()
    after = compiles.snapshot()
    in_window = {"traced": after[0] - before[0],
                 "compiled": after[1] - before[1]}
    stats = devs[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    del steps
    t_end = time.perf_counter()

    counters = {k: v["value"] for k, v in obs.METRICS.to_json().items()
                if "value" in v}
    kernel_counters = {k: v for k, v in counters.items()
                       if k.startswith("kernel_")}
    ws = harness.wave_stats(win, gen.batch)
    log(f"window: {len(win.waves)} waves, {ws['requests_started']} requests "
        f"started, {ws['requests_finished']} finished, "
        f"{ws['output_tokens']} tokens inside {seconds:g}s; samples: "
        f"ttft {len(ws['ttft_s'])} requests in {ws['ttft_waves']} waves, "
        f"tpot {len(ws['tpot_s'])} requests in {ws['tpot_waves']} waves")
    log(f"gc inside the window: {len(pauses.pauses)} collections, "
        f"{sum(1 for g, _ in pauses.pauses if g == 2)} full, longest "
        f"{1e3 * max((s for _, s in pauses.pauses), default=0):.3f} ms")
    log(f"compiles inside the window: {in_window}; kernel counters: "
        f"{kernel_counters or 'none'}; peak_bytes_in_use={peak} "
        f"bytes_limit={stats.get('bytes_limit')}")

    # -- correctness: after the window, with the program's state freed ------
    t_check = time.perf_counter()
    vocab = config["arch"]["vocab_size"]
    in_vocab = check.tokens_in_vocab(win, vocab)
    try:
        limits = cell.limits()
    except FileNotFoundError:
        limits = {}
    gap = lim = None
    if check.finished_requests(win):
        sample = check.draw_sample(win, gen, seed)
        gap = check.program_gap(ref, config, params, sample)
        lim = limits.get(check.GAP, {}).get("limit")
        log(f"check: {sample.served.shape[0]} requests, "
            f"{sample.served.size} served tokens, "
            f"{time.perf_counter() - t_check:.2f}s")
    else:
        log("check: no request finished inside the window")
    correct = in_vocab and None not in (gap, lim) and gap <= lim
    checks = {check.GAP: {"value": gap, "limit": lim},
              "tokens_in_vocab": {"value": int(in_vocab), "limit": 1}}

    # -- metrics -------------------------------------------------------------
    metrics = {}
    result = {"correct": bool(correct),
              "attempted": ws["requests_started"],
              "failed": 0 if in_vocab else ws["requests_started"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    if not trace:
        values = {"setup_s": setup_s,
                  "output_tokens_per_s": ws["output_tokens"] / seconds}
        if ws["ttft_s"]:
            values["ttft_p95_ms"] = 1e3 * harness.p95(ws["ttft_s"])
        if ws["tpot_s"]:
            values["tpot_p95_ms"] = 1e3 * harness.p95(ws["tpot_s"])
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        flops = sum(cell.counter().flops(config["arch"], gen, prog, pos)
                    for w in win.waves
                    for prog, pos in _wave_calls(w, gen, win.end))
        power = peaks["modeled_power"]
        ws_modeled = seconds * power["p_static_w"] + flops * power["e_flop_j"]
        log(f"modeled (not measured) W*s per output token: "
            f"{ws_modeled / max(ws['output_tokens'], 1):.6f} "
            f"({seconds:g}s x {power['p_static_w']} W + {flops:.4e} FLOPs x "
            f"{power['e_flop_j']} J; {power['source']})")
    else:
        t_read = time.perf_counter()
        tr = from_xplane(find_xplane(trace_dir), harness.SPANS)
        lo, hi = tr.span("slice")
        ctx = MetricContext(trace=tr, lo=lo, hi=hi, cell=cell, gen=gen,
                            calls=tracer.calls, peaks=peaks,
                            counters=counters)
        for m in cell.per_layer:
            v = cell.metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_ns(lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = tr.breakdown(lo, hi)
        log(f"trace: {len(tr.ops)} device ops, {len(tr.programs)} program "
            f"events, {len(tr.host)} host spans on {tr.n_devices} device(s), "
            f"read and reduced in {time.perf_counter() - t_read:.2f}s")
    log(f"after the window: {time.perf_counter() - t_end:.2f}s")
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    return result


def _wave_calls(wave, gen, end: float):
    """(program, position) of the calls whose tokens landed before `end`."""
    n = sum(1 for a in wave.arrivals if a < end)
    p0 = gen.prompt_tokens
    return [("prefill", p0)][:n] + [("decode", p0 + j) for j in range(n - 1)]


class MetricContext:
    """What a per-layer metric's `read(ctx)` may use.  `counters` holds the
    value of each counter and gauge on `repro.obs.METRICS`, read after
    the window: what the program counted while it traced and ran."""

    def __init__(self, trace, lo, hi, cell, gen, calls, peaks,
                 counters=None):
        self.trace, self.lo, self.hi = trace, lo, hi
        self.cell, self.gen, self.calls, self.peaks = cell, gen, calls, peaks
        self.counters = counters or {}


def main(argv=None) -> int:
    args = parse(argv)
    from registry import load_cell, load_peaks
    cell = load_cell(args.workload, ROOT)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devs = find_device(cell.chips)
    log(f"workload {cell.name}: seed {args.seed}, {args.seconds:g}s, "
        f"trace {args.trace}, compile cache {cache_dir}")
    peaks = load_peaks(devs[0].device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                      peaks, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 placeholder host devices, lowers the real
train/prefill/decode step with the real shardings, compiles it, and records
``memory_analysis()`` (fits?), ``cost_analysis()`` (FLOPs/bytes) and the
collective payload census parsed from the post-SPMD HLO (for §Roofline).

This is also the *compiled measurement rung*'s child process
(``repro.core.backends.CompiledBackend``): every cell additionally emits a
stage sidecar — per-stage wall-clock timestamps plus the utilization its
own process counters measured — which the parent samples into a real
phase-marked power trace.

Results are JSON-cached under artifacts/dryrun/ — reruns are incremental,
and a malformed/stale cache file silently falls back to re-lowering.

Importing this module has no side effects; the 512-device pin happens in
``setup_host_devices()``, which ``main()`` calls before touching jax.
(jax locks the host device count when its backend first initializes, so
anything that imports this module from a live process — tests, benches —
keeps its single real device.)

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  python -m repro.launch.dryrun --all                  # single-pod sweep
  python -m repro.launch.dryrun --all --multi-pod      # 2-pod sweep
"""
from __future__ import annotations

import argparse
import json
import os
import time
import traceback
from contextlib import contextmanager
from pathlib import Path
from typing import Optional

ART = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

HOST_DEVICE_COUNT = 512


def setup_host_devices(n: int = HOST_DEVICE_COUNT) -> None:
    """Pin this process to the CPU platform with ``n`` placeholder host
    devices, keeping any other ``XLA_FLAGS`` it was given.

    Must run before jax's backend initializes (``main()`` calls it first
    thing; the CompiledBackend subprocess therefore gets 512 devices while
    in-process importers keep their real device count)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "").split()
    flags = [f for f in flags
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def model_flops(cfg, shape) -> float:
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch     # decode: one token per sequence


def _mem_dict(mem) -> dict:
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        try:
            out[k] = int(getattr(mem, k))
        except Exception:
            pass
    if not out:
        out["repr"] = str(mem)
    return out


def _clamp_microbatches(plan, shape, mesh) -> int:
    """Microbatch size must stay divisible by the batch sharding ways."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ways = sizes.get("data", 1) * sizes.get("pod", 1)
    if not plan.use_tp:   # model axis joins batch sharding (pure DP)
        ways *= sizes.get("model", 1)
    per_shard = max(shape.global_batch // ways, 1)
    n = min(plan.microbatches, per_shard)
    while per_shard % n:
        n -= 1
    return n


# ---------------------------------------------------------------------------
# Stage clock — the sidecar the compiled rung samples
# ---------------------------------------------------------------------------

try:                     # host counters: optional, never a hard dependency
    import psutil as _psutil
    _PSUTIL_PROC = _psutil.Process()
except Exception:        # pragma: no cover - psutil baked into the image
    _psutil = None
    _PSUTIL_PROC = None


class StageClock:
    """Wall-clock stage windows + measured utilization for one trial.

    Each ``stage(name)`` block records ``(t0, t1)`` on the trial's wall
    clock and the utilization the host's process counters actually
    measured over the window — CPU seconds per wall second, clamped to
    [0, 1].  When psutil is importable the counters come from the
    process's ``cpu_times`` (user+system across every thread, the
    RAPL-adjacent host signal the ROADMAP asks for) and the sidecar tags
    the stage ``util_src="psutil"``; otherwise the stdlib
    ``time.process_time`` ratio fallback keeps the rung working on
    machines without it.  Either way this is the verification machine's
    achieved utilization during lowering/compilation, the signal the
    parent's power sampler drives the node envelope with."""

    def __init__(self, proc=_PSUTIL_PROC) -> None:
        self._base = time.perf_counter()
        self._proc = proc
        self.stages: list[dict] = []

    def _cpu_seconds(self) -> tuple[float, str]:
        if self._proc is not None:
            try:
                ct = self._proc.cpu_times()
                return ct.user + ct.system, "psutil"
            except Exception:       # process table hiccup: fall back
                self._proc = None
        return time.process_time(), "process_time"

    @contextmanager
    def stage(self, name: str):
        t0, (c0, _) = time.perf_counter(), self._cpu_seconds()
        try:
            yield
        finally:
            t1, (c1, src) = time.perf_counter(), self._cpu_seconds()
            wall = max(t1 - t0, 1e-9)
            self.stages.append({
                "name": name,
                "t0": t0 - self._base,
                "t1": t1 - self._base,
                "util": min(max((c1 - c0) / wall, 0.0), 1.0),
                "util_src": src,
            })

    def sidecar(self) -> dict:
        return {"wall_s": time.perf_counter() - self._base,
                "stages": self.stages}


def load_cached(path: Path) -> Optional[dict]:
    """Cached record, or None when missing/malformed/stale -> re-lower."""
    from repro.core.backends import load_record
    return load_record(path)


# ---------------------------------------------------------------------------


def build_step(arch: str, shape_name: str, mesh, plan=None):
    """Returns (fn, args_specs, in_shardings, donate) for the cell."""
    import dataclasses

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES, get_config
    from repro.models.model import Model
    from repro.parallel.param_sharding import (batch_shardings,
                                               cache_shardings,
                                               opt_shardings,
                                               param_shardings)
    from repro.parallel.sharding import make_rules
    from repro.train.step import make_opt_init, make_train_step

    cfg = get_config(arch)
    if plan is not None:
        cfg = dataclasses.replace(cfg, plan=plan)
    shape = SHAPES[shape_name]
    n_micro = _clamp_microbatches(cfg.plan, shape, mesh)
    if n_micro != cfg.plan.microbatches:
        cfg = dataclasses.replace(
            cfg, plan=cfg.plan.replace(microbatches=n_micro))
    model = Model(cfg)
    rules = make_rules(cfg, mesh, cfg.plan)
    aparams = model.abstract_params()
    p_sh = param_shardings(aparams, rules)
    b_specs = model.input_specs(shape)
    b_sh = batch_shardings(model, shape, rules)

    if shape.kind == "train":
        opt_abs = jax.eval_shape(make_opt_init(model), aparams)
        o_sh = opt_shardings(opt_abs, aparams, rules)
        fn = make_train_step(model, rules)
        scalar = NamedSharding(mesh, P())
        out_sh = (p_sh, o_sh, {"loss": scalar, "grad_norm": scalar})
        return (fn, (aparams, opt_abs, b_specs), (p_sh, o_sh, b_sh),
                out_sh, (0, 1), cfg, shape)

    cache_abs = model.abstract_cache(shape.global_batch, shape.seq_len)
    c_sh = cache_shardings(cache_abs, rules)
    from repro.parallel.param_sharding import pick_spec
    logits_sh = NamedSharding(mesh, pick_spec(
        (shape.global_batch, cfg.vocab_size), [("batch", "vocab")], rules))
    if shape.kind == "prefill":
        def fn(params, batch, cache):
            return model.prefill(params, batch, cache, rules)
    else:
        def fn(params, batch, cache):
            return model.decode_step(params, batch, cache, rules)
    return (fn, (aparams, b_specs, cache_abs), (p_sh, b_sh, c_sh),
            (logits_sh, c_sh), (2,), cfg, shape)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             force: bool = False, plan=None, tag: str = "") -> dict:
    import jax

    from repro.configs import SHAPES, get_config
    from repro.core.transfer import batching_report
    from repro.core.transfer import census as collective_census
    from repro.launch.mesh import make_production_mesh

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    key = f"{arch}__{shape_name}__{mesh_name}{tag}"
    out_path = ART / f"{key}.json"
    if out_path.exists() and not force:
        cached = load_cached(out_path)
        # a record cached by a pre-sidecar run has no stage file: honour
        # it only when the compiled rung's measurement input exists too,
        # else re-lower so both artifacts are regenerated together
        if cached is not None and (cached.get("status") != "OK"
                                   or (ART / f"{key}.stages.json").exists()):
            return cached
        # malformed/stale artifact: fall through and re-lower

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "params": cfg.param_count(),
           "active_params": cfg.active_param_count()}
    if shape_name in cfg.skip_shapes:
        rec.update(status="SKIP", reason=cfg.skip_shapes[shape_name])
        ART.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    clock = StageClock()
    t0 = time.time()
    try:
        with clock.stage("build"):
            mesh = make_production_mesh(multi_pod=multi_pod)
            fn, args, in_sh, out_sh, donate, cfg2, shape = build_step(
                arch, shape_name, mesh, plan)
        with mesh:
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            with clock.stage("lower"):
                lowered = jitted.lower(*args)
            with clock.stage("compile"):
                compiled = lowered.compile()
            with clock.stage("analyze"):
                mem = compiled.memory_analysis()
                cost = compiled.cost_analysis() or {}
                hlo = compiled.as_text()
        census = collective_census(hlo)
        brep = batching_report(hlo)
        n_chips = mesh.devices.size
        stage_s = {s["name"]: s["t1"] - s["t0"] for s in clock.stages}
        rec.update(
            status="OK",
            lower_s=round(stage_s.get("lower", 0.0), 2),
            compile_s=round(stage_s.get("compile", 0.0), 2),
            n_chips=n_chips,
            hlo_flops=float(cost.get("flops", 0.0)),
            hlo_bytes=float(cost.get("bytes accessed", 0.0)),
            collectives=census,
            batching={"fusible_ops": brep.fusible_ops,
                      "fusible_bytes": brep.fusible_bytes,
                      "groups": brep.groups[:6]},
            memory=_mem_dict(mem),
            model_flops=model_flops(cfg2, shape),
            plan=cfg2.plan.describe(),
        )
    except Exception as e:  # sharding mismatch / OOM-at-compile are bugs
        rec.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-4000:],
                   seconds=round(time.time() - t0, 2))
    ART.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1))
    # stage sidecar: the compiled rung's wall-clock measurement input
    (ART / f"{key}.stages.json").write_text(
        json.dumps(clock.sidecar(), indent=1))
    return rec


def main() -> None:
    setup_host_devices()                # before jax's backend initializes

    from repro.configs import SHAPES, list_archs

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--plan-json", default=None,
                    help="PlanConfig overrides as JSON (verifier subprocess)")
    ap.add_argument("--tag", default="",
                    help="cache-key suffix for plan variants")
    args = ap.parse_args()

    plan = None
    if args.plan_json:
        from repro.configs.base import PlanConfig
        plan = PlanConfig(**json.loads(args.plan_json))

    cells = []
    if args.all or not args.arch:
        archs = [a for a in list_archs() if not a.startswith("tiny")]
    else:
        archs = [args.arch]
    for a in archs:
        shapes = ([args.shape] if args.shape else list(SHAPES))
        for s in shapes:
            cells.append((a, s))

    for a, s in cells:
        rec = run_cell(a, s, args.multi_pod, args.force or bool(args.tag),
                       plan=plan, tag=args.tag)
        line = f"{rec['arch']:26s} {rec['shape']:12s} {rec['mesh']:10s} {rec['status']}"
        if rec["status"] == "OK":
            mem = rec["memory"]
            per_dev = (mem.get("argument_size_in_bytes", 0)
                       + mem.get("temp_size_in_bytes", 0))
            line += (f"  compile={rec['compile_s']:.0f}s"
                     f" flops={rec['hlo_flops']:.3g}"
                     f" coll={rec['collectives']['total_bytes']:.3g}B"
                     f" mem/dev={per_dev/2**30:.2f}GiB")
        elif rec["status"] == "FAIL":
            line += "  " + rec["error"][:120]
        else:
            line += "  " + rec["reason"][:80]
        print(line, flush=True)


if __name__ == "__main__":
    main()

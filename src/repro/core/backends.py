"""Measurement rungs — the verification environment as a backend layer.

The paper measures every offload pattern on a *verification machine*, but
not every trial costs the same: the GA inner loop needs thousands of cheap
estimates while the narrowed finalists earn a real (expensive) trial — the
FPGA-compile asymmetry that §3.2's narrowing exists for.  This module makes
that asymmetry a first-class abstraction: a ``MeasurementBackend`` turns a
plan into a ``Measurement``, and the registered rungs order themselves by
fidelity and cost:

  * ``analytic`` — roofline estimate + ``synthesize_phase_trace``:
    milliseconds per pattern, the GA inner loop's rung.
  * ``compiled`` — spawn the dry-run in a subprocess (512 placeholder
    devices, real GSPMD lowering of the actual plan) with a power sampler
    attached to its *wall clock*: the subprocess emits per-stage
    timestamps + measured utilization to a JSON sidecar, and the parent
    samples those through the verification node's envelope into a real
    phase-marked ``PowerTrace``.  Nothing on this rung is synthesized from
    the estimate.
  * ``replay`` — re-read a trace a compiled trial persisted (JSONL), for
    offline analysis and CI machines that cannot afford the lowering.

Every rung obeys one invariant: ``Measurement.energy_j`` equals the
integral of its trace (``trace.integrate()``), so Watt·second comparisons
across rungs always compare trace-backed numbers.

``repro.core.verifier.Verifier`` is the thin cache over this layer; its
``RungPolicy`` holds the promotion rules (which consumer measures on which
rung).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Optional, Protocol, runtime_checkable

from repro import obs
from repro.configs.base import ArchConfig, PlanConfig, SHAPES, ShapeSpec
from repro.core.fitness import TIMEOUT_PENALTY_S, TIMEOUT_SECONDS, fitness
from repro.core.intensity import estimate_program
from repro.core.power import PowerModel, R740_ARRIA10, V5E
from repro.telemetry.dvfs import PowerEnvelope, node_envelope
from repro.telemetry.sampler import sample_stage_trace, synthesize_phase_trace
from repro.telemetry.trace import PowerTrace

REPO_ROOT = Path(__file__).resolve().parents[3]
ART_DRYRUN = REPO_ROOT / "artifacts" / "dryrun"


# ---------------------------------------------------------------------------
# Measurement — one verification trial's result, whatever rung produced it
# ---------------------------------------------------------------------------

@dataclass
class Measurement:
    seconds: float
    watts: float
    energy_j: float
    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_bytes: float = 0.0
    peak_mem_per_chip: float = 0.0
    source: str = "analytic"            # which rung measured this
    ok: bool = True
    error: str = ""
    # phase-marked power trace of the trial.  The analytic rung synthesizes
    # it from the roofline terms; the compiled/replay rungs carry the
    # measured one.  On every rung integral(trace) == energy_j.
    trace: Optional[PowerTrace] = field(default=None, repr=False)
    # measured per-phase utilization (compiled/replay rungs; empty when the
    # rung had no counter to read)
    utilization: dict = field(default_factory=dict)

    def fitness(self, alpha: float = 0.5, beta: float = 0.5) -> float:
        return fitness(self.seconds, self.watts, alpha, beta)


def penalty_measurement(error: str, power: PowerModel) -> Measurement:
    """Paper §4.1: timeout/failure -> processing time := 1000 s."""
    trace = synthesize_phase_trace(
        [("penalty", TIMEOUT_PENALTY_S, 0.0)],
        static_watts=power.hw.p_static, samples_per_phase=4,
        meta={"source": "penalty"})
    return Measurement(seconds=TIMEOUT_PENALTY_S,
                       watts=power.hw.p_static,
                       energy_j=TIMEOUT_PENALTY_S * power.hw.p_static,
                       ok=False, error=error, source="penalty", trace=trace)


# ---------------------------------------------------------------------------
# The backend contract + registry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeasureContext:
    """Everything a rung needs to know about the trial besides the plan."""
    cfg: ArchConfig
    shape_name: str
    n_chips: int = 256
    tp: int = 16
    power: PowerModel = field(default_factory=lambda: PowerModel(V5E))
    overlap: float = 0.0                # collective/compute overlap fraction
    timeout_s: float = TIMEOUT_SECONDS

    @property
    def shape(self) -> ShapeSpec:
        return SHAPES[self.shape_name]


@runtime_checkable
class MeasurementBackend(Protocol):
    name: str

    def measure(self, ctx: MeasureContext,
                plan: PlanConfig) -> Measurement: ...


BACKENDS: dict = {}          # rung name -> backend class


def register_backend(cls):
    """Class decorator: make the rung constructible by name."""
    BACKENDS[cls.name] = cls
    return cls


def make_backend(name: str, **kwargs) -> MeasurementBackend:
    if name not in BACKENDS:
        raise KeyError(f"unknown measurement rung {name!r}; "
                       f"registered: {sorted(BACKENDS)}")
    return BACKENDS[name](**kwargs)


def plan_tag(plan: PlanConfig) -> str:
    """Stable pattern id for a concrete plan (cache keys, artifact names)."""
    doc = json.dumps(dataclasses.asdict(plan), sort_keys=True)
    return hashlib.sha1(doc.encode()).hexdigest()[:10]


# ---------------------------------------------------------------------------
# Shared roofline finishing (the analytic rung's whole job; the compiled
# rung reuses the OOM gate against the target chip)
# ---------------------------------------------------------------------------

def _roofline_measurement(ctx: MeasureContext, flops: float, hbm: float,
                          coll: float, peak_mem: float, source: str,
                          overlap: Optional[float] = None,
                          coll_ops: int = 0) -> Measurement:
    if peak_mem > ctx.power.hw.hbm_bytes:
        return penalty_measurement(
            f"OOM: {peak_mem/2**30:.1f} GiB/chip > "
            f"{ctx.power.hw.hbm_bytes/2**30:.0f} GiB", ctx.power)
    overlap = ctx.overlap if overlap is None else overlap
    t = ctx.power.step_time(flops, hbm, coll, ctx.n_chips, overlap)
    if coll_ops:
        import math as _m
        # per-collective launch/hop latency grows with ring size
        t += coll_ops * 5e-6 * max(_m.log2(max(ctx.n_chips, 2)), 1.0) \
            * (1.0 - overlap)
    w = ctx.power.watts(flops, hbm, coll * ctx.n_chips, t,
                        ctx.n_chips) / ctx.n_chips
    e = w * t * ctx.n_chips
    return Measurement(seconds=t, watts=w, energy_j=e, flops=flops,
                       hbm_bytes=hbm, coll_bytes=coll,
                       peak_mem_per_chip=peak_mem, source=source,
                       trace=_synthesize_roofline_trace(ctx, flops, hbm,
                                                        coll, t, source))


def _synthesize_roofline_trace(ctx: MeasureContext, flops: float,
                               hbm: float, coll: float, t: float,
                               source: str) -> Optional[PowerTrace]:
    """Phase-marked trace from the roofline decomposition: the
    compute/memory-bound span followed by the exposed-collective span,
    each drawing static + its dynamic joules.  By construction the
    trapezoidal integral equals ``energy_j``."""
    if t <= 0:
        return None
    hw = ctx.power.hw
    t_cm = min(max(ctx.power.compute_term(flops, ctx.n_chips),
                   ctx.power.memory_term(hbm, ctx.n_chips)), t)
    dyn_cm = flops * hw.e_flop + hbm * hw.e_hbm
    dyn_coll = coll * ctx.n_chips * hw.e_ici
    return synthesize_phase_trace(
        [("compute", t_cm, dyn_cm), ("collective", t - t_cm, dyn_coll)],
        static_watts=hw.p_static * ctx.n_chips,
        meta={"source": source, "arch": ctx.cfg.name,
              "shape": ctx.shape_name, "chips": ctx.n_chips})


# ---------------------------------------------------------------------------
# Rung 1 — analytic: roofline + synthesized trace (the GA inner loop)
# ---------------------------------------------------------------------------

@register_backend
@dataclass
class AnalyticBackend:
    """estimate_program + PowerModel: milliseconds per pattern."""

    name = "analytic"

    def measure(self, ctx: MeasureContext,
                plan: PlanConfig) -> Measurement:
        try:
            est = estimate_program(ctx.cfg, ctx.shape, plan,
                                   ctx.n_chips, ctx.tp)
        except Exception as e:
            return penalty_measurement(f"{type(e).__name__}: {e}", ctx.power)
        return _roofline_measurement(
            ctx, est.flops, est.hbm_bytes, est.coll_bytes,
            est.peak_mem_per_chip, self.name,
            overlap=0.5 if plan.overlap_collectives else None,
            coll_ops=est.coll_ops)


# ---------------------------------------------------------------------------
# Rung 2 — compiled: dry-run subprocess, wall-clock sampled
# ---------------------------------------------------------------------------

def load_record(path: Path) -> Optional[dict]:
    """A dry-run JSON artifact, or None when missing/malformed/stale.

    ``None`` tells the caller to fall back to re-lowering (or, for a rung,
    to a penalty) — a half-written or hand-edited cache file must never
    crash the measurement spine."""
    try:
        rec = json.loads(Path(path).read_text())
    except (OSError, ValueError):
        return None
    if not isinstance(rec, dict) or "status" not in rec:
        return None
    return rec


def load_stage_sidecar(path: Path) -> Optional[list]:
    """The per-stage timestamp/utilization sidecar, or None when unusable.

    Values are validated, not just keys: a hand-edited sidecar with
    non-numeric or non-monotonic windows must fall back to a penalty,
    never crash the measurement spine downstream (the stage sampler and
    ``PowerTrace.add`` both reject such input with exceptions)."""
    try:
        doc = json.loads(Path(path).read_text())
    except (OSError, ValueError):
        return None
    stages = doc.get("stages") if isinstance(doc, dict) else None
    if not isinstance(stages, list) or not stages:
        return None
    t_prev = float("-inf")
    for s in stages:
        if not isinstance(s, dict) or not {"name", "t0", "t1"} <= set(s):
            return None
        try:
            t0, t1 = float(s["t0"]), float(s["t1"])
            float(s.get("util", 0.0))
        except (TypeError, ValueError):
            return None
        if not (t_prev <= t0 <= t1):    # windows must be ordered
            return None
        t_prev = t1
    return stages


@register_backend
@dataclass
class CompiledBackend:
    """Real GSPMD lowering in a subprocess, measured on its wall clock.

    The child (``repro.launch.dryrun``) lowers + compiles the actual plan
    on 512 placeholder devices and emits two artifacts: the cost/
    collective/memory record, and a *stage sidecar* — per-stage wall-clock
    timestamps plus the utilization its process counters measured.  The
    parent turns the sidecar into the trial's ``PowerTrace`` by sampling
    the verification node's envelope at the measured utilization across
    the recorded windows (``sample_stage_trace``) — the trace's samples
    come from the subprocess wall clock, not from ``synthesize_phase_
    trace``.  ``seconds``/``watts``/``energy_j`` are that trace's
    duration/average/integral: the verification-machine trial, as the
    paper measures it.  HLO-derived counters (collective bytes, peak
    memory) ride along, and a plan that would not fit the target chip
    still penalties out.

    Every successful trial persists its measured trace next to the dry-run
    record (``<key>.trace.jsonl``) so the replay rung can re-serve it on
    machines that cannot afford the lowering.
    """

    name = "compiled"

    interval: float = 0.05              # the IPMI poll cadence analogue
    envelope: Optional[PowerEnvelope] = None   # verification node envelope
    # stage name -> envelope that stage samples through.  The dry-run's
    # stages (build/lower/compile/analyze) are CPU work on the
    # verification host and fall back to ``envelope`` (the CPU-active
    # node point); an ``execute`` stage in the sidecar — a trial that
    # actually ran the step — draws the accelerator-active point instead.
    stage_envelopes: Optional[dict] = None
    art_dir: Path = ART_DRYRUN
    multi_pod: bool = False             # lower on the 2-pod production mesh
    record_trace: bool = True
    # injectable trial runner (tests stub the subprocess out); signature
    # matches subprocess.run's use below
    runner: Optional[Callable] = None

    def __post_init__(self) -> None:
        if self.envelope is None:
            # the dry-run executes on the verification host (a CPU node),
            # so its draw is the paper's measured CPU-node operating points
            self.envelope = node_envelope(R740_ARRIA10, accelerated=False)
        if self.stage_envelopes is None:
            self.stage_envelopes = {
                "execute": node_envelope(R740_ARRIA10, accelerated=True)}
        self.art_dir = Path(self.art_dir)

    @property
    def mesh_name(self) -> str:
        return "pod2x16x16" if self.multi_pod else "pod16x16"

    # -- subprocess ---------------------------------------------------------

    def _spawn(self, ctx: MeasureContext, plan: PlanConfig,
               tag: str) -> Optional[str]:
        """Run the dry-run child; returns an error string on failure."""
        plan_json = json.dumps(dataclasses.asdict(plan), sort_keys=True)
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", ctx.cfg.name, "--shape", ctx.shape_name,
               "--plan-json", plan_json, "--tag", tag]
        if self.multi_pod:
            cmd.append("--multi-pod")
        # inherit the parent environment, pin the import path and the CPU
        # platform: the child compiles for 512 placeholder host devices
        # (its own XLA_FLAGS, via setup_host_devices) and must never
        # reach for an accelerator the parent may be holding
        env = dict(os.environ, PYTHONPATH=str(REPO_ROOT / "src"),
                   JAX_PLATFORMS="cpu")
        run = self.runner or subprocess.run
        try:
            run(cmd, timeout=ctx.timeout_s, capture_output=True,
                cwd=REPO_ROOT, env=env, check=False)
        except subprocess.TimeoutExpired:
            return (f"verification timeout after {ctx.timeout_s:.0f}s "
                    f"(paper's 3-minute rule)")
        return None

    # -- measurement --------------------------------------------------------

    def measure(self, ctx: MeasureContext,
                plan: PlanConfig) -> Measurement:
        tag = "_p" + plan_tag(plan)
        err = self._spawn(ctx, plan, tag)
        if err is not None:
            return penalty_measurement(err, ctx.power)
        key = f"{ctx.cfg.name}__{ctx.shape_name}__{self.mesh_name}{tag}"
        rec = load_record(self.art_dir / f"{key}.json")
        if rec is None:
            return penalty_measurement("dry-run produced no usable record",
                                       ctx.power)
        if rec.get("status") != "OK":
            return penalty_measurement(rec.get("error", "dry-run failed"),
                                       ctx.power)
        stages = load_stage_sidecar(self.art_dir / f"{key}.stages.json")
        if stages is None:
            return penalty_measurement("dry-run produced no stage sidecar",
                                       ctx.power)
        try:
            m = self.measurement_from_trial(ctx, rec, stages, plan=plan)
        except (TypeError, ValueError) as e:
            # a sidecar that slipped past validation still may not crash
            # the measurement spine — malformed artifacts penalize out
            return penalty_measurement(f"malformed stage sidecar: {e}",
                                       ctx.power)
        if m.ok and self.record_trace and m.trace is not None:
            try:
                m.trace.to_jsonl(self.art_dir / f"{key}.trace.jsonl")
            except OSError:
                pass                    # recording is best-effort
        return m

    def measurement_from_trial(self, ctx: MeasureContext, rec: dict,
                               stages: list,
                               plan: Optional[PlanConfig] = None
                               ) -> Measurement:
        """Pure assembly: record + sidecar -> measured Measurement.

        Factored out so tests (and the invariant properties) can exercise
        the trace/energy construction without spawning the subprocess."""
        peak_mem = _target_mem_estimate(rec)
        if peak_mem > ctx.power.hw.hbm_bytes:
            return penalty_measurement(
                f"OOM: {peak_mem/2**30:.1f} GiB/chip > "
                f"{ctx.power.hw.hbm_bytes/2**30:.0f} GiB", ctx.power)
        trace = sample_stage_trace(
            stages, self.envelope, chips=1, interval=self.interval,
            stage_envelopes=self.stage_envelopes,
            meta={"source": self.name, "arch": ctx.cfg.name,
                  "shape": ctx.shape_name, "mesh": rec.get("mesh", ""),
                  "plan": rec.get("plan", "")})
        tr = obs.TRACER
        if tr.enabled and stages:
            # the stage sidecar's subprocess wall clock becomes its own
            # trace row: one root per trial, one child span per stage
            row = f"dryrun:{ctx.cfg.name}:{ctx.shape_name}"
            root = tr.begin("backend.compiled", node=row,
                            t0=min(s["t0"] for s in stages),
                            tags={"rung": self.name,
                                  "mesh": rec.get("mesh", ""),
                                  "plan": rec.get("plan", "")})
            for s in stages:
                tr.begin(f"dryrun.{s['name']}", node=row, t0=s["t0"],
                         parent=root,
                         tags={"util": s.get("util", 0.0)}
                         ).finish(s["t1"])
            root.finish(max(s["t1"] for s in stages))
        seconds = trace.duration
        energy = trace.integrate()
        # HLO cost_analysis counts loop bodies once -> lift the collective
        # census by the known trip counts (layers scan x microbatch scan)
        coll = rec.get("collectives", {}).get("total_bytes", 0.0)
        if plan is not None:
            coll *= _trip_correction(ctx, plan)
        return Measurement(
            seconds=seconds,
            watts=energy / seconds if seconds > 0 else 0.0,
            energy_j=energy,
            flops=float(rec.get("hlo_flops", 0.0)),
            hbm_bytes=float(rec.get("hlo_bytes", 0.0)),
            coll_bytes=float(coll),
            peak_mem_per_chip=peak_mem,
            source=self.name, trace=trace,
            utilization=dict(trace.meta.get("utilization", {})))


def _trip_correction(ctx: MeasureContext, plan: PlanConfig) -> float:
    from repro.models.transformer import unit_structure
    _, n_full, tail = unit_structure(ctx.cfg)
    trips = max(n_full, 1)
    if ctx.shape.kind == "train":
        trips *= max(plan.microbatches, 1)
    return float(trips)


def _target_mem_estimate(rec: dict) -> float:
    mem = rec.get("memory", {})
    if not isinstance(mem, dict):
        return 0.0
    raw = mem.get("argument_size_in_bytes", 0) \
        + mem.get("temp_size_in_bytes", 0)
    # CPU-backend dry-runs upcast bf16 dots to f32 (DESIGN.md §8):
    # halve the temp estimate toward the TPU target.
    return mem.get("argument_size_in_bytes", 0) \
        + mem.get("temp_size_in_bytes", 0) * 0.5 if raw else 0.0


# ---------------------------------------------------------------------------
# Rung 3 — replay: recorded traces for offline/CI runs
# ---------------------------------------------------------------------------

@register_backend
@dataclass
class ReplayBackend:
    """Re-serve persisted compiled-rung traces without any lowering.

    Looks for ``<arch>__<shape>__<mesh>_p<plan_tag>.trace.jsonl`` under
    ``root`` (exactly what ``CompiledBackend`` records); ``default`` is a
    fallback recording used when the plan has no trace of its own (CI
    machines replaying one checked-in trial).  A missing recording is a
    penalty, not a crash — the cache/promotion machinery treats it like
    any other failed trial.
    """

    name = "replay"

    root: Path = ART_DRYRUN
    default: Optional[Path] = None
    mesh_name: str = "pod16x16"

    def __post_init__(self) -> None:
        self.root = Path(self.root)
        if self.default is not None:
            self.default = Path(self.default)

    def trace_path(self, ctx: MeasureContext,
                   plan: PlanConfig) -> Optional[Path]:
        p = self.root / (f"{ctx.cfg.name}__{ctx.shape_name}__"
                         f"{self.mesh_name}_p{plan_tag(plan)}.trace.jsonl")
        if p.is_file():
            return p
        if self.default is not None and self.default.is_file():
            return self.default
        return None

    def measure(self, ctx: MeasureContext,
                plan: PlanConfig) -> Measurement:
        path = self.trace_path(ctx, plan)
        if path is None:
            return penalty_measurement(
                f"no recorded trace for plan _p{plan_tag(plan)} "
                f"under {self.root}", ctx.power)
        try:
            trace = PowerTrace.from_jsonl(path)
        except (OSError, ValueError, KeyError):
            return penalty_measurement(f"unreadable recording {path}",
                                       ctx.power)
        if len(trace) < 2:
            return penalty_measurement(f"empty recording {path}", ctx.power)
        seconds = trace.duration
        energy = trace.integrate()
        return Measurement(
            seconds=seconds,
            watts=energy / seconds if seconds > 0 else 0.0,
            energy_j=energy, source=self.name, trace=trace,
            utilization=dict(trace.meta.get("utilization", {})))


# ---------------------------------------------------------------------------
# Cross-rung agreement (the governor's re-verification gate)
# ---------------------------------------------------------------------------

def confirms_preference(new: Measurement, old: Measurement,
                        alpha: float = 0.5, beta: float = 0.5,
                        slack: float = 0.02) -> bool:
    """Does this rung confirm that ``new`` should replace ``old``?

    The cheap rung's estimate already preferred ``new`` (that is why it is
    a pending migration); both plans were then re-measured on a higher
    rung and the verdicts land here.  The migration is confirmed only when
    the new plan's trial succeeded AND its paper fitness on this rung is
    at least the incumbent's (minus ``slack``, so measurement jitter on an
    equal pair does not veto).  A penalty on the new plan — timeout, OOM,
    failed lowering — always vetoes, whatever the estimate promised; a
    penalty on the incumbent alone confirms (migrating away from a plan
    that cannot even lower is never wrong).
    """
    if not new.ok:
        return False
    if not old.ok:
        return True
    return new.fitness(alpha, beta) \
        >= old.fitness(alpha, beta) * (1.0 - slack)

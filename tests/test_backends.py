"""Measurement rungs: registry, the three backends, the Verifier cache,
finalist promotion, and the dry-run artifact robustness guarantees."""
import json

import pytest

from repro.configs import get_config
from repro.core.backends import (AnalyticBackend, CompiledBackend,
                                 MeasureContext, Measurement, ReplayBackend,
                                 confirms_preference, load_record,
                                 load_stage_sidecar, make_backend,
                                 penalty_measurement, plan_tag)
from repro.core.fitness import TIMEOUT_PENALTY_S
from repro.core.power import PowerModel, V5E
from repro.core.verifier import RungPolicy, Verifier


def _ctx(arch="tiny-test", shape="decode_32k", **kw):
    return MeasureContext(cfg=get_config(arch), shape_name=shape, **kw)


def _stages(*specs):
    """Sequential (name, dt, util) -> sidecar stage dicts."""
    t, out = 0.0, []
    for name, dt, util in specs:
        out.append({"name": name, "t0": t, "t1": t + dt, "util": util})
        t += dt
    return out


_OK_REC = {"status": "OK", "collectives": {"total_bytes": 1e6},
           "memory": {"argument_size_in_bytes": 2**20,
                      "temp_size_in_bytes": 2**20},
           "hlo_flops": 1e9, "hlo_bytes": 1e7, "mesh": "pod16x16"}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_builds_all_rungs_by_name():
    assert isinstance(make_backend("analytic"), AnalyticBackend)
    assert isinstance(make_backend("compiled"), CompiledBackend)
    assert isinstance(make_backend("replay"), ReplayBackend)
    with pytest.raises(KeyError):
        make_backend("fpga")


# ---------------------------------------------------------------------------
# Analytic rung (the refactor must keep the old verifier behavior)
# ---------------------------------------------------------------------------

def test_analytic_rung_matches_verifier_contract():
    cfg = get_config("qwen2-7b")
    v = Verifier(cfg, "train_4k", n_chips=256)
    m = v.measure_plan(cfg.plan)
    assert m.ok and m.source == "analytic"
    assert m.trace is not None and m.trace.phase_names()
    assert m.trace.integrate() == pytest.approx(m.energy_j, rel=0.01)
    assert m.trace.duration == pytest.approx(m.seconds, rel=1e-6)
    # the rung invariant: energy is the trace integral, on every rung
    direct = AnalyticBackend().measure(
        MeasureContext(cfg=cfg, shape_name="train_4k"), cfg.plan)
    assert direct.seconds == pytest.approx(m.seconds)
    assert direct.energy_j == pytest.approx(m.energy_j)


def test_verifier_caches_per_pattern_and_rung():
    calls = []

    class CountingRung:
        name = "stub"

        def measure(self, ctx, plan):
            calls.append(plan_tag(plan))
            return Measurement(seconds=1.0, watts=100.0, energy_j=100.0,
                               source="stub")

    cfg = get_config("tiny-test")
    v = Verifier(cfg, "decode_32k", backends={"stub": CountingRung()})
    m1 = v.measure_plan(cfg.plan, rung="stub")
    m2 = v.measure_plan(cfg.plan, rung="stub")
    assert m1 is m2 and len(calls) == 1          # pattern cache hit
    ma = v.measure_plan(cfg.plan, rung="analytic")
    assert ma.source == "analytic"               # rungs cache separately
    assert v.n_trials == len(v.cache) == 2


# ---------------------------------------------------------------------------
# Compiled rung: measured trace from the stage sidecar
# ---------------------------------------------------------------------------

def test_compiled_measurement_samples_wall_clock_stages():
    backend = CompiledBackend(record_trace=False, interval=0.01)
    stages = _stages(("build", 0.5, 0.9), ("lower", 1.0, 0.7),
                     ("compile", 2.0, 1.0), ("analyze", 0.1, 0.2))
    m = backend.measurement_from_trial(_ctx(), dict(_OK_REC), stages)
    assert m.ok and m.source == "compiled"
    # the trace spans the subprocess wall clock, not a synthesized timeline
    assert m.seconds == pytest.approx(3.6, rel=1e-6)
    assert m.trace.duration == pytest.approx(3.6, rel=1e-6)
    assert set(m.trace.phase_names()) == {"build", "lower", "compile",
                                          "analyze", "trial"}
    # every stage window carries real samples at the sampler cadence
    assert m.trace.phase_seconds("compile") == pytest.approx(2.0)
    assert len(m.trace) >= 3.6 / 0.01
    # energy is the measured integral; watts the measured average
    assert m.energy_j == pytest.approx(m.trace.integrate(), rel=1e-12)
    assert m.watts == pytest.approx(m.energy_j / m.seconds, rel=1e-12)
    # measured utilization rides along, clamped into [0, 1]
    assert m.utilization["compile"] == pytest.approx(1.0)
    assert m.utilization["lower"] == pytest.approx(0.7)
    assert all(0.0 <= u <= 1.0 for u in m.utilization.values())
    # higher measured utilization -> higher average draw in that window
    w_compile = m.trace.phase_energy("compile") / 2.0
    w_analyze = m.trace.phase_energy("analyze") / 0.1
    assert w_compile > w_analyze


def test_compiled_rung_via_stubbed_subprocess(tmp_path):
    """Full measure() path with the subprocess stubbed out: the runner
    drops the record + sidecar exactly where the child would."""
    cfg = get_config("tiny-test")
    ctx = _ctx()
    backend = CompiledBackend(art_dir=tmp_path)
    key = f"{cfg.name}__decode_32k__pod16x16_p{plan_tag(cfg.plan)}"

    def fake_runner(cmd, **kw):
        assert "--plan-json" in cmd
        (tmp_path / f"{key}.json").write_text(json.dumps(_OK_REC))
        (tmp_path / f"{key}.stages.json").write_text(json.dumps(
            {"wall_s": 1.5, "stages": _stages(("build", 0.5, 1.0),
                                              ("compile", 1.0, 0.8))}))

    backend.runner = fake_runner
    m = backend.measure(ctx, cfg.plan)
    assert m.ok
    assert m.seconds == pytest.approx(1.5, rel=1e-6)
    # a successful trial records its measured trace for the replay rung
    rec_path = tmp_path / f"{key}.trace.jsonl"
    assert rec_path.is_file()
    replay = ReplayBackend(root=tmp_path)
    mr = replay.measure(ctx, cfg.plan)
    assert mr.ok and mr.source == "replay"
    assert mr.energy_j == pytest.approx(m.energy_j, rel=1e-9)
    assert mr.utilization == pytest.approx(m.utilization)


def test_compiled_rung_child_is_pinned_to_cpu(tmp_path, monkeypatch):
    """The dry-run child compiles for placeholder host devices; it must
    never reach for an accelerator its parent may hold."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    seen = {}
    backend = CompiledBackend(art_dir=tmp_path)
    backend.runner = lambda cmd, **kw: seen.update(kw["env"])
    backend.measure(_ctx(), get_config("tiny-test").plan)
    assert seen["JAX_PLATFORMS"] == "cpu"


@pytest.mark.parametrize("record,sidecar", [
    (None, None),                                # nothing produced
    ("{not json", None),                         # malformed record
    (json.dumps({"no": "status"}), None),        # stale/foreign record
    (json.dumps({"status": "FAIL", "error": "boom"}), None),
    (json.dumps(_OK_REC), None),                 # OK but no sidecar
    (json.dumps(_OK_REC), "{not json"),          # OK but bad sidecar
    (json.dumps(_OK_REC), json.dumps({"stages": []})),
])
def test_compiled_rung_bad_artifacts_penalize_not_crash(tmp_path, record,
                                                        sidecar):
    cfg = get_config("tiny-test")
    backend = CompiledBackend(art_dir=tmp_path)
    key = f"{cfg.name}__decode_32k__pod16x16_p{plan_tag(cfg.plan)}"

    def fake_runner(cmd, **kw):
        if record is not None:
            (tmp_path / f"{key}.json").write_text(record)
        if sidecar is not None:
            (tmp_path / f"{key}.stages.json").write_text(sidecar)

    backend.runner = fake_runner
    m = backend.measure(_ctx(), cfg.plan)
    assert not m.ok and m.source == "penalty"
    assert m.seconds == TIMEOUT_PENALTY_S


def test_compiled_rung_target_oom_still_penalizes():
    backend = CompiledBackend(record_trace=False)
    rec = dict(_OK_REC)
    rec["memory"] = {"argument_size_in_bytes": int(64 * 2**30),
                     "temp_size_in_bytes": 0}
    m = backend.measurement_from_trial(_ctx(), rec,
                                       _stages(("compile", 1.0, 1.0)))
    assert not m.ok and "OOM" in m.error


# ---------------------------------------------------------------------------
# Artifact loaders (the cache robustness the whole rung leans on)
# ---------------------------------------------------------------------------

def test_load_record_rejects_malformed_and_stale(tmp_path):
    p = tmp_path / "rec.json"
    assert load_record(p) is None                      # missing
    p.write_text("{truncated")
    assert load_record(p) is None                      # malformed
    p.write_text(json.dumps([1, 2, 3]))
    assert load_record(p) is None                      # wrong shape
    p.write_text(json.dumps({"arch": "x"}))
    assert load_record(p) is None                      # stale (no status)
    p.write_text(json.dumps({"status": "OK"}))
    assert load_record(p) == {"status": "OK"}


def test_load_stage_sidecar_rejects_malformed(tmp_path):
    p = tmp_path / "s.json"
    assert load_stage_sidecar(p) is None
    p.write_text("{truncated")
    assert load_stage_sidecar(p) is None
    p.write_text(json.dumps({"stages": [{"name": "x"}]}))   # no t0/t1
    assert load_stage_sidecar(p) is None
    # values are validated too, not just key presence: non-numeric or
    # non-monotonic windows would crash the stage sampler downstream
    p.write_text(json.dumps({"stages": [
        {"name": "x", "t0": "oops", "t1": 2.0, "util": 1.0}]}))
    assert load_stage_sidecar(p) is None
    p.write_text(json.dumps({"stages": [
        {"name": "a", "t0": 0.0, "t1": 2.0, "util": 1.0},
        {"name": "b", "t0": 0.5, "t1": 1.5, "util": 1.0}]}))  # overlap
    assert load_stage_sidecar(p) is None
    p.write_text(json.dumps({"stages": [
        {"name": "a", "t0": 1.0, "t1": 0.5, "util": 1.0}]}))  # t1 < t0
    assert load_stage_sidecar(p) is None
    good = {"stages": _stages(("compile", 1.0, 0.5))}
    p.write_text(json.dumps(good))
    assert load_stage_sidecar(p) == good["stages"]


def test_run_cell_cache_without_sidecar_relowers(tmp_path, monkeypatch):
    """A pre-sidecar OK record (cached by an old run) must re-lower so
    the compiled rung gets its measurement input, instead of being
    honoured forever and penalizing the plan on every retry."""
    import repro.launch.dryrun as dryrun
    monkeypatch.setattr(dryrun, "ART", tmp_path)
    key = "tiny-test__decode_32k__pod16x16"
    (tmp_path / f"{key}.json").write_text(json.dumps({"status": "OK"}))
    rec = dryrun.run_cell("tiny-test", "decode_32k", multi_pod=False)
    assert rec["status"] in ("OK", "FAIL")     # re-lowered, no early return
    assert "arch" in rec                        # a fresh record, not the stub
    assert (tmp_path / f"{key}.stages.json").is_file()
    # a cached SKIP/FAIL record (which never writes a sidecar) is honoured
    stub = {"status": "SKIP", "reason": "x"}
    (tmp_path / f"{key}.json").write_text(json.dumps(stub))
    assert dryrun.run_cell("tiny-test", "decode_32k",
                           multi_pod=False) == stub


def test_run_cell_malformed_cache_falls_back_to_relower(tmp_path,
                                                        monkeypatch):
    """A half-written cache artifact must re-lower, not crash.  In-process
    the 256-device mesh cannot build (single host device), so the fallback
    lands in a graceful FAIL record — the point is the malformed JSON was
    discarded, re-measured and overwritten."""
    import repro.launch.dryrun as dryrun
    monkeypatch.setattr(dryrun, "ART", tmp_path)
    key = "tiny-test__decode_32k__pod16x16"
    (tmp_path / f"{key}.json").write_text("{truncated json...")
    rec = dryrun.run_cell("tiny-test", "decode_32k", multi_pod=False)
    assert rec["status"] in ("OK", "FAIL")             # no exception
    # the malformed artifact was replaced by a well-formed record
    reread = json.loads((tmp_path / f"{key}.json").read_text())
    assert reread["status"] == rec["status"]
    # ... and the trial emitted its stage sidecar next to it
    assert (tmp_path / f"{key}.stages.json").is_file()


# ---------------------------------------------------------------------------
# Replay rung
# ---------------------------------------------------------------------------

def test_replay_missing_recording_is_penalty(tmp_path):
    cfg = get_config("tiny-test")
    m = ReplayBackend(root=tmp_path).measure(_ctx(), cfg.plan)
    assert not m.ok and "no recorded trace" in m.error


def test_replay_default_recording_serves_any_plan(tmp_path):
    from repro.telemetry import synthesize_phase_trace
    tr = synthesize_phase_trace([("compile", 2.0, 0.0)], static_watts=120.0,
                                meta={"utilization": {"compile": 0.8}})
    p = tmp_path / "recorded.trace.jsonl"
    tr.to_jsonl(p)
    backend = ReplayBackend(root=tmp_path / "nowhere", default=p)
    m = backend.measure(_ctx(), get_config("tiny-test").plan)
    assert m.ok and m.source == "replay"
    assert m.energy_j == pytest.approx(240.0, rel=1e-9)
    assert m.utilization == {"compile": 0.8}


# ---------------------------------------------------------------------------
# Promotion rules: finalists re-measured on the higher rung
# ---------------------------------------------------------------------------

def test_select_destination_promotes_finalists_to_higher_rung():
    from repro.core.destinations import select_destination
    from repro.core.ga import GAConfig

    promoted_tags = []

    class RecordingRung:
        """Stands in for the compiled rung: penalizes pallas-offloaded
        plans (as a failed lowering would), confirms the rest."""
        name = "compiled"

        def measure(self, ctx, plan):
            promoted_tags.append(plan_tag(plan))
            if "pallas" in plan.describe():
                return penalty_measurement("stub: kernel build failed",
                                           PowerModel(V5E))
            return Measurement(seconds=2.0, watts=110.0, energy_j=220.0,
                               source="compiled")

    from repro.core.destinations import Requirement
    cfg = get_config("qwen2-7b")
    v = Verifier(cfg, "train_4k", n_chips=256,
                 rungs=RungPolicy(finalist="compiled"),
                 backends={"compiled": RecordingRung()})
    sel = select_destination(cfg, "train_4k", v,
                             requirement=Requirement(max_seconds=1e-9),
                             ga=GAConfig(population=4, generations=1))
    assert promoted_tags                       # the higher rung was used
    stages = [s["stage"] for s in sel.stages]
    assert "finalist[compiled]" in stages
    # every pallas finalist penalized out -> the winner must be a plan the
    # compiled rung actually confirmed
    assert sel.chosen.measurement.ok
    assert sel.chosen.measurement.source == "compiled"
    assert "pallas" not in sel.chosen.genome.to_plan().describe()


def test_select_destination_analytic_ladder_unchanged():
    """Default policy (finalist == search) must not add promotion trials."""
    from repro.core.destinations import select_destination
    from repro.core.ga import GAConfig
    cfg = get_config("qwen2-7b")
    v = Verifier(cfg, "train_4k", n_chips=256)
    sel = select_destination(cfg, "train_4k", v,
                             ga=GAConfig(population=4, generations=1))
    assert all(not s["stage"].startswith("finalist") for s in sel.stages)
    assert sel.chosen is not None


# ---------------------------------------------------------------------------
# Cross-rung agreement
# ---------------------------------------------------------------------------

def test_confirms_preference_rules():
    ok_fast = Measurement(seconds=1.0, watts=100.0, energy_j=100.0)
    ok_slow = Measurement(seconds=4.0, watts=100.0, energy_j=400.0)
    bad = penalty_measurement("boom", PowerModel(V5E))
    assert confirms_preference(ok_fast, ok_slow)       # real trial agrees
    assert not confirms_preference(ok_slow, ok_fast)   # real trial vetoes
    assert not confirms_preference(bad, ok_slow)       # new plan failed
    assert confirms_preference(ok_slow, bad)           # incumbent failed
    # slack: an equal pair is confirmed, not vetoed by jitter
    assert confirms_preference(ok_fast, ok_fast)


# ---------------------------------------------------------------------------
# Penalty retry policy (transient compiled-rung failures must heal)
# ---------------------------------------------------------------------------

class _FlakyRung:
    """Fails the first ``fail_n`` trials, then succeeds — a transient
    subprocess blip on the verification machine."""

    name = "compiled"

    def __init__(self, fail_n):
        self.fail_n = fail_n
        self.calls = 0

    def measure(self, ctx, plan):
        self.calls += 1
        if self.calls <= self.fail_n:
            return penalty_measurement("stub: transient blip", ctx.power)
        return Measurement(seconds=1.0, watts=100.0, energy_j=100.0,
                           source="compiled")


def test_penalty_retry_heals_transient_compiled_failure():
    cfg = get_config("tiny-test")
    flaky = _FlakyRung(fail_n=1)
    v = Verifier(cfg, "decode_32k", backends={"compiled": flaky})
    m1 = v.measure_plan(cfg.plan, rung="compiled")
    assert not m1.ok                            # the blip penalized
    # the next lookup spends the retry budget and heals the cache
    m2 = v.measure_plan(cfg.plan, rung="compiled")
    assert m2.ok and flaky.calls == 2
    # healed results cache normally again
    m3 = v.measure_plan(cfg.plan, rung="compiled")
    assert m3 is m2 and flaky.calls == 2


def test_penalty_retry_budget_exhausts_for_persistent_failures():
    cfg = get_config("tiny-test")
    flaky = _FlakyRung(fail_n=10_000)           # never heals
    v = Verifier(cfg, "decode_32k", backends={"compiled": flaky})
    for _ in range(5):
        m = v.measure_plan(cfg.plan, rung="compiled")
        assert not m.ok
    # first trial + the default single retry, then the penalty sticks
    assert flaky.calls == 1 + v.penalties.retries


def test_penalty_ttl_re_measures_after_expiry():
    from repro.core.verifier import PenaltyPolicy
    cfg = get_config("tiny-test")
    flaky = _FlakyRung(fail_n=2)
    now = [0.0]
    v = Verifier(cfg, "decode_32k", backends={"compiled": flaky},
                 penalties=PenaltyPolicy(retries=1, ttl_s=60.0),
                 clock=lambda: now[0])
    assert not v.measure_plan(cfg.plan, rung="compiled").ok   # trial 1
    assert not v.measure_plan(cfg.plan, rung="compiled").ok   # retry spent
    # budget exhausted, TTL not yet reached -> stays cached
    assert v.measure_plan(cfg.plan, rung="compiled").ok is False
    assert flaky.calls == 2
    now[0] = 61.0                               # the environment healed
    assert v.measure_plan(cfg.plan, rung="compiled").ok
    assert flaky.calls == 3


def test_analytic_penalties_stay_cached_once():
    """Analytic penalties are deterministic (OOM): no retry, and the GA's
    ``n_trials == len(cache)`` accounting still holds."""
    from repro.core.plan import PlanGenome
    cfg = get_config("llama3-405b")
    v = Verifier(cfg, "train_4k", n_chips=4, mode="analytic")
    g = PlanGenome.from_plan(cfg, "train", cfg.plan)
    m1 = v.measure(g)
    m2 = v.measure(g)
    assert not m1.ok and m2 is m1
    assert v.n_trials == len(v.cache) == 1


# ---------------------------------------------------------------------------
# Per-stage envelopes (compile is CPU-bound; execute draws the accelerator)
# ---------------------------------------------------------------------------

def test_compiled_rung_samples_per_stage_envelopes():
    from repro.core.power import R740_ARRIA10
    from repro.telemetry import node_envelope
    backend = CompiledBackend(record_trace=False, interval=0.01)
    cpu = node_envelope(R740_ARRIA10, accelerated=False)
    accel = node_envelope(R740_ARRIA10, accelerated=True)
    # the defaults: compile-pipeline stages fall back to the CPU point,
    # an execute stage draws the accelerator point
    assert backend.envelope.name == cpu.name
    assert backend.stage_envelopes["execute"].name == accel.name
    m = backend.measurement_from_trial(
        _ctx(), _OK_REC, _stages(("compile", 1.0, 1.0),
                                 ("execute", 2.0, 1.0)))
    assert m.ok
    tr = m.trace
    assert tr.phase_stats("compile")["avg_w"] == \
        pytest.approx(cpu.watts(1.0), rel=1e-9)
    assert tr.phase_stats("execute")["avg_w"] == \
        pytest.approx(accel.watts(1.0), rel=1e-9)
    assert tr.meta["envelopes"] == {"compile": cpu.name,
                                    "execute": accel.name}
    # the rung invariant survives the per-stage envelopes
    assert m.energy_j == pytest.approx(tr.integrate(), rel=1e-12)

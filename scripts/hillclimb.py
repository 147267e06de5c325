"""§Perf hillclimb driver: hypothesis -> change -> re-lower -> validate.

Runs the three chosen cells (worst roofline / most collective-bound /
paper-representative), lowering each plan variant on the production mesh
and recording HLO census + analytic roofline terms before/after.

    PYTHONPATH=src python scripts/hillclimb.py
"""
import os
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs import SHAPES, get_config                  # noqa: E402
from repro.core.intensity import estimate_program             # noqa: E402
from repro.core.power import PowerModel, V5E                  # noqa: E402
from repro.launch.dryrun import run_cell, setup_host_devices  # noqa: E402

OUT = Path(__file__).resolve().parents[1] / "artifacts" / "hillclimb"
POWER = PowerModel(V5E)
CHIPS = 256


def measure(arch, shape_name, plan, tag):
    """Lower the real program; return roofline terms + census."""
    rec = run_cell(arch, shape_name, multi_pod=False, force=False,
                   plan=plan, tag=tag)
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    est = estimate_program(cfg, shape, plan, CHIPS)
    if rec["status"] != "OK":
        return {"status": rec["status"],
                "error": rec.get("error", "")[:200], "tag": tag}
    coll_raw = rec["collectives"]["total_bytes"]
    coll = max(coll_raw, est.coll_bytes)
    tc = POWER.compute_term(est.flops, CHIPS)
    tm = POWER.memory_term(est.hbm_bytes, CHIPS)
    tcl = POWER.collective_term(coll * CHIPS, CHIPS)
    if plan.overlap_collectives:
        tcl *= 0.5
    t = max(tc, tm) + tcl
    w = POWER.watts(est.flops, est.hbm_bytes, coll * CHIPS, t, CHIPS) / CHIPS
    mem = rec["memory"]
    return {
        "status": "OK", "tag": tag,
        "t_compute": tc, "t_memory": tm, "t_collective": tcl,
        "step_time": t, "watts_chip": w, "energy_j": w * t * CHIPS,
        "roofline_fraction": tc / t,
        "coll_bytes_hlo": coll_raw,
        "coll_count_hlo": rec["collectives"].get("total_count", 0),
        "mem_dev_gib": (mem.get("argument_size_in_bytes", 0)
                        + mem.get("temp_size_in_bytes", 0)) / 2**30,
        "compile_s": rec["compile_s"],
    }


def log_iter(cell, name, hypothesis, m_before, m_after, notes=""):
    if m_after["status"] != "OK":
        verdict = f"FAILED: {m_after.get('error')}"
        delta = 0.0
    else:
        dom_b = max(("t_compute", "t_memory", "t_collective"),
                    key=lambda k: m_before[k])
        delta = 1 - m_after[dom_b] / max(m_before[dom_b], 1e-12)
        sp = m_before["step_time"] / m_after["step_time"]
        verdict = (f"dominant({dom_b}) {m_before[dom_b]:.4f}s -> "
                   f"{m_after[dom_b]:.4f}s ({delta:+.1%}); "
                   f"step {m_before['step_time']:.4f}->"
                   f"{m_after['step_time']:.4f}s ({sp:.2f}x); "
                   f"E {m_before['energy_j']:.0f}->"
                   f"{m_after['energy_j']:.0f}J")
    rec = {"cell": cell, "iteration": name, "hypothesis": hypothesis,
           "before": m_before, "after": m_after, "verdict": verdict,
           "notes": notes}
    print(f"\n[{cell}] {name}\n  H: {hypothesis}\n  -> {verdict}"
          + (f"\n  note: {notes}" if notes else ""), flush=True)
    return rec


def main():
    setup_host_devices()
    OUT.mkdir(parents=True, exist_ok=True)
    log = []

    # ===== Cell A: mamba2-1.3b train_4k — worst train roofline (19.7%),
    # collective-bound: per-layer TP collectives on a 1.3B model ============
    arch, shp = "mamba2-1.3b", "train_4k"
    base_plan = get_config(arch).plan
    a0 = measure(arch, shp, base_plan, "_hc_a0")
    print(f"[A] baseline: {json.dumps({k: round(v, 4) if isinstance(v, float) else v for k, v in a0.items()}, indent=0)}")

    p = base_plan.replace(use_tp=False, microbatches=1)
    a1 = measure(arch, shp, p, "_hc_a1")
    log.append(log_iter(
        "mamba2-1.3b/train_4k", "A1 pure-DP (use_tp=False)",
        "a 1.3B model does not need 16-way TP on 256 chips; mapping the "
        "model axis into DP removes ~2*(T/dp)*d*L per-layer TP traffic "
        "(napkin: 1.05s -> ~0.3s of FSDP+DP collectives) at the cost of "
        "replicated weights (1.3B*4B/256-way ZeRO = fits easily)",
        a0, a1))

    p2 = p.replace(grad_compress="int8_ef")
    a2 = measure(arch, shp, p2, "_hc_a2")
    log.append(log_iter(
        "mamba2-1.3b/train_4k", "A2 +int8 error-feedback grad compression",
        "DP gradient all-reduce is now the collective floor; int8 wire "
        "format cuts its bytes 4x (napkin: dp term /4)",
        a1, a2,
        notes="HLO census cannot see the byte reduction (pjit realizes "
              "compression numerics only; the wire saving needs the "
              "shard_map compressed_psum path — tests/test_substrates.py "
              "covers it); the analytic collective term reflects it."))

    p3 = p2.replace(overlap_collectives=True)
    a3 = measure(arch, shp, p3, "_hc_a3")
    log.append(log_iter(
        "mamba2-1.3b/train_4k", "A3 +collective/compute overlap",
        "remaining FSDP gathers are per-layer and independent of the next "
        "layer's compute; async scheduling hides ~50%",
        a2, a3))

    # ===== Cell B: llama3-405b decode_32k — most collective-bound:
    # seq-sharded KV cache all-gathered across TP every layer ===============
    arch, shp = "llama3-405b", "decode_32k"
    base_plan = get_config(arch).plan
    b0 = measure(arch, shp, base_plan, "_hc_b0")

    p = base_plan.replace(kv_cache_dtype="int8")
    b1 = measure(arch, shp, p, "_hc_b1")
    log.append(log_iter(
        "llama3-405b/decode_32k", "B1 int8 KV cache",
        "the dominant collective is the per-layer all-gather of the "
        "seq-sharded KV cache (kv=8 cannot take 16-way TP); int8 storage "
        "halves the gathered payload (napkin: 1.85GB -> ~0.95GB) and "
        "halves cache HBM traffic; decode quality loss ~0.7% rel "
        "(validated in tests)",
        b0, b1))

    p2 = p.replace(overlap_collectives=True)
    b2 = measure(arch, shp, p2, "_hc_b2")
    log.append(log_iter(
        "llama3-405b/decode_32k", "B2 +collective/compute overlap",
        "cache gathers for layer l+1 can prefetch under layer l compute "
        "(decode compute is tiny but gather latency chains; 50% hide)",
        b1, b2))

    p3 = p2.replace(attn_chunk=2048)
    b3 = measure(arch, shp, p3, "_hc_b3")
    log.append(log_iter(
        "llama3-405b/decode_32k", "B3 larger attention chunk (512->2048)",
        "decode attention over 32k cache in 2048-blocks quarters the "
        "number of chunk-scan iterations (less per-step overhead, same "
        "bytes) — expect small or no dominant-term change (refutation "
        "probe)",
        b2, b3))

    # ===== Cell C: qwen2-7b train_4k — paper-representative: the GA itself
    # finds the plan (paper-faithful), then beyond-paper sharding ===========
    arch, shp = "qwen2-7b", "train_4k"
    cfg = get_config(arch)
    c0 = measure(arch, shp, cfg.plan, "_hc_c0")

    # paper-faithful: GA with (t)^-1/2 (P)^-1/2 over the gene space
    from repro.core import GAConfig, Verifier, run_ga
    v = Verifier(cfg, shp, n_chips=CHIPS, mode="analytic")
    res = run_ga(cfg, "train", v, GAConfig(population=12, generations=8,
                                           seed=0))
    ga_plan = res.best.to_plan()
    c1 = measure(arch, shp, ga_plan, "_hc_c1")
    log.append(log_iter(
        "qwen2-7b/train_4k", "C1 GA-selected plan (PAPER-FAITHFUL)",
        "the paper's method: GA over offload genes with power fitness in "
        "the verification environment; best genome: " + res.best.describe(),
        c0, c1))

    c2_plan = ga_plan.replace(use_tp=False, microbatches=1,
                              grad_compress="int8_ef")
    c2 = measure(arch, shp, c2_plan, "_hc_c2")
    log.append(log_iter(
        "qwen2-7b/train_4k", "C2 BEYOND-PAPER pure-DP + int8 grads",
        "7B fits pure DP+ZeRO on 256 chips (28GB fp32 states / 256); "
        "removes all per-layer TP collectives; DP gradient all-reduce "
        "compressed 4x",
        c1, c2))

    c3_plan = c2_plan.replace(overlap_collectives=True)
    c3 = measure(arch, shp, c3_plan, "_hc_c3")
    log.append(log_iter(
        "qwen2-7b/train_4k", "C3 +overlap",
        "hide half of the remaining FSDP/DP traffic under backward",
        c2, c3))

    (OUT / "hillclimb_log.json").write_text(json.dumps(log, indent=1))
    print(f"\nwrote {OUT/'hillclimb_log.json'}")


if __name__ == "__main__":
    main()


def cell_c_extra():
    """C4 probe: does ZeRO (fsdp) help or hurt pure-DP qwen2-7b?"""
    arch, shp = "qwen2-7b", "train_4k"
    cfg = get_config(arch)
    base = json.loads((OUT / "hillclimb_log.json").read_text())
    c3_plan = cfg.plan.replace(use_tp=False, microbatches=1,
                               grad_compress="int8_ef",
                               overlap_collectives=True, fsdp=False,
                               remat="none", attn_chunk=2048)
    c3 = measure(arch, shp, c3_plan, "_hc_c3b")
    c4 = measure(arch, shp, c3_plan.replace(fsdp=True), "_hc_c4")
    rec = log_iter(
        "qwen2-7b/train_4k", "C4 +ZeRO weight sharding (fsdp=True)",
        "with weights replicated, the census shows ~30GB of all-gathers; "
        "ZeRO shards weights 256-way but must gather them per layer per "
        "pass — expect gathers to GROW (refutation probe: fsdp is a memory "
        "lever, not a collective lever, when the model already fits)",
        c3, c4)
    base.append(rec)
    (OUT / "hillclimb_log.json").write_text(json.dumps(base, indent=1))


if __name__ == "__main__" and os.environ.get("HC_EXTRA"):
    cell_c_extra()


def cell_a_extra():
    """A4/A5: with collectives tamed, attack the new dominant term
    (compute = remat recompute) on mamba2-1.3b."""
    arch, shp = "mamba2-1.3b", "train_4k"
    cfg = get_config(arch)
    base = json.loads((OUT / "hillclimb_log.json").read_text())
    a3_plan = cfg.plan.replace(use_tp=False, microbatches=1,
                               grad_compress="int8_ef",
                               overlap_collectives=True)
    a3 = measure(arch, shp, a3_plan, "_hc_a3")
    a4 = measure(arch, shp, a3_plan.replace(remat="none"), "_hc_a4")
    base.append(log_iter(
        "mamba2-1.3b/train_4k", "A4 remat=none (drop recompute)",
        "collectives are hidden; compute now dominates and remat=full "
        "recomputes the forward (4x fwd-flops multiplier vs 3x) — napkin: "
        "t_compute 0.257 -> 0.193 (-25%) IF the activation stash fits "
        "(~13GB/chip at 1 seq/chip + ZeRO'd states; borderline)",
        a3, a4))
    a5 = measure(arch, shp, a3_plan.replace(remat="dots"), "_hc_a5")
    base.append(log_iter(
        "mamba2-1.3b/train_4k", "A5 remat=dots (middle ground)",
        "if full-stash OOMs or regresses memory, checkpoint only the "
        "matmul outputs: 3.5x multiplier, half the stash",
        a4 if a4["status"] == "OK" else a3, a5))
    (OUT / "hillclimb_log.json").write_text(json.dumps(base, indent=1))


if __name__ == "__main__" and os.environ.get("HC_EXTRA_A"):
    cell_a_extra()

"""Fleet-wide §Perf sweep: re-lower every runnable cell with its optimized
plan and compare the roofline terms against the baseline artifacts.

    PYTHONPATH=src python scripts/optimize_all.py
"""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs import SHAPES, get_config, list_archs   # noqa: E402
from repro.configs.optimized import optimized_plan          # noqa: E402
from repro.core.intensity import estimate_program           # noqa: E402
from repro.core.power import PowerModel, V5E                # noqa: E402
from repro.launch.dryrun import run_cell, setup_host_devices  # noqa: E402

POWER = PowerModel(V5E)
CHIPS = 256
OUT = Path(__file__).resolve().parents[1] / "artifacts" / "hillclimb"


def terms(rec, cfg, shape, plan):
    est = estimate_program(cfg, shape, plan, CHIPS)
    coll = max(rec["collectives"]["total_bytes"], est.coll_bytes)
    tc = POWER.compute_term(est.flops, CHIPS)
    tm = POWER.memory_term(est.hbm_bytes, CHIPS)
    tcl = POWER.collective_term(coll * CHIPS, CHIPS)
    if plan.overlap_collectives:
        tcl *= 0.5
    t = max(tc, tm) + tcl
    return {"t": t, "tc": tc, "tm": tm, "tcl": tcl,
            "roofline": tc / t if t else 0.0,
            "watts": POWER.watts(est.flops, est.hbm_bytes, coll * CHIPS, t,
                                 CHIPS) / CHIPS}


def main():
    setup_host_devices()
    rows = []
    print(f"{'cell':44s} {'base_t':>9s} {'opt_t':>9s} {'speedup':>8s} "
          f"{'roofl':>13s} {'status'}")
    for arch in [a for a in list_archs() if not a.startswith("tiny")]:
        cfg = get_config(arch)
        for shape_name, shape in SHAPES.items():
            if shape_name in cfg.skip_shapes:
                continue
            base_path = (Path("artifacts/dryrun") /
                         f"{arch}__{shape_name}__pod16x16.json")
            base_rec = json.loads(base_path.read_text())
            if base_rec["status"] != "OK":
                continue
            base = terms(base_rec, cfg, shape, cfg.plan)
            plan = optimized_plan(arch, shape.kind)
            if plan == cfg.plan:
                continue
            rec = run_cell(arch, shape_name, multi_pod=False, force=False,
                           plan=plan, tag="_opt")
            cell = f"{arch}/{shape_name}"
            if rec["status"] != "OK":
                print(f"{cell:44s} {base['t']:9.4f} {'—':>9s} {'—':>8s} "
                      f"{'—':>13s} FAIL {rec.get('error', '')[:60]}")
                rows.append({"cell": cell, "status": "FAIL",
                             "error": rec.get("error", "")[:200]})
                continue
            opt = terms(rec, cfg, shape, plan)
            sp = base["t"] / opt["t"]
            print(f"{cell:44s} {base['t']:9.4f} {opt['t']:9.4f} "
                  f"{sp:7.2f}x {base['roofline']*100:5.1f}->"
                  f"{opt['roofline']*100:5.1f}% OK")
            rows.append({"cell": cell, "status": "OK",
                         "base": base, "opt": opt, "speedup": sp,
                         "plan": plan.describe()})
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / "fleet_optimized.json").write_text(json.dumps(rows, indent=1))
    oks = [r for r in rows if r["status"] == "OK"]
    if oks:
        import statistics
        print(f"\n{len(oks)} cells optimized; median speedup "
              f"{statistics.median(r['speedup'] for r in oks):.2f}x; "
              f"geomean "
              f"{(__import__('math').prod(r['speedup'] for r in oks))**(1/len(oks)):.2f}x")


if __name__ == "__main__":
    main()

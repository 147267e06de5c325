"""Serving driver (CLI): a power-governed fleet of continuous-batching
decode loops.

    PYTHONPATH=src python -m repro.launch.serve --arch tiny-test --requests 6

Fleet serving (the control plane over per-node Step-7 governors):

    PYTHONPATH=src python -m repro.launch.serve --arch tiny-test \
        --fleet 2 --requests 12 --tenants teamA,teamB --govern \
        --admission teamB=2.5 --admission-window 64 \
        --ledger-out artifacts/serve/fleet.json

Every run builds ``--fleet N`` nodes (each a ServeLoop + DVFS-envelope
DecodeEnergyMeter bundle, ``repro.fleet.Node``) under one
``FleetScheduler``: requests route to the node with the lowest predicted
marginal Ws/token (``--router round_robin`` for the energy-blind
baseline), a drifted node's load drains to healthy nodes at a checkpoint
boundary (``FleetEvent``), and ``--admission tenant=Ws[,t=Ws]`` throttles
submits against per-tenant budget windows on the merged fleet ledger.
With ``--govern`` each node additionally gets its own PowerGovernor, so
plan migrations keep working underneath the fleet plane.  With
``--placement gate`` the fleet power planner
(``repro.fleet.power``) additionally decides which nodes are powered at
all: idle nodes book their floor watts, consolidation gates spare nodes
to a parked draw at checkpoint boundaries, and gated/drained nodes
re-admit through a canary request (``--placement always_on`` keeps every
node powered — the A/B baseline; ``--slo-queue-depth`` is the queue SLO
the planner must hold).  The persisted ledger re-renders offline via
``scripts/power_report.py --ledger`` (pass it repeatedly to merge
fleets).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.core.adapt import ReconfigPolicy, Reconfigurator
from repro.core.ga import GAConfig
from repro.core.power import modeled_spec
from repro.fleet import (AdmissionController, FleetPolicy, FleetPowerPlanner,
                         FleetScheduler, Node, PowerPlanPolicy, SegmentFleet,
                         VectorArrivals, VectorFleet, VectorNodeSpec)
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.serve.engine import Request
from repro.telemetry import (GovernorPolicy, PowerGovernor, WsBudget,
                             render_rollups)


def parse_diurnal(spec: str) -> list:
    """``1:8:1,160:12:3`` -> due steps [1..8] + [160, 163, ..] — each
    ``start:count:spacing`` burst contributes ``count`` arrivals spaced
    ``spacing`` fleet steps apart, starting at ``start``."""
    due = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        fields = part.split(":")
        if len(fields) != 3:
            raise ValueError(f"bad --diurnal burst {part!r} "
                             f"(want start:count:spacing)")
        start, count, spacing = (int(f) for f in fields)
        if count < 1 or spacing < 1:
            raise ValueError(f"bad --diurnal burst {part!r} "
                             f"(count and spacing must be >= 1)")
        due.extend(start + i * spacing for i in range(count))
    return sorted(due)


def parse_budgets(spec: str, window_steps: int) -> dict:
    """``teamA=2.5,teamB=0.8`` -> {tenant: WsBudget} (Ws per window)."""
    budgets = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        tenant, _, ws = part.partition("=")
        if not tenant or not ws:
            raise ValueError(f"bad --admission entry {part!r} "
                             f"(want tenant=Ws)")
        budgets[tenant.strip()] = WsBudget(budget_ws=float(ws),
                                           window_steps=window_steps)
    return budgets


def build_governor(cfg, args, node: str) -> PowerGovernor:
    recon = Reconfigurator(cfg, args.recon_shape,
                           policy=ReconfigPolicy(),
                           ga=GAConfig(population=6, generations=2),
                           node=node)
    return PowerGovernor(
        recon, plan=cfg.plan,
        policy=GovernorPolicy(flush_every=args.flush_every,
                              checkpoint_every=args.checkpoint_every),
        verify_rung=args.verify_rung)


def run_vector(args) -> None:
    """``--engine vector``: the same fleet/placement/admission surface
    through ``repro.fleet.vector`` — no model, no params, no jax decode;
    token values never exist, only the joule account.  The arrival
    script (rng prompt lengths, tenant cycling, diurnal dues) replays
    the exact recipe the object engine serves, so the two engines are
    A/B-comparable run for run."""
    from repro.core.power import V5E
    from repro.telemetry import envelope_for

    cfg = get_config(args.arch, reduced=args.reduced)
    tenants = [t.strip() for t in args.tenants.split(",") if t.strip()] \
        or ["default"]
    rng = np.random.default_rng(0)
    if args.diurnal:
        dues = parse_diurnal(args.diurnal)
    elif args.arrival_every > 0:
        dues = [i * args.arrival_every for i in range(args.requests)]
    else:
        dues = [0] * args.requests
    plens = []
    for _ in dues:
        plen = int(rng.integers(4, 12))
        rng.integers(2, cfg.vocab_size, size=plen)   # keep the rng
        plens.append(plen)                           # stream aligned
    arrivals = VectorArrivals(
        due=dues,
        tenant_idx=[i % len(tenants) for i in range(len(dues))],
        prompt_len=plens,
        max_new=[args.max_new] * len(dues),
        tenant_names=tenants)

    env = envelope_for(V5E)
    specs = [VectorNodeSpec(f"{args.node}{i}", env, slots=args.slots,
                            step_s=args.tick, max_seq=args.max_seq)
             for i in range(max(args.fleet, 1))]
    admission = None
    if args.admission:
        admission = AdmissionController(
            parse_budgets(args.admission, args.admission_window))
    plan = None
    if args.placement:
        plan = PowerPlanPolicy(mode=args.placement,
                               slo_queue_depth=args.slo_queue_depth)
    policy = FleetPolicy(flush_every=args.flush_every,
                         checkpoint_every=args.checkpoint_every,
                         router=args.router,
                         migrate_on_drift=False)
    if args.engine == "vector":
        vec = VectorFleet(specs, policy=policy, plan=plan,
                          admission=admission, loop_model="serve")
    elif args.engine == "vector-shard":
        from repro.fleet.shard import ShardedSegmentFleet
        vec = ShardedSegmentFleet(specs, policy=policy, plan=plan,
                                  admission=admission,
                                  loop_model="serve",
                                  shards=args.shard_workers,
                                  parallel=args.shard_parallel)
    else:
        # a vector-jax request without jax warns and degrades to the
        # numpy booking plane inside SegmentFleet — same ledger floats,
        # no jit — so scripted runs never die on an optional dep
        backend = "jax" if args.engine == "vector-jax" else "numpy"
        vec = SegmentFleet(specs, policy=policy, plan=plan,
                           admission=admission, loop_model="serve",
                           backend=backend)
    t0 = time.time()
    finished = vec.run(arrivals)
    wall = time.time() - t0

    if admission is not None:
        for rej in admission.rejections:
            print(f"req {rej.rid}: tenant={rej.tenant} THROTTLED @step "
                  f"{rej.step} ({rej.reason})")
    rows = vec.results()
    n_tok = sum(r["tokens"] for r in rows if r["finished"])
    for r in rows:
        if not r["finished"]:
            continue
        print(f"req {r['rid']}: tenant={r['tenant']} node={r['node']} "
              f"({r['tokens']} tokens) {r['prefill_ws']:.3f}Ws prefill + "
              f"{r['decode_ws']:.3f}Ws decode")
    print(f"\nserved {len(finished)} requests, {n_tok} tokens in "
          f"{wall:.2f}s simulated on {vec.n} nodes ({vec.steps} fleet "
          f"steps, router={args.router}, engine={args.engine})")
    for line in render_rollups(vec.ledger, label="fleet[vector]"):
        print(line)
    summary = vec.summary()
    for d in summary["nodes"]:
        print(f"node {d['name']}: served={d['served']} "
              f"{d['total_ws']:.2f}Ws parked={d['parked']}")
    if plan is not None:
        for ev in vec.events:
            print(f"placement {ev.action} @step {ev.step}: {ev.node} "
                  f"(rate={ev.rate:.3f}/step, "
                  f"Lq={ev.queue_depth_est:.2f}, "
                  f"keep {ev.active_target} nodes) {ev.reason}")
        p = summary["placement"]
        print(f"placement[{args.placement}]: states={p['states']} "
              f"max_queue_depth={p['max_queue_depth']} "
              f"(SLO {args.slo_queue_depth:g})")
    if admission is not None:
        for tenant, row in summary["admission"].items():
            print(f"admission {tenant}: spent {row['spent_ws']:.2f}Ws of "
                  f"{row['budget_ws']:.2f}Ws, rejected {row['rejected']} "
                  f"submits (0.00Ws booked)")
    if args.ledger_out:
        print(f"ledger -> {vec.ledger.to_json(args.ledger_out)}")
    if args.trace_spans:
        from pathlib import Path
        result = obs.attribute_joules(list(obs.TRACER.spans), vec.ledger)
        for node_name, row in sorted(
                result.conservation(vec.ledger).items()):
            flag = "ok" if row["ok"] else "DRIFT"
            print(f"attribution {node_name}: ledger {row['ledger_ws']:.4f}Ws "
                  f"attributed {row['attributed_ws']:.4f}Ws "
                  f"(delta {row['delta']:+.2e}) {flag}")
        spans_out = str(Path(args.trace_spans).with_suffix(".spans.jsonl"))
        print(f"spans  -> "
              f"{obs.write_chrome_trace(result.all_spans(), args.trace_spans)}"
              f" (+ {obs.write_spans_jsonl(result.all_spans(), spans_out)})")
        if obs.TRACER.dropped:
            print(f"spans  dropped {obs.TRACER.dropped} past the tracer cap")
    if args.metrics_out:
        print(f"metrics -> {obs.METRICS.write_prometheus(args.metrics_out)}")
        h = obs.METRICS.histogram("queue_wait_s")
        print("queue_wait_s " + " ".join(
            f"p{int(q * 100)}={h.quantile(q):.4f}s" for q in obs.QUANTILES))
    fl = obs.FLIGHT
    if fl.enabled:
        if args.flight_log:
            print(f"flight -> {fl.write_jsonl()} "
                  f"({len(fl.snapshots)} snapshots)")
        elif fl.snapshot_every > 0:
            print(f"flight: {len(fl.snapshots)} snapshots "
                  f"(pass --flight-log to persist)")
        if fl.sampling and obs.TRACER.enabled:
            sa = obs.attribute_joules_sampled(
                list(obs.TRACER.spans), vec.ledger, fl.sample_rate,
                population=fl.population)
            if sa.scaled_ws is None:
                print(f"flight sampled 0/{sa.total_requests} requests "
                      f"(rate {fl.sample_rate:g}) — nothing to scale up")
            else:
                print(f"flight sampled {sa.sampled_requests}/"
                      f"{sa.total_requests} requests "
                      f"(rate {fl.sample_rate:g}): scaled "
                      f"{sa.scaled_ws:.2f}Ws vs ledger "
                      f"{sa.ledger_request_ws:.2f}Ws request-phase "
                      f"(err {sa.error_ws:+.2f}Ws, bound "
                      f"{sa.error_bound_ws:.2f}Ws) "
                      f"{'ok' if sa.ok else 'OUT OF BOUND'}")
    prof = summary.get("profile")
    if prof:
        for p, row in sorted(prof["phases"].items()):
            print(f"profile {p}: {row['seconds']:.4f}s x{row['count']}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-test")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--fleet", type=int, default=1,
                    help="number of serving nodes under the scheduler")
    ap.add_argument("--engine", default="object",
                    choices=("object", "vector", "vector-seg", "vector-jax",
                             "vector-shard"),
                    help="fleet core: the object-level reference "
                         "(ServeLoop per node, real jax decode), the "
                         "stepped repro.fleet.vector core (numpy node "
                         "arrays, joule-equivalent by contract, no model), "
                         "the event-horizon segment engine (vector-seg: "
                         "quiet stretches advance in one batched update), "
                         "the segment engine with the jax lax.scan "
                         "booking backend (vector-jax), or the sharded "
                         "segment engine (vector-shard: node shards with "
                         "a two-level routing argmin, bit-identical "
                         "ledger to vector-seg)")
    ap.add_argument("--shard-workers", type=int, default=2,
                    help="vector-shard: node shards (1/2/4/8...)")
    ap.add_argument("--shard-parallel", default="auto",
                    choices=("auto", "inline", "process"),
                    help="vector-shard booking plane: shared-memory "
                         "worker processes, the in-process fold (bit-"
                         "identical), or auto (processes only when more "
                         "than one CPU is usable)")
    ap.add_argument("--tick", type=float, default=0.004,
                    help="vector engine: virtual TickClock seconds per "
                         "decode/prefill/idle window")
    ap.add_argument("--node", default="node",
                    help="node label prefix (node0..nodeN-1)")
    ap.add_argument("--router", default="energy",
                    choices=("energy", "round_robin"),
                    help="dispatch policy: lowest marginal Ws/token, or "
                         "the energy-blind round-robin baseline")
    ap.add_argument("--tenants", default="default",
                    help="comma-separated tenant labels, cycled across "
                         "requests (per-tenant energy billing)")
    ap.add_argument("--admission", default=None,
                    help="per-tenant Ws budgets, e.g. teamA=2.5,teamB=0.8; "
                         "exhausted tenants are throttled (zero Ws booked)")
    ap.add_argument("--admission-window", type=int, default=0,
                    help="budget window in fleet steps (0 = whole run)")
    ap.add_argument("--arrival-every", type=int, default=0,
                    help="pace arrivals: submit one request every N fleet "
                         "steps (0 = all upfront); paced arrivals are what "
                         "make admission throttling observable")
    ap.add_argument("--no-drain", action="store_true",
                    help="disable cross-node load migration on drift")
    ap.add_argument("--placement", default=None,
                    choices=("gate", "always_on"),
                    help="attach the fleet power planner: consolidate-and-"
                         "gate idle nodes to a parked draw (gate), or keep "
                         "every node powered but book its idle floor "
                         "(always_on, the A/B baseline)")
    ap.add_argument("--slo-queue-depth", type=float, default=4.0,
                    help="expected queued requests the placement planner "
                         "must keep the active node set under")
    ap.add_argument("--govern", action="store_true",
                    help="attach a per-node PowerGovernor (Step-7 loop)")
    ap.add_argument("--flush-every", type=int, default=8,
                    help="serve steps between meter flushes")
    ap.add_argument("--checkpoint-every", type=int, default=16,
                    help="serve steps between checkpoint boundaries")
    ap.add_argument("--recon-shape", default="decode_32k",
                    help="shape the governor's re-search evaluates")
    ap.add_argument("--verify-rung", default=None,
                    choices=("compiled", "replay"),
                    help="re-verify pending plan migrations on this "
                         "measurement rung before applying them")
    ap.add_argument("--ledger-out", default=None,
                    help="persist the fleet ledger (JSON) here")
    ap.add_argument("--trace-out", default=None,
                    help="persist node0's power trace (JSONL) here")
    ap.add_argument("--diurnal", default=None,
                    help="bursty arrival script start:count:spacing[,...]; "
                         "overrides --requests/--arrival-every with due "
                         "fleet steps (troughs let the placement planner "
                         "gate idle nodes)")
    ap.add_argument("--trace-spans", default=None,
                    help="enable span tracing; write the Chrome trace_event "
                         "JSON here (plus <stem>.spans.jsonl raw spans), "
                         "rendered offline via scripts/trace_report.py")
    ap.add_argument("--metrics-out", default=None,
                    help="enable the metrics registry; write the Prometheus "
                         "text exposition here")
    ap.add_argument("--trace-sample", type=float, default=1.0,
                    help="flight recorder: head-sample this fraction of "
                         "request ids for full serve.request span trees "
                         "(deterministic splitmix64 hash; < 1.0 also "
                         "suppresses per-arrival route/submit instants so "
                         "the fused dispatch path stays fused)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="flight recorder: record one fleet time-series "
                         "row (watts, active nodes, queue depth, "
                         "cumulative Ws, arrivals) every N simulated "
                         "fleet steps (0 = off)")
    ap.add_argument("--flight-log", default=None,
                    help="persist the flight-recorder snapshot rows "
                         "(JSONL) here, rendered offline via "
                         "scripts/trace_report.py --flight")
    return ap


def build_fleet(args, cfg):
    """The object engine's fleet: the model with random weights from seed
    0, one ``Node`` per ``--fleet`` under a ``FleetScheduler``.  Returns
    (nodes, scheduler, admission controller or None, planner or None)."""
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    nodes = []
    for i in range(max(args.fleet, 1)):
        name = f"{args.node}{i}"
        governor = build_governor(cfg, args, name) if args.govern else None
        nodes.append(Node.build(name, model, params, slots=args.slots,
                                max_seq=args.max_seq, governor=governor))
    admission = None
    if args.admission:
        admission = AdmissionController(
            parse_budgets(args.admission, args.admission_window))
    planner = None
    if args.placement:
        planner = FleetPowerPlanner(policy=PowerPlanPolicy(
            mode=args.placement, slo_queue_depth=args.slo_queue_depth))
    sched = FleetScheduler(
        nodes,
        policy=FleetPolicy(flush_every=args.flush_every,
                           checkpoint_every=args.checkpoint_every,
                           router=args.router,
                           migrate_on_drift=not args.no_drain),
        admission=admission, planner=planner)
    return nodes, sched, admission, planner


def request_maker(args, cfg, prompt_len: tuple = (4, 12)):
    """``make_request(i)``: request i with a random prompt of
    ``prompt_len`` [low, high) tokens from seed 0, tenants cycled."""
    tenants = [t.strip() for t in args.tenants.split(",") if t.strip()] \
        or ["default"]
    rng = np.random.default_rng(0)

    def make_request(i: int) -> Request:
        plen = int(rng.integers(*prompt_len))
        prompt = rng.integers(2, cfg.vocab_size, size=plen).astype(np.int32)
        return Request(rid=i, prompt=prompt, max_new=args.max_new,
                       tenant=tenants[i % len(tenants)])
    return make_request


def main() -> None:
    enable_compile_cache()
    ap = build_parser()
    args = ap.parse_args()

    if args.engine != "object":
        for flag, name in ((args.govern, "--govern"),
                           (args.trace_out, "--trace-out"),
                           (args.verify_rung, "--verify-rung")):
            if flag:
                ap.error(f"{name} is object-engine only (per-node "
                         f"governors and power traces need the object "
                         f"loops) — drop it or use --engine object")
    flight_on = args.trace_sample < 1.0 or args.snapshot_every > 0 \
        or args.flight_log
    if flight_on and args.engine == "object":
        ap.error("--trace-sample/--snapshot-every/--flight-log ride the "
                 "vectorized cores — pick --engine vector/vector-seg/"
                 "vector-jax/vector-shard")
    if args.trace_spans or args.metrics_out:
        obs.enable()
    if flight_on:
        obs.set_flight(obs.FlightRecorder(sample_rate=args.trace_sample,
                                          snapshot_every=args.snapshot_every,
                                          log_path=args.flight_log))
        if args.trace_sample < 1.0 and not obs.TRACER.enabled:
            obs.enable()        # sampled trees need a live tracer
    if args.engine != "object":
        run_vector(args)
        return

    cfg = get_config(args.arch, reduced=args.reduced)
    nodes, sched, admission, planner = build_fleet(args, cfg)
    make_request = request_maker(args, cfg)
    dev = jax.devices()[0]
    print(f"energy: modeled {modeled_spec(dev).name} watts x measured "
          f"seconds, serving on {dev.platform} ({dev.device_kind})")

    t0 = time.time()
    if args.diurnal:
        arrivals = [(due, make_request(i))
                    for i, due in enumerate(parse_diurnal(args.diurnal))]
        finished = sched.run(arrivals=arrivals)
    elif args.arrival_every > 0:
        arrivals = [make_request(i) for i in range(args.requests)]
        finished = sched.run(arrivals=arrivals,
                             arrival_every=args.arrival_every)
    else:
        arrivals = [make_request(i) for i in range(args.requests)]
        for req in arrivals:
            sched.submit(req)
        finished = sched.run()
    wall = time.time() - t0
    if admission is not None:
        for rej in admission.rejections:
            print(f"req {rej.rid}: tenant={rej.tenant} THROTTLED @step "
                  f"{rej.step} ({rej.reason})")
    n_tok = sum(len(r.out) for r in finished)
    for r in finished:
        print(f"req {r.rid}: tenant={r.tenant} "
              f"prompt={r.prompt.tolist()[:6]}... "
              f"out={r.out[:10]} ({len(r.out)} tokens) "
              f"{r.prefill_ws:.3f}Ws prefill + {r.decode_ws:.3f}Ws decode")
    steps = sum(n.loop.steps_done for n in nodes)
    print(f"\nserved {len(finished)} requests, {n_tok} tokens in {wall:.2f}s "
          f"({n_tok/max(wall,1e-9):.1f} tok/s, {steps} decode steps on "
          f"{len(nodes)} nodes, router={args.router})")

    for line in render_rollups(sched.ledger, label="fleet"):
        print(line)
    for node in nodes:
        d = node.to_dict()
        util = node.loop.utilization.per_phase() \
            if node.loop.utilization is not None else {}
        util_s = " ".join(f"{k}={v:.2f}" for k, v in sorted(util.items()))
        print(f"node {d['name']}: served={d['served']} "
              f"{d['total_ws']:.2f}Ws parked={d['parked']} "
              f"measured_util[{util_s}]")
    for ev in sched.events:
        print(f"fleet drain @step {ev.step} (detected {ev.detected_step}): "
              f"{ev.node} drift {ev.drift_ratio:.2f}x -> "
              f"{len(ev.moved_rids)} requests to {','.join(ev.targets)}")
    if planner is not None:
        for ev in planner.events:
            print(f"placement {ev.action} @step {ev.step}: {ev.node} "
                  f"(rate={ev.rate:.3f}/step, "
                  f"Lq={ev.queue_depth_est:.2f}, "
                  f"keep {ev.active_target} nodes) {ev.reason}")
        print(f"placement[{args.placement}]: states={planner.states} "
              f"max_queue_depth={planner.max_queue_depth} "
              f"(SLO {args.slo_queue_depth:g})")
    if admission is not None:
        for tenant, row in admission.summary(sched.ledger).items():
            print(f"admission {tenant}: spent {row['spent_ws']:.2f}Ws of "
                  f"{row['budget_ws']:.2f}Ws, rejected {row['rejected']} "
                  f"submits (0.00Ws booked)")
    for node in nodes:
        if node.governor is None:
            continue
        for ev in node.governor.events:
            verdict = "plan migration" if ev.applied else \
                (f"REJECTED by {ev.verify_rung} rung "
                 f"({ev.reject_reason[:60]})")
            print(f"reconfig @step {ev.step} (detected {ev.detected_step}, "
                  f"node {ev.node}): drift {ev.drift_ratio:.2f}x -> "
                  f"{verdict}")
    if args.ledger_out:
        print(f"ledger -> {sched.ledger.to_json(args.ledger_out)}")
    if args.trace_out:
        print(f"trace  -> {nodes[0].meter.trace.to_jsonl(args.trace_out)}")
    if args.trace_spans:
        from pathlib import Path
        result = obs.attribute_joules(list(obs.TRACER.spans), sched.ledger)
        for node_name, row in sorted(
                result.conservation(sched.ledger).items()):
            flag = "ok" if row["ok"] else "DRIFT"
            print(f"attribution {node_name}: ledger {row['ledger_ws']:.4f}Ws "
                  f"attributed {row['attributed_ws']:.4f}Ws "
                  f"(delta {row['delta']:+.2e}) {flag}")
        spans_out = str(Path(args.trace_spans).with_suffix(".spans.jsonl"))
        print(f"spans  -> {obs.write_chrome_trace(result.all_spans(), args.trace_spans)}"
              f" (+ {obs.write_spans_jsonl(result.all_spans(), spans_out)})")
        if obs.TRACER.dropped:
            print(f"spans  dropped {obs.TRACER.dropped} past the tracer cap")
    if args.metrics_out:
        print(f"metrics -> {obs.METRICS.write_prometheus(args.metrics_out)}")
        h = obs.METRICS.histogram("queue_wait_s")
        print("queue_wait_s " + " ".join(
            f"p{int(q * 100)}={h.quantile(q):.4f}s" for q in obs.QUANTILES))


if __name__ == "__main__":
    main()

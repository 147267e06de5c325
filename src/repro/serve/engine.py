"""Serving engine: prefill / decode steps + a batched request scheduler.

``make_prefill`` / ``make_decode_step`` are the lowered units (the dry-run
compiles these for the decode/prefill shapes).  ``ServeLoop`` is a simple
continuous-batching scheduler: fixed decode batch, slots freed on EOS/length
and refilled from the queue, greedy sampling.

Pass a ``repro.telemetry.DecodeEnergyMeter`` to attribute per-request
Watt*seconds: every prefill/decode step's wall time + slot utilization is
booked into the meter's trace and ledger, and the step's energy is split
across the requests that shared the batch (``Request.energy_ws``).
Requests carry a ``tenant`` label, so the meter's ledger cells double as
per-tenant energy billing.  Utilization is *measured*, not scheduled: the
loop counts the slots each window actually occupied and records the
fraction as a ``LiveUtilization`` span on the meter's timeline — the
meter's envelope reads that signal (``meter.utilization``), and
``loop.utilization.per_phase()`` is the run's measured occupancy profile.

The loop is also a fleet citizen (``repro.fleet``): ``park()`` stops it
taking new work, and ``drain()`` evicts its queue *and* its active slots
as resumable requests — an evicted request keeps its generated tokens, and
whichever loop it is resubmitted to teacher-forces prompt+output back
through its own cache before decoding the remainder (the cross-node load
migration the ``FleetScheduler`` applies at checkpoint boundaries).

Pass a ``repro.telemetry.governor.PowerGovernor`` too and the loop closes
the paper's Step-7 circuit under serving traffic: every
``governor.policy.flush_every`` steps the meter's fresh energy rolls into
the shared fleet ledger and the node's drift monitor; at checkpoint
boundaries a drift-triggered plan migration is applied (recorded in
``plan_migrations`` — re-jit/restore is the caller's checkpointed swap).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.model import Model
from repro.parallel.sharding import ShardingRules
from repro.telemetry.dvfs import LiveUtilization
from repro.telemetry.energy import (IDLE_PHASE, INFRA_TENANT,
                                    DecodeEnergyMeter)


def make_prefill(model: Model, rules: Optional[ShardingRules] = None):
    def prefill(params, batch, cache):
        return model.prefill(params, batch, cache, rules)
    return prefill


def make_decode_step(model: Model, rules: Optional[ShardingRules] = None):
    def decode_step(params, batch, cache):
        return model.decode_step(params, batch, cache, rules)
    return decode_step


@dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (P,) int32
    max_new: int
    tenant: str = "default"     # billing label for the energy ledger
    out: list[int] = field(default_factory=list)
    done: bool = False
    energy_ws: float = 0.0      # attributed prefill+decode Watt*seconds
    prefill_ws: float = 0.0     # ... the prefill share of it
    decode_ws: float = 0.0      # ... the decode share of it
    enq_t: Optional[float] = None   # host meter time at submit (queue-wait)
    queue_wait_s: float = 0.0   # meter-time spent queued before each fill


class ServeLoop:
    """Continuous-batching greedy decoder over a fixed slot batch."""

    def __init__(self, model: Model, params, batch_slots: int, max_seq: int,
                 eos_id: int = 1,
                 meter: Optional[DecodeEnergyMeter] = None,
                 governor: Optional[Any] = None,
                 node: Optional[str] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.model = model
        self.params = params
        self.slots = batch_slots
        self.max_seq = max_seq
        self.eos = eos_id
        self.meter = meter
        self.governor = governor
        # node label precedence: an explicit argument re-tags the meter; a
        # configured meter otherwise keeps (and lends the loop) its own
        if node is None:
            node = meter.node if meter is not None else "node0"
        elif meter is not None:
            meter.node = node
        self.node = node
        # injectable step timer: deterministic tests tick a virtual clock
        self.clock = clock
        self.queue: list[Request] = []
        self.active: list[Optional[Request]] = [None] * batch_slots
        self.finished: list[Request] = []
        self.plan_migrations: list = []     # (step, new_plan) from governor
        self.steps_done = 0
        self._t_mark: Optional[float] = None    # last step's clock reading
        self.parked = False                 # a parked loop takes no new work
        # measured slot-occupancy signal: unless the meter already carries
        # a measured utilization, the loop feeds it one — real occupancy
        # counters per step window, not the schedule-derived fraction
        self.utilization: Optional[LiveUtilization] = None
        if meter is not None and meter.utilization is None:
            self.utilization = LiveUtilization()
            meter.utilization = self.utilization
        self.cache = model.init_cache(batch_slots, max_seq)
        self.pos = np.zeros(batch_slots, np.int32)
        self._decode = jax.jit(make_decode_step(model))
        self._tokens = np.zeros((batch_slots, 1), np.int32)
        # observability: open request spans by rid + the coalesced idle
        # span (one per idle stretch, not one per idle step)
        self._req_spans: dict = {}
        self._idle_span = None

    def submit(self, req: Request):
        # stamp the enqueue on the meter's busy-time timeline (a peek,
        # not a clock() call — the virtual tick clock must not advance);
        # _fill_slots turns the gap into the request's queue-wait
        if self.meter is not None:
            req.enq_t = self.meter.now
        self.queue.append(req)

    @property
    def occupied_slots(self) -> int:
        """Real occupancy counter: slots currently holding a request."""
        return sum(1 for r in self.active if r is not None)

    @property
    def has_work(self) -> bool:
        return self.occupied_slots > 0 or bool(self.queue
                                               and not self.parked)

    def park(self) -> None:
        """Stop taking new work (queued or resubmitted); in-flight slots
        still decode to completion.  A parked loop is what a fleet
        scheduler drains — and what its router skips.

        Parking does not serve or discard queued requests: they stay in
        ``queue`` (and ``run()`` returns without touching them) until the
        loop is unparked or ``drain()`` hands them to another loop — a
        caller that parks without doing either is choosing to hold that
        traffic."""
        self.parked = True

    def unpark(self) -> None:
        self.parked = False
        # a parked loop was not this meter's responsibility (the fleet
        # power planner books the parked/gated draw itself): idle
        # accounting must restart from re-admission, not back-book the
        # whole parked span at floor watts on top of those bookings
        self._t_mark = None

    def drain(self, include_queue: bool = True) -> list[Request]:
        """Evict the queue and every active slot as resumable requests.

        Evicted requests keep their generated tokens; resubmitting one to
        another loop teacher-forces prompt+output through that loop's
        cache (see ``_fill_slots``) and decoding continues where it
        stopped.  This is the load half of a checkpointed migration: the
        fleet scheduler calls it at a checkpoint boundary, exactly like
        plan migrations apply."""
        moved: list[Request] = []
        if include_queue:
            moved.extend(self.queue)
            self.queue.clear()
        for i, req in enumerate(self.active):
            if req is not None:
                self.active[i] = None
                moved.append(req)
        self._close_idle()
        if self.meter is not None:
            now = self.meter.now
            for req in moved:
                ent = self._req_spans.pop(req.rid, None)
                if ent is not None:
                    if "decode" in ent:
                        ent["decode"].finish(now)
                    ent["root"].tags["outcome"] = "migrated"
                    ent["root"].finish(now)
        return moved

    def _close_idle(self) -> None:
        if self._idle_span is not None:
            self._idle_span.finish()
            self._idle_span = None

    def _record_util(self, phase: str, seconds: float, util: float) -> None:
        """Book the window's measured occupancy on the meter timeline
        (just before the meter integrates it)."""
        if self.utilization is not None and seconds > 0:
            t0 = self.meter.now
            self.utilization.record(phase, t0, t0 + seconds, util)

    def _fill_slots(self):
        if self.parked:
            return
        for i in range(self.slots):
            if self.active[i] is None and self.queue:
                req = self.queue.pop(0)
                self.active[i] = req
                if self.meter is not None and req.enq_t is not None:
                    # the fill ends this hop's queue-wait: both edges are
                    # meter-time peeks, so the virtual clock never moves
                    qw = max(self.meter.now - req.enq_t, 0.0)
                    req.queue_wait_s += qw
                    mx = obs.METRICS
                    if mx.enabled:
                        mx.histogram(
                            "queue_wait_s",
                            "meter-time queued before a slot").observe(qw)
                    tr = obs.TRACER
                    if tr.enabled:
                        root = tr.begin("serve.request", node=self.node,
                                        t0=req.enq_t,
                                        tags={"rid": req.rid,
                                              "tenant": req.tenant})
                        tr.begin("serve.queue_wait", node=self.node,
                                 t0=req.enq_t, parent=root,
                                 tags={"rid": req.rid,
                                       "tenant": req.tenant}
                                 ).finish(self.meter.now)
                        self._req_spans[req.rid] = {"root": root}
                # teacher-forced sequential prefill through the decode path
                # (single-slot prompts stay short in the examples; production
                # prefill uses make_prefill on a full batch).  A migrated
                # request resumes here: its already-generated tokens are
                # teacher-forced along with the prompt, so decode continues
                # from where the drained node stopped.
                seq = np.asarray(req.prompt, np.int32) if not req.out else \
                    np.concatenate([np.asarray(req.prompt, np.int32),
                                    np.asarray(req.out, np.int32)])
                t0 = self.clock()
                for t, tok in enumerate(seq[:-1]):
                    self._step_one(i, int(tok), t)
                if self.meter is not None:
                    # dispatch is asynchronous: wait for the prefill to
                    # finish on the device, or its seconds (and Ws) slide
                    # into the next decode step
                    jax.block_until_ready(self.cache)
                    dt = self.clock() - t0
                    util = 1.0 / self.slots
                    self._record_util("prefill", dt, util)
                    p0 = self.meter.now
                    ws = self.meter.observe(dt, util=util, phase="prefill",
                                            tenants=[req.tenant])
                    req.energy_ws += ws
                    req.prefill_ws += ws
                    ent = self._req_spans.get(req.rid)
                    if ent is not None:
                        tr = obs.TRACER
                        tr.begin("serve.prefill", node=self.node, t0=p0,
                                 parent=ent["root"],
                                 tags={"rid": req.rid, "tenant": req.tenant,
                                       "phase": "prefill", "ws": ws}
                                 ).finish(self.meter.now)
                        ent["decode"] = tr.begin(
                            "serve.decode", node=self.node,
                            t0=self.meter.now, parent=ent["root"],
                            tags={"rid": req.rid, "tenant": req.tenant,
                                  "phase": "decode", "ws": 0.0})
                self.pos[i] = len(seq) - 1
                self._tokens[i, 0] = int(seq[-1])

    def _step_one(self, slot: int, token: int, pos: int):
        toks = self._tokens.copy()
        toks[slot, 0] = token
        batch = {"tokens": jnp.asarray(toks),
                 "pos": jnp.asarray(pos, jnp.int32)}
        _, self.cache = self._decode(self.params, batch, self.cache)

    def _idle_step(self) -> int:
        """A step with no work still burns the envelope floor: book the
        time since the previous step's last clock reading as ``idle``
        Watt*seconds at zero utilization (the DVFS gated floor), billed
        to the infra tenant — so a fleet that keeps this node powered
        sees its draw in the ledger and the meter totals match the
        envelope integral.  Under a virtual ``TickClock`` the window is
        exactly one tick; under a wall clock it is the real silence
        since the node last did (or idled) anything — two back-to-back
        reads would book nothing there."""
        if self.meter is not None:
            now = self.clock()
            if self._t_mark is None:        # first-ever step: no history
                dt = self.clock() - now     # one tick virtual, ~0 wall
                now += dt
            else:
                dt = max(now - self._t_mark, 0.0)
            self._t_mark = now
            self._record_util(IDLE_PHASE, dt, 0.0)
            ws = self.meter.observe(dt, util=0.0, phase=IDLE_PHASE,
                                    tenants=[INFRA_TENANT])
            tr = obs.TRACER
            if tr.enabled and dt > 0:
                # coalesce: one span per idle stretch, extended each tick
                t1 = self.meter.now
                if self._idle_span is None:
                    self._idle_span = tr.begin(
                        "serve.idle", node=self.node, t0=t1 - dt,
                        tags={"phase": IDLE_PHASE, "tenant": INFRA_TENANT,
                              "ws": 0.0})
                self._idle_span.extend(t1, ws=ws)
        self.steps_done += 1
        if self.governor is not None and self.meter is not None:
            self.governor.tick(self.meter, self.steps_done, node=self.node)
        return 0

    def step(self) -> int:
        """One decode step across all active slots. Returns #active.

        With no active slots (empty queue, or parked) the step books
        floor-watts ``idle`` energy instead of nothing — see
        ``_idle_step``.  ``run()`` never idles (it exits when the loop
        has no work); only an external stepper such as the
        ``FleetScheduler`` holds an unloaded loop powered."""
        self._fill_slots()
        if all(r is None for r in self.active):
            return self._idle_step()
        self._close_idle()
        participants = [r for r in self.active if r is not None]
        t0 = self.clock()
        pos = int(max(self.pos[i] for i, r in enumerate(self.active)
                      if r is not None))
        batch = {"tokens": jnp.asarray(self._tokens),
                 "pos": jnp.asarray(pos, jnp.int32)}
        logits, self.cache = self._decode(self.params, batch, self.cache)
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        if self.meter is not None:
            # the step's Ws splits evenly across the requests in the batch;
            # the measured occupancy (slots that actually decoded this
            # window) drives the envelope through the utilization signal
            dt = self.clock() - t0
            self._t_mark = t0 + dt      # idle accounting resumes here
            util = len(participants) / self.slots
            self._record_util("decode", dt, util)
            ws = self.meter.observe(dt, util=util, phase="decode",
                                    tenants=[r.tenant for r in participants])
            share = ws / len(participants)
            now_m = self.meter.now
            mx, tr = obs.METRICS, obs.TRACER
            for r in participants:
                r.energy_ws += share
                r.decode_ws += share
                if mx.enabled:
                    mx.histogram("decode_ws_per_token",
                                 "Ws billed per generated token"
                                 ).observe(share)
                if tr.enabled:
                    ent = self._req_spans.get(r.rid)
                    if ent is not None and "decode" in ent:
                        ent["decode"].extend(now_m, ws=share)
        n_active = 0
        for i, req in enumerate(self.active):
            if req is None:
                continue
            tok = int(nxt[i])
            req.out.append(tok)
            self.pos[i] += 1
            self._tokens[i, 0] = tok
            if tok == self.eos or len(req.out) >= req.max_new \
                    or self.pos[i] >= self.max_seq - 1:
                req.done = True
                self.active[i] = None
                self.finished.append(req)
                ent = self._req_spans.pop(req.rid, None)
                if ent is not None and self.meter is not None:
                    end = self.meter.now
                    if "decode" in ent:
                        ent["decode"].finish(end)
                    ent["root"].tags["tokens"] = len(req.out)
                    ent["root"].finish(end)
            else:
                n_active += 1
        self.steps_done += 1
        if self.governor is not None and self.meter is not None:
            new_plan = self.governor.tick(self.meter, self.steps_done,
                                          node=self.node)
            if new_plan is not None:
                # checkpointed migration: the caller re-jits/restores with
                # the new plan; the loop records that the boundary fired
                self.plan_migrations.append((self.steps_done, new_plan))
        return n_active

    def run(self, max_steps: int = 10_000) -> list[Request]:
        """Drain queue + active slots; returns requests finished this run."""
        n0 = len(self.finished)
        for _ in range(max_steps):
            if not self.has_work:
                break
            self.step()
        self._close_idle()
        if self.governor is not None and self.meter is not None:
            # drain trailing un-flushed energy so the fleet ledger totals
            # match the meter at run end; govern=False keeps the partial
            # tail window out of the drift median
            self.governor.flush(self.meter, self.steps_done, node=self.node,
                                govern=False)
        return self.finished[n0:]

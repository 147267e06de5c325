"""``repro.fleet.jax_backend`` — the jax array backend for the
segment-batched fleet core (``repro.fleet.segment``).

The segment engine splits its bookkeeping into two planes:

  * the **control plane** (routing, admission, the planner, clocks,
    decode meters, per-tenant spend) stays eager numpy — every branch
    the reference engine takes reads these live, so deferring them
    would change placement control flow;
  * the **booking plane** (the dense decode/idle ledger cells, phase
    rollups and per-node Ws) is a pure fold over per-step/per-stretch
    records — no control flow ever reads it mid-run (admission reads
    ``_tenant_ws``, which the fleet keeps eager).

This module implements the booking plane as a jit-compiled
``lax.scan`` over fixed-size record chunks.  Records are buffered
dense (one ``[n]``/``[n, t]`` row set per live step or quiet stretch),
padded with no-op zero records to the chunk size so one compilation
serves the whole run, and folded into float64 carry tensors under
``jax.enable_x64`` — scoped, never the global flag, so co-resident jax
code keeps its default precision.  The carries are added into the
fleet's numpy cell tensors at ``finalize``.  Every kernel here runs on
the host's CPU device, named explicitly (``_on_host``): this is float64
bookkeeping, and it stays off the accelerator that serves the model.

Float contract: every scan operation is an elementwise add or
max-compare mirroring the numpy accumulator, so the jax path lands
within reduction-reorder distance (~1e-15 rel) of the stepped
reference — far inside the 1e-6 equivalence budget — while integer
counts and placement events stay exact.
"""
from __future__ import annotations

from contextlib import contextmanager

import numpy as np

try:                                    # pragma: no cover - import gate
    import jax
    import jax.numpy as jnp
    HAVE_JAX = True
except ImportError:                     # pragma: no cover
    jax = None
    jnp = None
    HAVE_JAX = False


@contextmanager
def _on_host():
    """Run on the CPU device in float64 (scoped, not the global flag)."""
    with jax.default_device(jax.devices("cpu")[0]), jax.enable_x64(True):
        yield


#: records folded per compiled scan call (padded to this length)
CHUNK = 64

# ----------------------------------------------------------------------
# control-plane kernels
# ----------------------------------------------------------------------
#
# The routing argmin and the planner's Erlang-C k-search are the two
# control-plane hot spots.  Both ship here as jit-compiled twins of the
# numpy reference implementations below — numpy stays the bit-exact
# reference the engines run on (placement control flow reads these
# live), the jax twins are the accelerator path for offline sweeps and
# the planner's ``backend="jax"`` opt-in.  The equivalence contract
# (tests/test_fleet_jax_kernels.py) pins the jax results to the numpy
# references: integer winners exactly, Lq floats within reduction-
# reorder distance.


def route_argmin_np(marg, load, rank, active):
    """Reference energy-router winner: lowest marginal Ws/token among
    ``active`` nodes, float-equal marginal ties broken by lowest load,
    load ties by lowest name rank.  Returns -1 with no active node."""
    marg = np.asarray(marg, np.float64)
    active = np.asarray(active, bool)
    idxs = np.flatnonzero(active)
    if idxs.size == 0:
        return -1
    mc = marg[idxs]
    ti = idxs[mc == mc.min()]
    if ti.size > 1:
        lc = np.asarray(load, np.float64)[ti]
        ti = ti[lc == lc.min()]
        if ti.size > 1:
            rc = np.asarray(rank)[ti]
            return int(ti[rc.argmin()])
    return int(ti[0])


def _build_route_kernel():
    """jit twin of ``route_argmin_np``: one masked three-level
    lexicographic argmin over the watt-table marginal costs.  Inactive
    lanes are padded to +inf so they never win (the stepped engine's
    inf-padding contract); the final argmin runs on the rank column,
    which is a permutation, so the winner is unique."""
    def kernel(marg, load, rank, active):
        inf = jnp.asarray(jnp.inf, marg.dtype)
        m = jnp.where(active, marg, inf)
        t1 = active & (m == m.min())
        l = jnp.where(t1, load, inf)
        t2 = t1 & (l == l.min())
        r = jnp.where(t2, rank, jnp.asarray(jnp.iinfo(rank.dtype).max,
                                            rank.dtype))
        return jnp.where(active.any(), jnp.argmin(r), -1)
    return jax.jit(kernel)


_route_kernel = None


def route_argmin_jax(marg, load, rank, active):
    """Run the jit routing kernel (compiled once, float64-scoped)."""
    global _route_kernel
    if not HAVE_JAX:
        raise RuntimeError("route_argmin_jax needs jax installed")
    with _on_host():
        if _route_kernel is None:
            _route_kernel = _build_route_kernel()
        return int(_route_kernel(jnp.asarray(marg, jnp.float64),
                                 jnp.asarray(load, jnp.float64),
                                 jnp.asarray(rank, jnp.int64),
                                 jnp.asarray(active, bool)))


def _build_lq_kernel(c_max: int):
    """jit twin of ``ArrivalForecaster.expected_queue_depth_many``:
    price every candidate server count in one pass.  The term chain is
    one cumprod and the partial sums one cumsum (the scalar Erlang-C's
    sequential reductions), followed by gathers at each candidate —
    the same op sequence as the numpy sweep, so the floats land within
    reduction-reorder distance of the reference.  ``c_max`` (the
    largest candidate count — the fleet's total slots in the planner's
    k-search) is static, so one compilation serves a whole run."""
    def kernel(servers, lam, mu, horizon):
        servers = jnp.maximum(servers, 1)
        offered = lam / mu
        terms = (jnp.cumprod(offered / jnp.arange(1, c_max,
                                                  dtype=jnp.float64))
                 if c_max > 1 else jnp.zeros(0, jnp.float64))
        partial_all = jnp.cumsum(
            jnp.concatenate([jnp.ones(1, jnp.float64), terms]))
        partial = partial_all[servers - 1]
        term = (jnp.where(servers > 1,
                          terms[jnp.maximum(servers - 2, 0)], 1.0)
                if c_max > 1 else jnp.ones(servers.shape, jnp.float64))
        term = term * (offered / servers)
        rho = offered / servers
        last = term / jnp.maximum(1.0 - rho, _MIN_GAP_J)
        denom = partial + last
        p_wait = jnp.where(
            (denom <= 0.0) | ~jnp.isfinite(denom), 1.0,
            jnp.clip(last / jnp.where(denom != 0.0, denom, 1.0),
                     0.0, 1.0))
        lq = p_wait * rho / jnp.maximum(1.0 - rho, _MIN_GAP_J)
        lq = jnp.where(jnp.isfinite(lq), jnp.maximum(lq, 0.0),
                       horizon * mu)
        h = jnp.maximum(horizon, 1.0)
        sat = lam * h + jnp.maximum((lam - servers * mu) * h, 0.0)
        return jnp.where(rho >= 1.0, sat, lq)
    return jax.jit(kernel)


_MIN_GAP_J = 1e-6                       # forecast.py's _MIN_GAP
_lq_kernels: dict = {}


def expected_queue_depth_many_jax(servers, service_time, lam,
                                  horizon=64.0):
    """jit Erlang-C sweep over candidate server counts.

    Mirrors ``ArrivalForecaster.expected_queue_depth_many`` given the
    same forecast rate ``lam``.  Kernels are cached per (chain length,
    candidate count) — both fixed for a given fleet, so the planner
    pays one trace on its first window and jit dispatch after."""
    if not HAVE_JAX:
        raise RuntimeError(
            "expected_queue_depth_many_jax needs jax installed")
    servers = np.maximum(np.asarray(servers, np.int64), 1)
    if servers.size == 0:
        return np.zeros(0)
    service_time = max(float(service_time), _MIN_GAP_J)
    c_max = int(servers.max())
    with _on_host():
        key = (c_max, servers.size)
        kern = _lq_kernels.get(key)
        if kern is None:
            kern = _lq_kernels[key] = _build_lq_kernel(c_max)
        out = kern(jnp.asarray(servers),
                   jnp.float64(lam),
                   jnp.float64(1.0 / service_time),
                   jnp.float64(max(float(horizon), 0.0)))
        return np.asarray(out)


def _dec_scan(chunk: int):
    """Build the decode-cell fold: carry += one chunk of dec records."""
    def body(carry, rec):
        cws, cs, cn, cpk, pws, ps, pn, ppk, nws = carry
        tc, sc, cnk, w, dt, ws, pn_inc, wmax = rec
        cws = cws + tc
        cs = cs + sc
        cn = cn + cnk
        cpk = jnp.where(cnk > 0, jnp.maximum(cpk, w[:, None]), cpk)
        pws = pws + jnp.sum(ws)
        ps = ps + jnp.sum(dt)
        pn = pn + pn_inc
        ppk = jnp.where(wmax > ppk, wmax, ppk)
        nws = nws + ws
        return (cws, cs, cn, cpk, pws, ps, pn, ppk, nws), None

    def run(carry, recs):
        return jax.lax.scan(body, carry, recs)[0]

    return jax.jit(run)


def _idle_scan(chunk: int):
    """Build the idle-cell fold (infra tenant only): carry += chunk."""
    def body(carry, rec):
        cws, cs, cn, cpk, pws, ps, pn, ppk, nws = carry
        w, dt, ws, cnk, pn_inc, wmax = rec
        cws = cws + ws
        cs = cs + dt
        cn = cn + cnk
        # the stepped reference books idle peaks with np.maximum
        # (NaN-propagating), masked here to the nodes actually idling
        cpk = jnp.where(cnk > 0, jnp.maximum(cpk, w), cpk)
        pws = pws + jnp.sum(ws)
        ps = ps + jnp.sum(dt)
        pn = pn + pn_inc
        ppk = jnp.where(wmax > ppk, wmax, ppk)
        nws = nws + ws
        return (cws, cs, cn, cpk, pws, ps, pn, ppk, nws), None

    def run(carry, recs):
        return jax.lax.scan(body, carry, recs)[0]

    return jax.jit(run)


class JaxAccumulator:
    """Deferred booking plane: buffer dense records, fold in chunks.

    The fleet calls ``book_dec``/``book_idle`` with the *already
    computed* batched arrays (indices, per-tenant cell adds, watt
    points); this class only defers the fold.  ``finalize`` drains the
    buffers and adds the carries into the fleet's numpy tensors.
    """

    def __init__(self, fleet):
        if not HAVE_JAX:
            raise RuntimeError(
                "backend='jax' needs jax installed — it is optional; "
                "use backend='numpy' (engine vector-seg) instead")
        self.f = fleet
        n = fleet.n
        t = len(fleet.tenant_names)
        self.n, self.t = n, t
        self._dec_recs: list = []
        self._idle_recs: list = []
        with _on_host():
            z_nt = jnp.zeros((n, t), jnp.float64)
            z_nti = jnp.zeros((n, t), jnp.int64)
            z_n = jnp.zeros(n, jnp.float64)
            z_ni = jnp.zeros(n, jnp.int64)
            z = jnp.float64(0.0)
            zi = jnp.int64(0)
            self._dec_carry = (z_nt, z_nt, z_nti, z_nt, z, z, zi, z, z_n)
            self._idle_carry = (z_n, z_n, z_ni, z_n, z, z, zi, z, z_n)
        self._dec_fold = _dec_scan(CHUNK)
        self._idle_fold = _idle_scan(CHUNK)

    # -- record builders ----------------------------------------------

    def book_dec(self, bi, cnt, tcell, scell, w, dt, ws, k, wmax):
        n, t = self.n, self.t
        tc = np.zeros((n, t))
        sc = np.zeros((n, t))
        cnk = np.zeros((n, t), np.int64)
        dw = np.zeros(n)
        ddt = np.zeros(n)
        dws = np.zeros(n)
        tc[bi] = tcell
        sc[bi] = scell
        cnk[bi] = cnt * k
        dw[bi] = w
        ddt[bi] = dt
        dws[bi] = ws
        self._dec_recs.append(
            (tc, sc, cnk, dw, ddt, dws, np.int64(bi.size * k),
             np.float64(wmax)))
        if len(self._dec_recs) >= CHUNK:
            self._flush_dec()

    def book_idle(self, ii, w, dt, ws, k, wmax):
        n = self.n
        iw = np.zeros(n)
        idt = np.zeros(n)
        iws = np.zeros(n)
        cnk = np.zeros(n, np.int64)
        iw[ii] = w
        idt[ii] = dt
        iws[ii] = ws
        cnk[ii] = k
        self._idle_recs.append(
            (iw, idt, iws, cnk, np.int64(ii.size * k), np.float64(wmax)))
        if len(self._idle_recs) >= CHUNK:
            self._flush_idle()

    # -- folds --------------------------------------------------------

    @staticmethod
    def _pad_stack(recs, chunk):
        """Stack record tuples into chunk-length arrays, zero-padding
        the tail (wmax pads to -inf so padded records update nothing)."""
        pad = chunk - len(recs)
        cols = list(zip(*recs))
        out = []
        for ci, col in enumerate(cols):
            a = np.stack(col)
            if pad:
                shape = (pad,) + a.shape[1:]
                if ci == len(cols) - 1:         # wmax column
                    fill = np.full(shape, -np.inf)
                else:
                    fill = np.zeros(shape, a.dtype)
                a = np.concatenate([a, fill])
            out.append(a)
        return tuple(out)

    def _flush_dec(self):
        if not self._dec_recs:
            return
        recs = self._pad_stack(self._dec_recs, CHUNK)
        self._dec_recs = []
        with _on_host():
            jrecs = tuple(jnp.asarray(a) for a in recs)
            self._dec_carry = self._dec_fold(self._dec_carry, jrecs)

    def _flush_idle(self):
        if not self._idle_recs:
            return
        recs = self._pad_stack(self._idle_recs, CHUNK)
        self._idle_recs = []
        with _on_host():
            jrecs = tuple(jnp.asarray(a) for a in recs)
            self._idle_carry = self._idle_fold(self._idle_carry, jrecs)

    def finalize(self):
        """Drain buffers and add the deferred deltas into the fleet's
        numpy account (phase indices match ``vector.PHASES``)."""
        self._flush_dec()
        self._flush_idle()
        f = self.f
        from repro.fleet.vector import _DEC, _IDLE
        cws, cs, cn, cpk, pws, ps, pn, ppk, nws = \
            [np.asarray(x) for x in self._dec_carry]
        f._cell_ws[:, :, _DEC] += cws
        f._cell_s[:, :, _DEC] += cs
        f._cell_n[:, :, _DEC] += cn
        f._cell_peak[:, :, _DEC] = np.maximum(f._cell_peak[:, :, _DEC], cpk)
        f._phase_ws[_DEC] += pws
        f._phase_s[_DEC] += ps
        f._phase_n[_DEC] += pn
        if ppk > f._phase_peak[_DEC]:
            f._phase_peak[_DEC] = ppk
        f._node_ws += nws
        iws_c, is_c, in_c, ipk, pws, ps, pn, ppk, nws = \
            [np.asarray(x) for x in self._idle_carry]
        f._cell_ws[:, f._infra, _IDLE] += iws_c
        f._cell_s[:, f._infra, _IDLE] += is_c
        f._cell_n[:, f._infra, _IDLE] += in_c
        f._cell_peak[:, f._infra, _IDLE] = np.maximum(
            f._cell_peak[:, f._infra, _IDLE], ipk)
        f._phase_ws[_IDLE] += pws
        f._phase_s[_IDLE] += ps
        f._phase_n[_IDLE] += pn
        if ppk > f._phase_peak[_IDLE]:
            f._phase_peak[_IDLE] = ppk
        f._node_ws += nws

"""Pallas event-driven simulator: the closed-loop (p_hit x seed) grid.

Prong B's measurement grid (`repro.core.simulator.simulate_network`) is a
vmapped ``lax.while_loop`` whose per-event cost is dominated by RNG
plumbing: every event splits a threefry key 4 (closed) to 7 (coalescing)
ways before drawing at most 3 variates.  On an accelerator the split
chains serialize; this kernel replaces them with a **counter-based 32-bit
hash stream** (a splitmix-style finalizer over ``seed ^ ctr``) — one
multiply-xorshift chain per variate, vectorizes over lanes, and stays in
uint32 end to end (the repo's jit-hash64 lint bans 64-bit dtypes in
traced scopes).

Everything else — FIFO release by enqueue sequence, multi-server busy
accounting, route advance, warmup snapshots — is the exact event loop of
``_simulate``, restricted to the closed-loop non-coalescing case (the
open-loop/MSHR prongs keep the scan backend; ``simulate_network`` raises
if you ask the pallas backend for them).

Because the RNG stream differs, agreement with ``simulate_network`` is
*statistical* (same network, same mean/dispersion laws — pinned within a
few percent by tests), while the pallas kernels and the vmapped twin share
:func:`_sim_lane` verbatim and are therefore bit-identical, the same
twin-pair structure as the replay kernel: the lane is written once
against the indexed-state interface of :mod:`repro.indexed_state` and run
on an ``ArrayState`` by the twin and on one of two kernel layouts:

* lane-packed (:func:`_lane_grid`, untraced grids): a ``LaneRefState``,
  ``grid=(ceil(lanes / 128),)``, each cell a block of up to 128 lanes,
  one per vector lane — every table ``(rows, 128)`` in VMEM with element
  ``i`` of lane ``l`` at ``[i, l]``, per-lane scalars ``(1, 128)`` rows,
  the loop run while any lane of the block is active;
* per lane (``trace_cap > 0``): a ``RefState``, one grid cell per lane;
  job tables and the record rings in VMEM rows, spec tables, per-job
  scalars and busy counts in SMEM, transcendentals on the vector unit.

``interpret=None`` auto-selects: real kernel on TPU, jitted vmapped twin
on CPU; ``interpret=True`` runs the kernel body under the pallas
interpreter (CI fallback, tests only).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.simspec import (BIG_SEQ, INF_NS, SimResult, SimSpec,
                                network_tables)
from repro.kernels import on_tpu
from repro.indexed_state import ArrayState
from repro.kernels.state import LANES, LaneRefState, RefState, vmem_rows
from repro.obs import spans
from repro.obs.trace import CLS_HIT, CLS_MISS, TraceRings, decode_trace_grid

_GOLDEN = np.uint32(0x9E3779B9)
_MIX1 = np.uint32(0x21F0AAAD)
_MIX2 = np.uint32(0x735A2D97)
_INV24 = np.float32(1.0 / (1 << 24))


def _mix(x: jnp.ndarray) -> jnp.ndarray:
    """Murmur3-style 32-bit finalizer (full avalanche)."""
    x = x ^ (x >> 16)
    x = x * _MIX1
    x = x ^ (x >> 15)
    x = x * _MIX2
    x = x ^ (x >> 15)
    return x


# -- table names (the indexed state _sim_lane is written against) -----------
# per-lane spec, read only (2-D spec arrays flattened row-major)
IS_QUEUE = "is_queue"      # (K,) int32 0/1
SVC_NS = "svc_ns"          # (K,) float32 mean service
DIST_ID = "dist_id"        # (K,) int32
DIST_PAR = "dist_params"   # (K*4,) float32
BRANCH_CUM = "branch_cum"  # (B,) float32 cumulative branch law
VISITS = "visits"          # (B*L,) int32 routes, -1 padded
SERVERS = "servers"        # (K,) int32
SPEC_TABLES = (IS_QUEUE, SVC_NS, DIST_ID, DIST_PAR, BRANCH_CUM, VISITS,
               SERVERS)
# per-job state: READY/STATION/ENQ form the vector group
READY = "ready"            # (N,) int32 ns to the job's next event
STATION = "station"        # (N,) int32
ENQ = "enq"                # (N,) int32 FIFO enqueue sequence, BIG_SEQ idle
JOB_GROUP = (READY, STATION, ENQ)
BRANCH = "branch"          # (N,) int32
POS = "pos"                # (N,) int32 position on the route
BUSY = "busy"              # (K,) int32 busy servers per station
# tracing (trace_cap > 0): per-branch miss class, the record rings
# (cap + 1 rows, the last one scrap) and per-job visit stamps
BMISS = "bmiss"            # (B,) int32
RING_TABLES = ("req", "r_branch", "cls", "nvis", "parked_us")
RING_ROWS = ("enter_us", "leave_us")        # ((cap+1)*L,) float32
SCR_ENTER, SCR_LEAVE = "scr_enter_us", "scr_leave_us"  # (N*L,) float32
# job-table values a padded kernel slot holds: never the next event,
# never a waiter (a padded slot is invisible to every mask of _sim_lane)
_JOB_PAD = {READY: INF_NS, STATION: np.int32(-1), ENQ: BIG_SEQ}


def _service_ns(st, u, k):
    """Service draw (ns, int32 >= 1) — the `_sample_service_ns` formulas
    with the uniform supplied by the caller's counter stream."""
    params = [st.get(DIST_PAR, k * 4 + i) for i in range(4)]

    def draw(u, mean, did, alpha, lo, hi, raw_mean):
        s_exp = -jnp.log(u)
        ratio = 1.0 - (lo / hi) ** alpha
        s_par = lo * (1.0 - u * ratio) ** (-1.0 / alpha) / raw_mean
        unit = jnp.where(did == 0, np.float32(1.0),
                         jnp.where(did == 1, s_exp, s_par))
        return jnp.maximum(jnp.round(unit * mean), np.float32(1.0))

    ns = st.vmath(draw, u, st.get(SVC_NS, k),
                  st.get(DIST_ID, k).astype(jnp.float32), *params)
    return ns.astype(jnp.int32)


def _sim_lane(st, seed, *, n_requests: int, warmup: int, mpl: int,
              max_events: int, route_len: int, trace_cap: int = 0):
    """One (p_hit, seed) lane of the closed-loop simulation.

    Shared verbatim by the pallas kernel body (``st`` a RefState) and the
    vmapped CPU twin (an ArrayState).  Returns (st, x, completed, events,
    t_measured_us, n_traced); with ``trace_cap > 0`` the record rings in
    ``st`` are filled (tracing draws no RNG, so the simulated system is
    bit-identical either way).
    """
    n = mpl
    n_l = route_len
    base = _mix(seed.astype(jnp.uint32) + _GOLDEN)

    def u01(ctr):
        z = _mix(base + jnp.asarray(ctr).astype(jnp.uint32) * _GOLDEN)
        # 24 bits: exact through int32 on the way to float32
        z24 = (z >> np.uint32(8)).astype(jnp.int32)
        return st.vmath(lambda z: jnp.clip(z.astype(jnp.float32) * _INV24,
                                           np.float32(1e-7),
                                           np.float32(1.0 - 1e-7)), z24)

    # --- init: all mpl jobs start a request at their (think) first station.
    def init_job(st, j):
        b = st.count_less(BRANCH_CUM, u01(j))
        k0 = st.get(VISITS, b * n_l)
        st = st.set(BRANCH, j, b)
        st = st.set(POS, j, 0)
        st = st.set(STATION, j, k0)
        st = st.set(ENQ, j, BIG_SEQ)
        return st.set(READY, j, _service_ns(st, u01(n + j), k0)), j + 1

    st, _ = st.while_loop(lambda st, j: j < n, init_job, np.int32(0))

    def cond(st, carry):
        completed, events = carry[1], carry[6]
        return (completed < n_requests) & (events < max_events)

    def body(st, carry):
        (seq_ctr, completed, elapsed_us, warm_completed, warm_elapsed_us,
         ctr, events, n_traced) = carry
        u_svc1 = u01(ctr)
        u_svc2 = u01(ctr + 1)
        u_branch = u01(ctr + 2)
        ctr = ctr + 3

        j, t = st.argmin(lambda v: (True, v.ready))
        elapsed_us = elapsed_us + t.astype(jnp.float32) * np.float32(1e-3)
        st, _ = st.update(READY, lambda v: (v.ready < INF_NS, v.ready - t))
        k_cur = st.get(STATION, j)

        # ---- hand the server job j held (if any) to its FIFO successor.
        def release(st):
            w, seq_w = st.argmin(lambda v: (
                (v.station == k_cur) & (v.ready == INF_NS) & (v.slot != j),
                v.enq))
            has_waiter = seq_w < BIG_SEQ
            svc = _service_ns(st, u_svc1, k_cur)
            st = st.set_if(READY, w, has_waiter, svc)
            st = st.set_if(ENQ, w, has_waiter, BIG_SEQ)
            busy = st.get(BUSY, k_cur)
            return st.set(BUSY, k_cur, busy - (~has_waiter).astype(jnp.int32)), ()

        st, _ = st.cond(st.get(IS_QUEUE, k_cur) != 0, release,
                        lambda st: (st, ()))

        # ---- advance job j along its route (or complete + restart).
        pos_j = st.get(POS, j)
        branch_j = st.get(BRANCH, j)
        nxt_pos = pos_j + 1
        route_next = jnp.where(
            nxt_pos < n_l, st.get(VISITS, branch_j * n_l + nxt_pos % n_l),
            np.int32(-1))
        done = route_next < 0
        new_branch = st.count_less(BRANCH_CUM, u_branch)
        branch_next = jnp.where(done, new_branch, branch_j)
        pos_next = jnp.where(done, np.int32(0), nxt_pos)
        k_next = jnp.where(done, st.get(VISITS, new_branch * n_l), route_next)
        if trace_cap:
            # Stamp j's departure from its current visit; on completion
            # emit the finished request's record (req id = completed so
            # far — the same id the threefry engine would assign) into
            # the ring, else into its scrap row.
            st = st.set(SCR_LEAVE, j * n_l + pos_j, elapsed_us)
            idx = jnp.where(done, completed % trace_cap, np.int32(trace_cap))
            miss = st.get(BMISS, branch_j) != 0
            rec = (completed, branch_j,
                   jnp.where(miss, np.int32(CLS_MISS), np.int32(CLS_HIT)),
                   pos_j + 1, np.float32(0.0))
            for i, name in enumerate(RING_TABLES):
                st = st.set(name, idx, rec[i])
            for name, scr in zip(RING_ROWS, (SCR_ENTER, SCR_LEAVE)):
                for l in range(n_l):
                    st = st.set(name, idx * n_l + l,
                                st.get(scr, j * n_l + l))
            st = st.set(SCR_ENTER, j * n_l + pos_next, elapsed_us)
            n_traced = n_traced + done.astype(jnp.int32)
        completed = completed + done.astype(jnp.int32)

        # ---- place j at k_next.
        svc_next = _service_ns(st, u_svc2, k_next)
        is_q = st.get(IS_QUEUE, k_next) != 0
        busy = st.get(BUSY, k_next)
        starts_now = (~is_q) | (busy < st.get(SERVERS, k_next))
        waits = ~starts_now
        st = st.set(READY, j, jnp.where(starts_now, svc_next, INF_NS))
        st = st.set(ENQ, j, jnp.where(waits, seq_ctr, BIG_SEQ))
        seq_ctr = seq_ctr + waits.astype(jnp.int32)
        st = st.set(BUSY, k_next, busy + (is_q & starts_now).astype(jnp.int32))
        st = st.set(STATION, j, k_next)
        st = st.set(BRANCH, j, branch_next)
        st = st.set(POS, j, pos_next)

        # ---- warmup bookkeeping.
        warm_now = (completed >= warmup) & (warm_completed < 0)
        warm_completed = jnp.where(warm_now, completed, warm_completed)
        warm_elapsed_us = jnp.where(warm_now, elapsed_us, warm_elapsed_us)
        return st, (seq_ctr, completed, elapsed_us, warm_completed,
                    warm_elapsed_us, ctr, events + 1, n_traced)

    zero = np.int32(0)
    st, carry = st.while_loop(cond, body, (
        zero,                  # seq_ctr
        zero,                  # completed
        np.float32(0.0),       # elapsed_us
        np.int32(-1),          # warm_completed
        np.float32(0.0),       # warm_elapsed_us
        np.int32(2 * n),       # rng counter
        zero,                  # events
        zero,                  # records emitted to the trace ring
    ))
    (_, completed, elapsed_us, warm_completed, warm_elapsed_us, _, events,
     n_traced) = carry
    n_measured = completed - warm_completed
    t_measured = jnp.maximum(elapsed_us - warm_elapsed_us, np.float32(1e-6))
    x = n_measured.astype(jnp.float32) / t_measured
    return st, x, completed, events, t_measured, n_traced


def _trace_tables(trace_cap: int, n_jobs: int, route_len: int):
    """Zero-state trace tables (name -> (length, dtype, fill))."""
    rows = (trace_cap + 1) * route_len
    return {
        "req": (trace_cap + 1, jnp.int32, -1),
        "r_branch": (trace_cap + 1, jnp.int32, 0),
        "cls": (trace_cap + 1, jnp.int32, 0),
        "nvis": (trace_cap + 1, jnp.int32, 0),
        "parked_us": (trace_cap + 1, jnp.float32, 0.0),
        "enter_us": (rows, jnp.float32, 0.0),
        "leave_us": (rows, jnp.float32, 0.0),
        SCR_ENTER: (n_jobs * route_len, jnp.float32, 0.0),
        SCR_LEAVE: (n_jobs * route_len, jnp.float32, 0.0),
    }


def _rings(tabs, n_traced, trace_cap: int, route_len: int) -> TraceRings:
    """TraceRings from (lanes, >= length) trace tables."""
    c1 = trace_cap + 1

    def flat(name, n):
        a = tabs[name]
        return a.reshape(a.shape[0], -1)[:, :n]

    return TraceRings(
        n_count=n_traced, req=flat("req", c1), branch=flat("r_branch", c1),
        cls=flat("cls", c1), nvis=flat("nvis", c1),
        parked_us=flat("parked_us", c1),
        enter_us=flat("enter_us", c1 * route_len).reshape(-1, c1, route_len),
        leave_us=flat("leave_us", c1 * route_len).reshape(-1, c1, route_len),
    )


@functools.partial(jax.jit,
                   static_argnames=("n_requests", "warmup", "mpl",
                                    "max_events", "route_len", "trace_cap"))
def _twin_grid(spec_tabs, seeds, bmiss=None, *, n_requests: int,
               warmup: int, mpl: int, max_events: int, route_len: int,
               trace_cap: int = 0):
    """The CPU twin: ``_sim_lane`` vmapped over lanes on ArrayState."""
    n_k = spec_tabs[IS_QUEUE].shape[1]

    def lane(spec, seed, bm):
        tabs = {
            **spec,
            READY: jnp.zeros((mpl,), jnp.int32),
            STATION: jnp.zeros((mpl,), jnp.int32),
            ENQ: jnp.zeros((mpl,), jnp.int32),
            BRANCH: jnp.zeros((mpl,), jnp.int32),
            POS: jnp.zeros((mpl,), jnp.int32),
            BUSY: jnp.zeros((n_k,), jnp.int32),
        }
        if trace_cap:
            tabs[BMISS] = bm
            for name, (n, dt, fill) in _trace_tables(
                    trace_cap, mpl, route_len).items():
                tabs[name] = jnp.full((n,), fill, dt)
        st, *out = _sim_lane(
            ArrayState(tabs, READY), seed, n_requests=n_requests,
            warmup=warmup, mpl=mpl, max_events=max_events,
            route_len=route_len, trace_cap=trace_cap)
        return out, {k: st.tabs[k] for k in RING_TABLES + RING_ROWS
                     if trace_cap}

    in_bm = 0 if trace_cap else None
    (x, completed, events, t_meas, n_traced), tr = jax.vmap(
        lane, in_axes=(0, 0, in_bm))(spec_tabs, seeds, bmiss)
    rings = _rings(tr, n_traced, trace_cap, route_len) if trace_cap else None
    return x, completed, events, t_meas, rings, None


def _sim_kernel(*refs, in_names, scratch_names, ring_names, n_requests: int,
                warmup: int, mpl: int, max_events: int, route_len: int,
                trace_cap: int):
    """One grid cell = one (p_hit, seed) lane's full event loop, traced.

    Refs arrive as the named inputs, the four per-lane results, the
    record count, the ring tables and the named scratch tables.
    """
    n_in = len(in_names)
    n_out = 5 + len(ring_names)
    outs = refs[n_in:n_in + n_out]
    tables = {**dict(zip(in_names, refs[:n_in])),
              **dict(zip(scratch_names, refs[n_in + n_out:])),
              **dict(zip(ring_names, outs[5:]))}
    seed = tables.pop("seed")[0, 0]
    for name, fill in _JOB_PAD.items():
        tables[name][...] = jnp.full(tables[name].shape, fill, jnp.int32)
    for k in range(tables[BUSY].shape[0]):
        tables[BUSY][k] = np.int32(0)
    vmem = JOB_GROUP
    for name, (_, dt, fill) in _trace_tables(trace_cap, mpl,
                                             route_len).items():
        tables[name][...] = jnp.full(tables[name].shape, fill, dt)
        vmem += (name,)
    st = RefState(tables, smem=[k for k in tables if k not in vmem],
                  block_rows=tables[READY].shape[0], n_blocks=1)
    _, x, completed, events, t_meas, n_traced = _sim_lane(
        st, seed, n_requests=n_requests, warmup=warmup, mpl=mpl,
        max_events=max_events, route_len=route_len, trace_cap=trace_cap)
    for ref, v in zip(outs, (x, completed, events, t_meas, n_traced)):
        ref[0, 0] = v


def _lane_kernel(*refs, in_names, scratch_names, sizes, n_lanes: int,
                 n_requests: int, warmup: int, mpl: int, max_events: int,
                 route_len: int):
    """One grid cell = the event loop of a block of up to 128 lanes, one
    lane per vector lane (LaneRefState: every table ``(rows, 128)``).

    Refs arrive as the named inputs, the five per-block results (x,
    completed, events, t_measured_us as ``(1, 128)`` rows, then the loop's
    trip count) and the named scratch tables.  Lanes past ``n_lanes`` (the
    last block's padding) start inactive.
    """
    n_in = len(in_names)
    outs = refs[n_in:n_in + 5]
    tables = {**dict(zip(in_names, refs[:n_in])),
              **dict(zip(scratch_names, refs[n_in + 5:]))}
    seed = tables.pop("seed")[...]
    for name in scratch_names:
        ref = tables[name]
        ref[...] = jnp.full(ref.shape, _JOB_PAD.get(name, 0), jnp.int32)
    lane = (pl.program_id(0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1))
    st = LaneRefState(tables, vec=READY, mask=lane < n_lanes, sizes=sizes)
    st, x, completed, events, t_meas, _ = _sim_lane(
        st, seed, n_requests=n_requests, warmup=warmup, mpl=mpl,
        max_events=max_events, route_len=route_len)
    for ref, v in zip(outs, (x, completed, events, t_meas, st.trips)):
        ref[...] = jnp.broadcast_to(v, (1, LANES)).astype(ref.dtype)


def _rows8(n: int) -> int:
    """Rows of a lane-packed table of ``n`` elements: whole (8, 128) tiles."""
    return -(-int(n) // 8) * 8


def _lane_grid(spec_tabs, seeds, *, n_requests: int, warmup: int, mpl: int,
               max_events: int, route_len: int, interpret: bool):
    """The lane-packed launch: ``grid=(ceil(lanes / 128),)``, each cell one
    128-lane block.  Returns (x, completed, events, t_measured_us) per lane
    and the loop's trip count per block."""
    n_lanes = seeds.shape[0]
    n_blocks = -(-n_lanes // LANES)
    pad = n_blocks * LANES - n_lanes

    def pack(a, rows):
        # (lanes, width) -> (blocks, rows, 128); padded lanes copy the last
        # real lane, so their log and pow stay finite
        if pad:
            a = a[np.minimum(np.arange(n_blocks * LANES), n_lanes - 1)]
        a = jnp.pad(a, ((0, 0), (0, rows - a.shape[1])))
        return a.reshape(n_blocks, LANES, rows).transpose(0, 2, 1)

    def block(rows):
        return pl.BlockSpec((None, rows, LANES), lambda i: (i, 0, 0))

    sizes = {k: spec_tabs[k].shape[1] for k in SPEC_TABLES}
    in_names = list(SPEC_TABLES) + ["seed"]
    operands = [pack(spec_tabs[k], _rows8(sizes[k])) for k in SPEC_TABLES]
    operands.append(pack(seeds.astype(jnp.int32)[:, None], 1))
    job_rows = _rows8(mpl)
    scratch = {name: job_rows for name in JOB_GROUP + (BRANCH, POS)}
    scratch[BUSY] = _rows8(sizes[IS_QUEUE])
    row = jax.ShapeDtypeStruct((n_blocks, 1, LANES), jnp.int32)
    f32_row = jax.ShapeDtypeStruct((n_blocks, 1, LANES), jnp.float32)
    kernel = functools.partial(
        _lane_kernel, in_names=in_names, scratch_names=list(scratch),
        sizes=sizes, n_lanes=n_lanes, n_requests=n_requests, warmup=warmup,
        mpl=mpl, max_events=max_events, route_len=route_len)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[block(a.shape[1]) for a in operands],
        out_specs=[block(1)] * 5,
        out_shape=[f32_row, row, row, f32_row, row],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.int32)
                        for rows in scratch.values()],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*operands)
    per_lane = tuple(o.reshape(-1)[:n_lanes] for o in out[:4])
    return per_lane, out[4][:, 0, 0]


@functools.partial(jax.jit,
                   static_argnames=("n_requests", "warmup", "mpl",
                                    "max_events", "route_len", "trace_cap",
                                    "interpret"))
def pallas_grid(spec_tabs, seeds, bmiss=None, *, n_requests: int,
                warmup: int, mpl: int, max_events: int, route_len: int,
                trace_cap: int = 0, interpret: bool = False):
    """The event-sim kernel over a lane grid: one dispatch.

    ``spec_tabs`` maps the SPEC_TABLES names to (lanes, width) arrays,
    ``seeds`` is (lanes,) int32.  Returns (x, completed, events,
    t_measured_us, rings, lane_steps): the first four per lane, rings
    None unless ``trace_cap``, lane_steps the lane-packed loop's trip
    count per 128-lane block (None on the per-lane kernel).  Without
    tracing the lanes run packed (:func:`_lane_grid`), at every lane
    count: one packed step costs less than one lane's event on the
    per-lane kernel.  ``trace_cap > 0`` runs one lane per grid cell, each
    writing its own ring rows, which the packed layout would turn into a
    pass over a whole table per write.
    Jitted, so ``pallas_grid.lower(...)`` AOT-compiles the kernel alone.
    """
    n_lanes = seeds.shape[0]
    if not trace_cap:
        per_lane, lane_steps = _lane_grid(
            spec_tabs, seeds, n_requests=n_requests, warmup=warmup, mpl=mpl,
            max_events=max_events, route_len=route_len, interpret=interpret)
        return (*per_lane, None, lane_steps)

    def lane_block(width):
        return pl.BlockSpec((None, 1, width), lambda i: (i, 0, 0),
                            memory_space=pltpu.SMEM)

    def lane_rows(rows):
        return pl.BlockSpec((None, rows, LANES), lambda i: (i, 0, 0))

    in_names = list(SPEC_TABLES) + ["seed", BMISS]
    operands = [spec_tabs[k][:, None, :] for k in SPEC_TABLES]
    operands.append(seeds.astype(jnp.int32)[:, None, None])
    operands.append(bmiss.astype(jnp.int32)[:, None, :])
    in_specs = [lane_block(a.shape[-1]) for a in operands]
    scalar_out = jax.ShapeDtypeStruct((n_lanes, 1, 1), jnp.int32)
    f32_out = jax.ShapeDtypeStruct((n_lanes, 1, 1), jnp.float32)
    out_shape = [f32_out, scalar_out, scalar_out, f32_out, scalar_out]
    out_specs = [lane_block(1)] * 5
    ring_names, scratch_names, scratch_shapes = [], [], []
    n_k = spec_tabs[IS_QUEUE].shape[-1]
    job_rows = vmem_rows(mpl)
    for name in JOB_GROUP:
        scratch_names.append(name)
        scratch_shapes.append(pltpu.VMEM((job_rows, LANES), jnp.int32))
    for name, width in ((BRANCH, mpl), (POS, mpl), (BUSY, n_k)):
        scratch_names.append(name)
        scratch_shapes.append(pltpu.SMEM((width,), jnp.int32))
    for name, (n, dt, _) in _trace_tables(trace_cap, mpl,
                                          route_len).items():
        rows = vmem_rows(n)
        if name in (SCR_ENTER, SCR_LEAVE):
            scratch_names.append(name)
            scratch_shapes.append(pltpu.VMEM((rows, LANES), dt))
        else:
            ring_names.append(name)
            out_shape.append(
                jax.ShapeDtypeStruct((n_lanes, rows, LANES), dt))
            out_specs.append(lane_rows(rows))
    kernel = functools.partial(
        _sim_kernel, in_names=in_names, scratch_names=scratch_names,
        ring_names=ring_names, n_requests=n_requests, warmup=warmup, mpl=mpl,
        max_events=max_events, route_len=route_len, trace_cap=trace_cap)
    out = pl.pallas_call(
        kernel,
        grid=(n_lanes,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*operands)
    x, completed, events, t_meas = (o[:, 0, 0] for o in out[:4])
    rings = _rings(dict(zip(ring_names, out[5:])), out[4][:, 0, 0],
                   trace_cap, route_len)
    return x, completed, events, t_meas, rings, None


def simulate_grid_pallas(net, p_hits, n_requests: int = 40_000,
                         seeds: Sequence[int] = (0, 1, 2),
                         warmup_frac: float = 0.25,
                         interpret: Optional[bool] = None,
                         trace: int = 0) -> SimResult:
    """Closed-loop (p_hit x seed) grid on the counter-RNG event engine.

    Same grid construction, warmup and summary statistics as
    ``simulate_network`` (per-p_hit specs tiled across seeds, one lane per
    cell, ONE dispatch for the whole grid), but every lane runs
    :func:`_sim_lane` — the kernel-resident event loop.  Agreement with
    the threefry scan engine is statistical; the pallas kernel and the
    CPU twin are bit-identical by shared code.

    ``trace=K`` keeps the last K per-request trace records per lane in a
    kernel-resident ring buffer (shape-static: K is baked into the
    compiled kernel) and decodes them onto the result's ``traces`` field,
    the same schema as the threefry engine's; ``trace=0`` compiles no
    tracing at all.
    """
    p_hits = np.atleast_1d(np.asarray(p_hits, dtype=np.float64))
    specs = []
    for p in p_hits:
        with spans.span("sim.compile_network"):
            specs.append(network_tables(net, float(p)))
    warmup = int(n_requests * warmup_frac)
    n_p, n_s = len(p_hits), len(seeds)
    trace = int(trace)

    def tile(a):
        return np.concatenate([a] * n_s, axis=0) if n_s > 1 else a

    with spans.span("sim.tables"):
        # numpy on the host: the jitted launch moves each table in one copy
        spec = SimSpec(*map(np.stack, zip(*(s[:-1] for s in specs))),
                       mpl=net.mpl)
        max_events = int(n_requests * (spec.visits.shape[-1] + 2) * 3)
        # the closed-loop non-coalescing kernel never touches the MSHR
        # machinery: drop disk_rank and the static mpl
        n_l = int(spec.visits.shape[-1])
        spec_tabs = {
            IS_QUEUE: tile(spec.is_queue.astype(np.int32)),
            SVC_NS: tile(spec.svc_ns),
            DIST_ID: tile(spec.dist_id),
            DIST_PAR: tile(spec.dist_params.reshape(n_p, -1)),
            BRANCH_CUM: tile(spec.branch_cum),
            VISITS: tile(spec.visits.reshape(n_p, -1)),
            SERVERS: tile(spec.servers),
        }
        seed_v = np.concatenate(
            [np.full((n_p,), s, np.int32) * 1000
             + np.arange(n_p, dtype=np.int32) for s in seeds]
        )
        bmiss_v = None
        if trace:
            # Per-branch sojourn class, precomputed host-side (the kernel's
            # spec tables carry no disk_rank): a branch whose route touches
            # a backing store is a miss, anything else a hit (the pallas
            # engine is closed-loop non-coalescing — no delayed hits).
            vis, dr = specs[0].visits, specs[0].disk_rank
            bmiss = ((dr[np.maximum(vis, 0)] >= 0) & (vis >= 0)).any(axis=1)
            bmiss_v = np.broadcast_to(
                bmiss, (n_p * n_s, bmiss.shape[0])).astype(np.int32)

    kw = dict(n_requests=n_requests, warmup=warmup, mpl=net.mpl,
              max_events=max_events, route_len=n_l, trace_cap=trace)
    with spans.span("sim.launch"):
        if interpret is None and not on_tpu():
            out = _twin_grid(spec_tabs, seed_v, bmiss_v, **kw)
        else:
            out = pallas_grid(
                spec_tabs, seed_v, bmiss_v,
                interpret=bool(interpret) if interpret is not None else False,
                **kw)
    with spans.span("sim.collect") as sp:
        rings = out[4]
        traces = None
        if trace:
            traces = decode_trace_grid(rings, specs[0].visits, n_s, n_p)
        xs = np.asarray(out[0]).reshape(n_s, n_p)
        mean = xs.mean(axis=0)
        ci = (1.96 * xs.std(axis=0, ddof=1) / math.sqrt(n_s) if n_s > 1
              else np.zeros_like(mean))
        if spans.recording():
            counts = {"events_count": int(np.asarray(out[2], np.int64).sum())}
            if out[5] is not None:
                # vector steps of the lane-packed loop, summed over blocks
                counts["lane_steps_count"] = int(
                    np.asarray(out[5], np.int64).sum())
            sp.count(**counts)
        return SimResult(p_hit=p_hits, throughput=mean, ci95=ci,
                         n_requests=n_requests, traces=traces)

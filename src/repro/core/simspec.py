"""Network -> simulator-spec compilation, shared by both event engines.

:func:`compile_network` freezes a :class:`repro.core.queueing.ClosedNetwork`
at one hit ratio into flat arrays (:class:`SimSpec`) that an event loop can
index with traced station ids (:func:`network_tables`: the same arrays in
numpy, on the host); :func:`stack_specs` stacks a grid of them for vmap.
The layer lives below the engines so that both the threefry scan
simulator (:mod:`repro.core.simulator`) and the pallas kernel engine
(:mod:`repro.kernels.event_sim`) can import it without the kernels package
and the core package importing each other.

:class:`SimResult` is the closed-loop summary both engines return.

:class:`MshrSpec` is the cross-tier MSHR annotation table for tiered
(hierarchy) networks: per-(branch, visit-position) acquire/release marks
that generalize the single ``disk_rank`` convention to a DAG of caches —
a request can hold an outstanding-fetch entry at its L1 client table
*and* at a shard-local origin table at once (see
:mod:`repro.hierarchy.model`, which builds these tables).
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.queueing import QUEUE, ClosedNetwork

# Sentinels: "idle / not ready" times and "not enqueued" sequence numbers.
# int32 max keeps every traced comparison in 32-bit (jit-hash64 lint).
INF_NS = np.int32(2**31 - 1)
BIG_SEQ = np.int32(2**31 - 1)

_DIST_IDS = {"det": 0, "exp": 1, "pareto": 2}


class SimSpec(NamedTuple):
    """A closed network compiled to arrays at one (or a grid of) p_hit."""

    is_queue: jax.Array  # (K,) bool
    svc_ns: jax.Array  # (K,) f32 mean service in ns
    dist_id: jax.Array  # (K,) i32
    dist_params: jax.Array  # (K, 4) f32: alpha, lo, hi, raw_mean (pareto)
    branch_cum: jax.Array  # (B,) f32 cumulative branch probabilities
    visits: jax.Array  # (B, L) i32 station indices, -1 padded
    servers: jax.Array  # (K,) i32 FCFS server count (1 for think stations)
    disk_rank: jax.Array  # (K,) i32 backing-store group id, -1 for non-disks
    mpl: int


def _bounded_pareto_mean(alpha: float, lo: float, hi: float) -> float:
    if abs(alpha - 1.0) < 1e-9:
        return lo * hi / (hi - lo) * math.log(hi / lo)
    num = lo**alpha * alpha * (lo ** (1 - alpha) - hi ** (1 - alpha))
    den = (alpha - 1.0) * (1.0 - (lo / hi) ** alpha)
    return num / den


def compile_network(net: ClosedNetwork, p_hit: float) -> SimSpec:
    """Freeze a network at a given hit ratio into simulator arrays."""
    spec = network_tables(net, p_hit)
    return SimSpec(*map(jnp.asarray, spec[:-1]), mpl=spec.mpl)


def network_tables(net: ClosedNetwork, p_hit: float) -> SimSpec:
    """:func:`compile_network`'s arrays as numpy arrays, left on the host."""
    names = [s.name for s in net.stations]
    idx = {n: i for i, n in enumerate(names)}
    K = len(names)
    is_queue = np.array([s.kind == QUEUE for s in net.stations], dtype=bool)
    svc_ns = np.array(
        [s.mean_service(p_hit) * 1e3 for s in net.stations], dtype=np.float32
    )
    dist_id = np.array([_DIST_IDS[s.dist] for s in net.stations], dtype=np.int32)
    dist_params = np.zeros((K, 4), dtype=np.float32)
    for i, s in enumerate(net.stations):
        if s.dist == "pareto":
            alpha, lo, hi = s.dist_params
            dist_params[i] = (alpha, lo, hi, _bounded_pareto_mean(alpha, lo, hi))
        else:
            dist_params[i] = (1.0, 1.0, 1.0, 1.0)

    probs = np.array([b.probability(p_hit) for b in net.branches], dtype=np.float64)
    if not math.isclose(probs.sum(), 1.0, abs_tol=1e-5):
        raise ValueError(f"branch probs sum to {probs.sum()} at p={p_hit}")
    probs = np.maximum(probs, 0.0)
    branch_cum = np.cumsum(probs / probs.sum()).astype(np.float32)

    L = max(len(b.visits) for b in net.branches)
    if min(len(b.visits) for b in net.branches) == 0:
        raise ValueError("empty branch routes are not supported")
    visits = np.full((len(net.branches), L), -1, dtype=np.int32)
    for bi, b in enumerate(net.branches):
        for vi, v in enumerate(b.visits):
            visits[bi, vi] = idx[v]
    if is_queue[visits[:, 0]].any():
        # init places all mpl jobs straight into service at their first
        # station; a queue-first route would bypass the busy accounting.
        raise ValueError("branch routes must start at a think station")

    servers = np.array(
        [s.servers if s.kind == QUEUE else 1 for s in net.stations],
        dtype=np.int32,
    )

    # A station is a backing store if it is named "disk" — either the bare
    # single-node disk or a per-shard replica ("s3:disk", the cluster
    # composition's naming).  Each disk gets its own MSHR flow group, so
    # miss coalescing is local to the shard whose disk serves the fetch.
    disk_rank = np.full(K, -1, dtype=np.int32)
    rank = 0
    for i, name in enumerate(names):
        if name.split(":")[-1] == "disk":
            disk_rank[i] = rank
            rank += 1

    return SimSpec(
        is_queue=is_queue,
        svc_ns=svc_ns,
        dist_id=dist_id,
        dist_params=dist_params,
        branch_cum=branch_cum,
        visits=visits,
        servers=servers,
        disk_rank=disk_rank,
        mpl=net.mpl,
    )


class MshrSpec(NamedTuple):
    """Cross-tier MSHR annotations for one composed (tiered) network.

    All arrays are shaped like ``SimSpec.visits`` (B branches × L route
    positions, -1 meaning "nothing here") and are *hit-ratio independent*
    (branch probabilities change with p, routes do not):

    ``acq_group[b, i]``
        MSHR group acquired on ARRIVAL at visit ``(b, i)``.  With F flows
        per group, the fetch for flow ``f`` of group ``g`` lives at leader
        slot ``g*F + f``.  Groups 0..n_clients-1 are the per-client L1
        tables; the shard-local origin tables follow (PR 5 layout: the
        deeper tier's coalescing never crosses shards).
    ``acq_slot[b, i]``
        Which of the job's ``max_held`` held-entry registers the
        acquisition writes (0 = shallowest tier).
    ``rel_slot[b, i]``
        Held-entry register released on COMPLETION of visit ``(b, i)`` —
        the fill lands, every request parked on that slot completes as a
        delayed hit (cascading across tiers: a woken job releases *its*
        held entries too, waking its own followers).

    Semantics contract (both simulators): a job samples one flow per
    request at its first acquire point; arriving at an acquire position
    whose slot already has a leader, it parks — no queue position, no
    I/O-depth slot, no further route visits — and completes at fill time,
    skipping all fill metadata (the single-tier delayed-hit convention).
    """

    acq_group: np.ndarray  # (B, L) i32, -1 = no acquire at this visit
    acq_slot: np.ndarray  # (B, L) i32, -1 matching acq_group
    rel_slot: np.ndarray  # (B, L) i32, -1 = no release at this visit
    n_groups: int
    max_held: int

    def validate(self, visits: np.ndarray) -> None:
        """Structural checks against a compiled route table."""
        ag = np.asarray(self.acq_group)
        asl = np.asarray(self.acq_slot)
        rs = np.asarray(self.rel_slot)
        if ag.shape != visits.shape or asl.shape != visits.shape \
                or rs.shape != visits.shape:
            raise ValueError(
                f"MshrSpec arrays {ag.shape} do not match visits "
                f"{visits.shape}")
        if ((ag >= 0) != (asl >= 0)).any():
            raise ValueError("acq_group and acq_slot must mark the same "
                             "positions")
        if (ag >= self.n_groups).any() or (asl >= self.max_held).any() \
                or (rs >= self.max_held).any():
            raise ValueError("MshrSpec group/slot index out of range")
        if (ag[:, 0] >= 0).any():
            raise ValueError("a branch cannot acquire at its first visit "
                             "(requests start at a think station)")
        for b in range(ag.shape[0]):
            acquired = {int(s) for s in asl[b] if s >= 0}
            released = {int(s) for s in rs[b] if s >= 0}
            if acquired != released:
                raise ValueError(
                    f"branch {b}: acquired slots {sorted(acquired)} != "
                    f"released slots {sorted(released)} — a leaked leader "
                    f"entry would deadlock the closed loop")
            for s in acquired:
                a_pos = int(np.nonzero(asl[b] == s)[0][0])
                r_pos = int(np.nonzero(rs[b] == s)[0][0])
                if r_pos < a_pos:
                    raise ValueError(
                        f"branch {b}: slot {s} released at position "
                        f"{r_pos} before its acquire at {a_pos}")


def stack_specs(specs) -> SimSpec:
    """Stack per-p_hit specs along a leading axis for vmap."""
    mpl = specs[0].mpl
    assert all(s.mpl == mpl for s in specs)
    return SimSpec(
        *[jnp.stack([getattr(s, f) for s in specs]) for f in SimSpec._fields[:-1]],
        mpl=mpl,
    )


@dataclasses.dataclass(frozen=True)
class SimResult:
    p_hit: np.ndarray
    throughput: np.ndarray  # requests/µs == M req/s
    ci95: np.ndarray  # 95% CI half-width across seeds
    n_requests: int
    # fraction of measured completions that were delayed hits (coalesced
    # onto an in-flight fetch); zeros unless coalesce_flows > 0.
    delayed_frac: np.ndarray | None = None
    # per-branch completion rates (requests/µs), (P, B) in the order of
    # ``net.branches``; ``branch_delayed`` is the delayed-hit subset of the
    # same completions.  The cluster prong folds these into per-shard
    # throughput / hit-ratio / delayed-hit breakdowns.
    branch_throughput: np.ndarray | None = None
    branch_delayed: np.ndarray | None = None
    # tiered (MshrSpec) runs only: delayed-hit completions split by the
    # held-slot level the job parked at, (P, max_held) fractions of
    # measured completions — column 0 is the shallowest tier's table
    # (client-local L1 coalescing), later columns the deeper tables
    # (shard-local origin coalescing).  None for non-tiered runs.
    delayed_tier_frac: np.ndarray | None = None
    # decoded per-lane trace records ([seed][p] repro.obs.trace
    # TraceRecords); None unless the run requested in-kernel tracing
    # (simulate_network(trace=K) / simulate_grid_pallas(trace=K)).
    traces: list | None = None
    # decoded per-lane streaming estimators ([seed][p] repro.obs.streaming
    # SketchEstimates); None unless simulate_network(sketch_cap=K).
    sketches: list | None = None

"""Prong C: virtual-time measurement of the *implemented* caches.

The paper's third prong measures a real cache implementation (HHVM-based)
under a closed loop of 72 client threads.  This container has one CPU core,
so wall-clock lock contention cannot be reproduced; instead we do the
honest equivalent:

  1. Drive an **actual cache implementation** with a Zipf(θ) workload at a
     given cache size.  This yields the *real* hit/miss sequence and the
     *real* per-request metadata-op counts — no Bernoulli assumption.
  2. Aggregate the observed (hit, op-vector) profiles into an *empirical*
     closed queueing network whose branch probabilities are the measured
     frequencies, and whose station service times are the paper's
     calibrated measurements.
  3. Evaluate that network with the validated event-driven simulator (and
     with the Thm-7.1 bound).

Step 1 has **two backends**, selected by ``backend=`` on
:func:`run_cache_trace` / :func:`sweep_cache_sizes`:

``"py"``
    The pure-Python references (:mod:`repro.cache.py_ref`), one request at
    a time.  Slow, but dead simple — this is the differential *oracle*.
``"jax"``
    The compiled trace-replay engine (:mod:`repro.cache.replay`): the
    jittable policies under ``lax.scan``, ``vmap``-ed over a
    (capacity x seed) grid so a whole cache-size sweep dispatches as one
    compiled program; for LRU the sweep further collapses into a single
    Mattson stack-distance pass covering every capacity at once.
    Bit-identical to the oracle (tests/test_replay.py) and ~10-80x faster.

Both backends draw the admission coins from an RNG substream independent
of the trace draws (``np.random.SeedSequence(seed).spawn(2)``), so
Prob-LRU / S3-FIFO coin flips never correlate with the key sequence.

Step 1 also gives the cache-size → hit-ratio mapping (the paper sweeps
p_hit the same way — by varying cache size under a fixed Zipf workload).

This closes the loop the paper closes: if the Bernoulli-branch *model*
network and the measured-profile *implementation* network agree (<5%), the
queueing model is a faithful representation of the implementation.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.cache.py_ref import PY_POLICIES
from repro.core.queueing import (
    QUEUE,
    THINK,
    Branch,
    ClosedNetwork,
    Station,
    disk_station,
)


@dataclasses.dataclass(frozen=True)
class ServiceTimes:
    """Calibrated per-op service times (µs).  Defaults = paper's LRU numbers."""

    lookup: float = 0.51
    disk: float = 100.0
    delink: float = 0.70
    head: float = 0.59
    tail: float = 0.59
    scan: float = 0.30  # per extra tail-scan step (CLOCK 0.3·g decomposition)


# The paper's measured service times differ per policy family because queue
# lengths change the cross-core communication overhead (Sec. 3.1, 4.1).
PAPER_SERVICES = {
    "lru": ServiceTimes(),
    "fifo": ServiceTimes(head=0.73, tail=0.73),
    "prob_lru": ServiceTimes(delink=0.78, head=0.65, tail=0.65),
    "clock": ServiceTimes(head=0.65, tail=0.65),
    "slru": ServiceTimes(),
    "s3fifo": ServiceTimes(head=0.65, tail=0.65),
    "sieve": ServiceTimes(head=0.65, tail=0.65),
}


def _seed_streams(seed: int):
    """Independent substreams for (key trace, admission coins).

    Constructing ``default_rng(seed)`` in both :func:`zipf_trace` and the
    coin draw made the Prob-LRU/S3-FIFO admission samples share a stream
    with the trace's permutation/choice draws — the coins were a
    deterministic function of the key sequence.  Spawning from one
    ``SeedSequence`` keeps the pairing reproducible but independent.
    """
    return np.random.SeedSequence(seed).spawn(2)


def zipf_trace(n: int, key_space: int, theta: float = 0.99, seed: int = 0) -> np.ndarray:
    """Zipfian key trace (θ=0.99 — paper Sec. 3.4 workload)."""
    rng = np.random.default_rng(_seed_streams(seed)[0])
    ranks = np.arange(1, key_space + 1, dtype=np.float64)
    probs = ranks ** (-theta)
    probs /= probs.sum()
    # shuffle key identities so key id != popularity rank
    perm = rng.permutation(key_space)
    return perm[rng.choice(key_space, size=n, p=probs)].astype(np.int64)


def coin_stream(n: int, seed: int = 0) -> np.ndarray:
    """Admission-coin samples u ~ U[0,1), independent of zipf_trace(seed).

    float32 so the py and jax backends compare the *same* values against
    q thresholds — identical hit sequences bit for bit.
    """
    rng = np.random.default_rng(_seed_streams(seed)[1])
    return rng.random(n, dtype=np.float32)


def miss_window_stream(n: int, mean_requests: float, seed: int = 0,
                       dist: str = "exp") -> np.ndarray:
    """Per-request in-flight windows (miss latencies in requests) drawn
    from the disk service distribution: ``dist="exp"`` samples
    Exp(mean_requests) rounded to whole requests, ``"det"`` pins every
    window at the mean (equivalent to the scalar ``miss_latency_requests``
    path).  Third ``SeedSequence(seed)`` substream, so the draws are
    independent of both the trace and the admission coins while staying
    reproducible alongside them.
    """
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(3)[2])
    if dist == "det":
        return np.full(n, int(round(mean_requests)), dtype=np.int64)
    if dist != "exp":
        raise ValueError(f"unknown window dist {dist!r} (want 'exp' or 'det')")
    return np.round(rng.exponential(mean_requests, n)).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class CacheMeasurement:
    policy: str
    capacity: int
    hit_ratio: float
    mean_ops_hit: np.ndarray  # mean (delink, head, tail, scan) on hits
    mean_ops_miss: np.ndarray  # ... on misses
    profiles: dict  # (hit, ops) -> frequency
    network: ClosedNetwork  # empirical-profile network
    # delayed-hit classification under an in-flight window of
    # ``miss_latency_requests`` requests (0 = classification disabled;
    # the mean window when per-request windows were used):
    # post-warmup fractions of (true miss, true hit, delayed hit).
    miss_latency_requests: int = 0
    class_fracs: np.ndarray | None = None

    def throughput_bound(self, p=None):
        return self.network.throughput_upper(self.hit_ratio if p is None else p)

    @property
    def coalesce_sigma(self) -> float:
        """Measured coalescing factor: of the requests that needed a fill
        (delayed + true miss), the fraction that found one in flight."""
        if self.class_fracs is None:
            return 0.0
        miss, _, delayed = (float(x) for x in self.class_fracs)
        return delayed / (delayed + miss) if (delayed + miss) > 0 else 0.0

    @property
    def true_hit_ratio(self) -> float:
        """Hit ratio with delayed hits reclassified out of the hit count."""
        if self.class_fracs is None:
            return self.hit_ratio
        return float(self.class_fracs[1])

    def coalesced_throughput_bound(self, p=None):
        """Thm-7.1 bound of the measured-profile network with the measured
        coalescing factor applied (delayed hits skip the disk and the fill
        metadata).  Falls back to the plain bound when classification is
        off or found no coalescing."""
        sig = self.coalesce_sigma
        if sig <= 0.0:
            return self.throughput_bound(p)
        from repro.core.queueing import coalesced_network

        net = coalesced_network(self.network, sigma=sig)
        return net.throughput_upper(self.hit_ratio if p is None else p)


def run_cache_trace(policy: str, capacity: int, trace: np.ndarray, seed: int = 0,
                    backend: str = "py", key_space: int | None = None,
                    pad_to: int | None = None, **policy_kwargs):
    """Replay a trace through a cache implementation; returns (hits, ops).

    The two backends are contractually interchangeable:

    ``backend="py"``
        walks the Python reference (:mod:`repro.cache.py_ref`) one request
        at a time.  Slow and dead simple — this is the differential
        *oracle*, and the only backend that never imports jax.
    ``backend="jax"``
        dispatches the compiled ``lax.scan`` engine
        (:mod:`repro.cache.replay`).  ``key_space`` bounds the key-indexed
        arrays (inferred from the trace when omitted) and ``pad_to`` sizes
        the slot arrays so different capacities share a compiled program.
    ``backend="pallas"``
        dispatches the flat-state accelerator engine
        (:mod:`repro.kernels.replay`): the replay runs as a pallas kernel
        with the cache state in scratch memory (its compiled scan twin on
        CPU), same ``key_space``/``pad_to`` knobs.

    All backends consume the same float32 coin substream (admission
    randomness independent of the trace stream) and must return
    bit-identical (hits, ops) arrays — ``tests/test_replay.py`` and
    ``tests/test_pallas_replay.py`` pin that contract element-wise for
    every policy, which is what keeps py_ref usable as the differential
    oracle for any new replay feature.
    """
    us = coin_stream(len(trace), seed)
    if backend == "jax":
        from repro.cache.replay import replay_trace  # lazy: pulls in jax

        res = replay_trace(policy, trace, us, int(capacity),
                           key_space=key_space, pad_to=pad_to,
                           **policy_kwargs)
        return np.asarray(res.hits), res.ops
    if backend == "pallas":
        from repro.kernels.replay import replay_grid_pallas, unpack_grid_ops

        pres = replay_grid_pallas(policy, trace, us, [int(capacity)],
                                  key_space=key_space, pad_to=pad_to,
                                  **policy_kwargs)
        return np.asarray(pres.hits)[0, 0], unpack_grid_ops(pres)[0, 0]
    if backend != "py":
        raise ValueError(f"unknown backend {backend!r} "
                         "(want 'py', 'jax' or 'pallas')")
    cache = PY_POLICIES[policy](capacity, **policy_kwargs)
    hits = np.empty(len(trace), dtype=bool)
    ops = np.empty((len(trace), 4), dtype=np.int64)
    for i, (k, u) in enumerate(zip(trace, us)):
        a = cache.access(int(k), float(u))
        hits[i] = a.hit
        ops[i] = a.ops
    return hits, ops


_SCAN_MASK = (1 << 26) - 1


def empirical_network(
    policy: str,
    hits: np.ndarray,
    ops: np.ndarray,
    service: ServiceTimes | None = None,
    mpl: int = 72,
    warmup_frac: float = 0.25,
    disk_servers: int = 0,
) -> tuple:
    """Build the measured-profile closed network from an execution trace.

    Scan steps are charged at a dedicated queue station (an approximation of
    the paper's folding of scan time into S_tail; documented in DESIGN.md).
    """
    service = service or PAPER_SERVICES.get(policy, ServiceTimes())
    w = int(len(hits) * warmup_frac)
    hits_m, ops_m = hits[w:], ops[w:]
    # vectorized profile histogram: each (hit, op-vector) row packs into one
    # int64 (12 bits per list-op count, 26 for the scan count — SIEVE's
    # hand can clear thousands of bits in one eviction), so the
    # unique+count is a scalar sort — a per-request Python Counter (and
    # even np.unique over rows, which sorts void views) dominated sweep
    # time at 60k requests.
    ops64 = np.asarray(ops_m, np.int64)
    if ops64.size and (ops64[:, :3].max() > 0xFFF
                       or ops64[:, 3].max() > _SCAN_MASK):
        raise ValueError("op count exceeds the profile packing")
    code = (
        (np.asarray(hits_m, np.int64) << 62)
        | (ops64[:, 0] << 50) | (ops64[:, 1] << 38)
        | (ops64[:, 2] << 26) | ops64[:, 3]
    )
    uniq, counts = np.unique(code, return_counts=True)
    profiles = {
        (bool(c >> 62), (int((c >> 50) & 0xFFF), int((c >> 38) & 0xFFF),
                         int((c >> 26) & 0xFFF), int(c & _SCAN_MASK))): int(n)
        for c, n in zip(uniq, counts)
    }
    total = int(counts.sum())

    stations = [
        Station("lookup", THINK, service.lookup, dist="det"),
        disk_station(service.disk, disk_servers),
        Station("delink", QUEUE, service.delink, dist="det"),
        Station("head", QUEUE, service.head, dist="pareto",
                dist_params=(0.45, 0.1, max(2 * service.head - 0.1, 0.2))),
        Station("tail", QUEUE, service.tail, dist="det"),
        Station("scan", QUEUE, service.scan, dist="det"),
    ]
    branches = []
    for (hit, op_vec), count in sorted(profiles.items()):
        n_delink, n_head, n_tail, n_scan = op_vec
        visits = ["lookup"]
        if not hit:
            visits.append("disk")
        visits += (["delink"] * n_delink + ["head"] * n_head
                   + ["tail"] * n_tail + ["scan"] * n_scan)
        branches.append(
            Branch(
                f"{'hit' if hit else 'miss'}_{op_vec}",
                count / total,
                tuple(visits),
            )
        )
    net = ClosedNetwork(
        f"{policy}-empirical", tuple(stations), tuple(branches), mpl,
        description=f"measured-profile network for {policy}",
    )

    # hit ratio and per-class mean op vectors straight from the histogram
    # (equivalent to masking the raw arrays, without the large copies)
    def mean_ops(want_hit: bool) -> np.ndarray:
        count = sum(c for (h, _), c in profiles.items() if h == want_hit)
        if not count:
            return np.zeros(4)
        acc = np.zeros(4)
        for (h, vec), c in profiles.items():
            if h == want_hit:
                acc += np.asarray(vec, np.float64) * c
        return acc / count

    n_hits = sum(c for (h, _), c in profiles.items() if h)
    hit_ratio = n_hits / total if total else 0.0
    mean_hit = mean_ops(True)
    mean_miss = mean_ops(False)
    return CacheMeasurement(
        policy=policy, capacity=-1, hit_ratio=hit_ratio,
        mean_ops_hit=mean_hit, mean_ops_miss=mean_miss,
        profiles=dict(profiles), network=net,
    )


def parameterized_network(
    policy: str,
    hit_ops,
    miss_ops,
    service: ServiceTimes | None = None,
    mpl: int = 72,
    disk_servers: int = 0,
) -> ClosedNetwork:
    """Hit-ratio-parameterized network from measured op vectors.

    Unlike :func:`empirical_network` (pinned at the measured hit ratio),
    this sweeps p_hit with the *measured* hit/miss op profiles — what you
    need for p* of an implemented controller."""
    service = service or PAPER_SERVICES.get(policy, ServiceTimes())
    stations = [
        Station("lookup", THINK, service.lookup, dist="det"),
        disk_station(service.disk, disk_servers),
        Station("delink", QUEUE, service.delink, dist="det"),
        Station("head", QUEUE, service.head, dist="det"),
        Station("tail", QUEUE, service.tail, dist="det"),
        Station("scan", QUEUE, service.scan, dist="det"),
    ]

    def visits(ops, miss):
        v = ["lookup"] + (["disk"] if miss else [])
        d, h, t, s = (int(round(x)) for x in ops)
        return tuple(v + ["delink"] * d + ["head"] * h + ["tail"] * t
                     + ["scan"] * s)

    branches = [
        Branch("hit", lambda p: p, visits(hit_ops, False)),
        Branch("miss", lambda p: 1.0 - p, visits(miss_ops, True)),
    ]
    return ClosedNetwork(f"{policy}-measured", tuple(stations),
                         tuple(branches), mpl)


def _class_fracs(cls, warmup_frac: float = 0.25) -> np.ndarray:
    """(true miss, true hit, delayed hit) fractions after warmup, from an
    int8 class stream — host- or device-resident (e.g. the fused ``cls``
    output of :func:`repro.kernels.replay.replay_grid_pallas`)."""
    w = int(cls.shape[-1] * warmup_frac)
    cls_m = np.asarray(cls)[..., w:]
    return np.stack(
        [(cls_m == c).mean(axis=-1) for c in range(3)], axis=-1
    )


def _classify(trace, hits, window, key_space: int, backend: str,
              warmup_frac: float = 0.25, fail_prob: float = 0.0,
              fail_seed: int = 0) -> np.ndarray:
    """Post-warmup (true miss, true hit, delayed hit) fractions.

    ``window`` is a scalar or a (T,) per-request array — passed straight
    to the classifiers, which share the fetch-expiry semantics (including
    the ``fail_prob`` TTL re-issue stretch)."""
    if backend in ("jax", "pallas"):
        from repro.cache.replay import classify_inflight  # lazy: pulls in jax

        cls = classify_inflight(trace, hits, window, key_space=key_space,
                                fail_prob=fail_prob, fail_seed=fail_seed)
    else:
        from repro.cache.py_ref import classify_inflight_py

        cls = classify_inflight_py(trace, hits, window, fail_prob=fail_prob,
                                   fail_seed=fail_seed)
    return _class_fracs(cls, warmup_frac)


def measure_cache(
    policy: str,
    capacity: int,
    key_space: int = 4096,
    n_requests: int = 60_000,
    theta: float = 0.99,
    disk_us: float = 100.0,
    mpl: int = 72,
    seed: int = 0,
    disk_servers: int = 0,
    backend: str = "py",
    miss_latency_requests: int = 0,
    fetch_fail_prob: float = 0.0,
    **policy_kwargs,
) -> CacheMeasurement:
    """End-to-end prong C measurement at one cache size.

    ``miss_latency_requests > 0`` additionally classifies every request
    against an in-flight-miss window of that many requests (see
    :func:`repro.cache.replay.classify_inflight`): the resulting
    ``class_fracs`` / ``coalesce_sigma`` on the returned measurement feed
    the delayed-hits variants of the model (prong A) and simulator
    (prong B).  A ``(n_requests,)`` array gives every request its own
    window (per-request miss latencies, e.g. from
    :func:`miss_window_stream`); the stored ``miss_latency_requests``
    then records the mean.  With 0 the measurement is bit-identical to
    the non-coalesced path.

    ``fetch_fail_prob`` models TTL-style fetch failure: each true miss's
    fetch re-issues on failure, stretching its window by a geometric
    attempt count (see :func:`repro.cache.replay.refetch_attempts`);
    0 keeps the classification unchanged.

    ``backend`` is ``"py"`` (the oracle loop), ``"jax"`` (the compiled
    scan engine) or ``"pallas"`` (the flat-state accelerator engine,
    :mod:`repro.kernels.replay` — replay *and* classification fuse into
    a single dispatch); all three return identical measurements.
    """
    trace = zipf_trace(n_requests, key_space, theta, seed)
    classify = bool(np.any(miss_latency_requests))
    fracs_fused = None
    if backend == "pallas":
        # replay + classification fused in ONE dispatch (the scan/py
        # backends replay first, then run the classifier as a post-pass)
        from repro.kernels.replay import replay_grid_pallas, unpack_grid_ops

        pres = replay_grid_pallas(
            policy, trace, coin_stream(n_requests, seed), [capacity],
            key_space=key_space,
            window=miss_latency_requests if classify else None,
            fail_prob=fetch_fail_prob, fail_seed=seed, **policy_kwargs)
        hits = np.asarray(pres.hits)[0, 0]
        ops = unpack_grid_ops(pres)[0, 0]
        if pres.cls is not None:
            fracs_fused = _class_fracs(pres.cls[0, 0])
    else:
        hits, ops = run_cache_trace(policy, capacity, trace, seed=seed,
                                    backend=backend, key_space=key_space,
                                    **policy_kwargs)
    service = dataclasses.replace(
        PAPER_SERVICES.get(policy, ServiceTimes()), disk=disk_us
    )
    meas = empirical_network(policy, hits, ops, service=service, mpl=mpl,
                             disk_servers=disk_servers)
    meas = dataclasses.replace(meas, capacity=capacity)
    if classify:
        fracs = fracs_fused if fracs_fused is not None else _classify(
            trace, hits, miss_latency_requests, key_space, backend,
            fail_prob=fetch_fail_prob, fail_seed=seed)
        meas = dataclasses.replace(
            meas,
            miss_latency_requests=int(round(float(
                np.mean(miss_latency_requests)))),
            class_fracs=fracs,
        )
    return meas


def sweep_cache_sizes(
    policy: str,
    sizes,
    key_space: int = 4096,
    n_requests: int = 60_000,
    theta: float = 0.99,
    disk_us: float = 100.0,
    mpl: int = 72,
    simulate: bool = False,
    sim_requests: int = 20_000,
    seed: int = 0,
    disk_servers: int = 0,
    backend: str = "jax",
    miss_latency_requests: int = 0,
    fetch_fail_prob: float = 0.0,
    **policy_kwargs,
):
    """Hit-ratio/throughput curve vs cache size — the paper's x-axis sweep.

    ``backend="jax"`` (default) replays every size in one compiled
    dispatch: a single Mattson stack-distance pass for LRU, the vmapped
    (capacity x seed) scan grid for everything else.  ``backend="py"``
    keeps the oracle loop (~10-80x slower, zero jax imports).
    ``backend="pallas"`` runs the flat-state accelerator engine
    (:mod:`repro.kernels.replay`) — every size is a grid lane of ONE
    kernel dispatch with the delayed-hit classification fused into the
    same pass when the sizes share a window stream (per-size scalar
    windows that differ fall back to the device classifier per size).
    All backends consume identical trace/coin streams and return
    identical arrays, so any can cross-check another.

    ``miss_latency_requests`` — a scalar, one window per size (in a
    closed system the window ~= X·L *depends on the operating point*, so
    per-size windows let one sweep carry its own calibration), or one
    window per *request* (an ``(n_requests,)`` array, e.g. from
    :func:`miss_window_stream`, applied to every size) — turns on
    delayed-hit classification and adds per-size columns: ``p_true_hit``,
    ``p_delayed``, ``sigma`` (measured coalescing factor) and
    ``x_bound_coalesced`` (the bound with delayed hits skipping the disk
    and fill metadata).  ``fetch_fail_prob`` stretches each fetch's
    window by its geometric re-issue attempts (TTL failure model).

    Returns dict of np arrays: size, p_hit, x_bound, (x_sim if simulate,
    delayed-hit columns if enabled).
    """
    from repro.core.simulator import simulate_network  # lazy: pulls in jax

    if backend not in ("py", "jax", "pallas"):
        raise ValueError(f"unknown backend {backend!r} "
                         "(want 'py', 'jax' or 'pallas')")
    sizes = [int(c) for c in sizes]
    mlr = np.asarray(miss_latency_requests)
    if mlr.ndim == 1 and mlr.size == n_requests:
        if mlr.size == len(sizes):
            raise ValueError(
                f"ambiguous miss_latency_requests: length {mlr.size} matches "
                "both len(sizes) (per-size windows) and n_requests "
                "(per-request windows) — change one of them")
        windows = [mlr] * len(sizes)  # per-request windows, every size
    else:
        windows = list(np.broadcast_to(mlr, len(sizes)).astype(int))
    classify = any(np.any(w) for w in windows)
    out: dict = {"size": [], "p_hit": [], "x_bound": [], "x_sim": [],
                 "p_true_hit": [], "p_delayed": [], "sigma": [],
                 "x_bound_coalesced": []}

    def _measurements():
        if backend == "py":
            for c, w in zip(sizes, windows):
                yield measure_cache(
                    policy, c, key_space=key_space, n_requests=n_requests,
                    theta=theta, disk_us=disk_us, mpl=mpl, seed=seed,
                    disk_servers=disk_servers,
                    miss_latency_requests=w,
                    fetch_fail_prob=fetch_fail_prob,
                    **policy_kwargs,
                )
            return
        trace = zipf_trace(n_requests, key_space, theta, seed)
        cls_g = hits_dev = None
        if backend == "pallas":
            from repro.kernels.replay import (replay_grid_pallas,
                                              unpack_grid_ops)

            # all sizes + (when the windows agree) the classification in
            # ONE kernel dispatch — the fused prong-C pipeline
            same_w = all(np.array_equal(w, windows[0]) for w in windows[1:])
            pres = replay_grid_pallas(
                policy, trace, coin_stream(n_requests, seed), sizes,
                key_space=key_space,
                window=windows[0] if (classify and same_w) else None,
                fail_prob=fetch_fail_prob, fail_seed=seed, **policy_kwargs)
            hits_dev = pres.hits[:, 0]  # device-resident, for the classifier
            hits_g = np.asarray(hits_dev)
            ops_g = unpack_grid_ops(pres)[:, 0]
            if pres.cls is not None:
                cls_g = pres.cls[:, 0]
        elif policy == "lru":
            from repro.cache.replay import lru_sweep

            hits_g, ops_g = lru_sweep(trace, sizes)
        else:
            from repro.cache.replay import replay_grid  # lazy: pulls in jax

            res = replay_grid(policy, trace, coin_stream(n_requests, seed),
                              sizes, key_space=key_space, **policy_kwargs)
            hits_g, ops_g = res.hits[:, 0], res.ops[:, 0]
        service = dataclasses.replace(
            PAPER_SERVICES.get(policy, ServiceTimes()), disk=disk_us
        )
        for i, (c, w) in enumerate(zip(sizes, windows)):
            meas = empirical_network(policy, hits_g[i], ops_g[i],
                                     service=service, mpl=mpl,
                                     disk_servers=disk_servers)
            meas = dataclasses.replace(meas, capacity=c)
            if np.any(w):
                if cls_g is not None:
                    fracs = _class_fracs(cls_g[i])
                else:
                    h_i = (hits_dev[i] if hits_dev is not None
                           else np.asarray(hits_g[i]))
                    fracs = _classify(trace, h_i, w, key_space, backend,
                                      fail_prob=fetch_fail_prob,
                                      fail_seed=seed)
                meas = dataclasses.replace(
                    meas,
                    miss_latency_requests=int(round(float(np.mean(w)))),
                    class_fracs=fracs,
                )
            yield meas

    for meas in _measurements():
        out["size"].append(meas.capacity)
        out["p_hit"].append(meas.hit_ratio)
        out["x_bound"].append(float(meas.throughput_bound()))
        if classify:
            out["p_true_hit"].append(meas.true_hit_ratio)
            out["p_delayed"].append(
                float(meas.class_fracs[2])
                if meas.class_fracs is not None else 0.0
            )
            out["sigma"].append(meas.coalesce_sigma)
            out["x_bound_coalesced"].append(
                float(meas.coalesced_throughput_bound())
            )
        if simulate:
            res = simulate_network(
                meas.network, [meas.hit_ratio], n_requests=sim_requests, seeds=(0,)
            )
            out["x_sim"].append(float(res.throughput[0]))
    return {k: np.asarray(v) for k, v in out.items() if v}

"""Batched JAX trace-replay engine — the compiled fast path of prong C.

The measurement stack used to replay traces through the pure-Python
reference caches one request at a time (``repro.core.harness``), looping
cache sizes and policies in Python on top.  This module runs the *same*
policies — the jit-compatible pure functions in
:mod:`repro.cache.policies` — under ``lax.scan`` over the request stream,
and ``vmap``s that scan over a (capacity x seed) grid so an entire
cache-size sweep dispatches as ONE compiled program:

    axis 0  capacities — states stacked by ``PolicyDef.batched_init``
                         (shared ``pad_to`` slot arrays, traced capacity)
    axis 1  seeds      — independent (trace, coin) streams
    axis 2  requests   — the ``lax.scan`` carry

Per request it returns the hit flag, the evicted key (-1 when none) and
the op vector (delink, head, tail, scan) — everything
``repro.core.harness.empirical_network`` needs to build the
measured-profile queueing networks, with no Python in the loop.

The Python references stay as the differential oracle:
``tests/test_replay.py`` pins the scan engine to ``py_ref`` element-wise
on every policy for a shared (trace, u) sequence.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from numpy.typing import ArrayLike

from repro.cache.policies import POLICIES, PolicyDef


class ReplayResult(NamedTuple):
    """Per-request replay outputs (leading axes: [capacity, [seed,]] ).

    ``ops`` columns are (delink, head, tail, scan) — the paper's queue
    stations, in the same order as ``repro.cache.py_ref.Access.ops``.
    """

    hits: np.ndarray  # bool   (..., T)
    evicted: np.ndarray  # int64  (..., T), -1 when none
    ops: np.ndarray  # int64  (..., T, 4)


def _scan_replay(
    pdef: PolicyDef, state: Any, keys: jax.Array, us: jax.Array
) -> tuple[Any, jax.Array, jax.Array, jax.Array]:
    """lax.scan a (keys, us) stream through one policy state."""

    def step(state: Any, ku: tuple[jax.Array, jax.Array]) -> Any:
        k, u = ku
        state, res = pdef.access(state, k, u)
        return state, (res.hit, res.evicted_key, jnp.stack(res.ops))

    state, (hits, evicted, ops) = lax.scan(step, state, (keys, us))
    return state, hits, evicted, ops


@partial(jax.jit, static_argnames=("policy",))
def _replay_one(
    policy: str, state: Any, keys: jax.Array, us: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    _, hits, evicted, ops = _scan_replay(POLICIES[policy], state, keys, us)
    return hits, evicted, ops


@partial(jax.jit, static_argnames=("policy",))
def _replay_grid(
    policy: str, states: Any, keys: jax.Array, us: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    pdef = POLICIES[policy]

    def one(state: Any, k: jax.Array, u: jax.Array) -> Any:
        _, hits, evicted, ops = _scan_replay(pdef, state, k, u)
        return hits, evicted, ops

    per_seed = jax.vmap(one, in_axes=(None, 0, 0))  # over the seed axis
    per_cap = jax.vmap(per_seed, in_axes=(0, None, None))  # over capacities
    return per_cap(states, keys, us)


def _as_device(keys: ArrayLike, us: ArrayLike) -> tuple[jax.Array, jax.Array]:
    keys = np.asarray(keys)
    us = np.asarray(us)
    if keys.shape != us.shape:
        raise ValueError(f"keys {keys.shape} vs us {us.shape} shape mismatch")
    return jnp.asarray(keys, jnp.int32), jnp.asarray(us, jnp.float32)


def _resolve_key_space(keys: ArrayLike, key_space: int | None) -> int:
    """Resolve and VALIDATE the key space: out-of-range keys must fail
    loudly — JAX clamps gather indices and drops out-of-bounds scatters,
    so they would otherwise alias other keys and silently corrupt the
    replay (the py_ref oracle, being dict-based, would not notice)."""
    keys = np.asarray(keys)
    if keys.size and keys.min() < 0:
        raise ValueError("trace keys must be non-negative")
    kmax = int(keys.max()) if keys.size else -1
    if not key_space:
        return kmax + 1
    if kmax >= int(key_space):
        raise ValueError(f"trace key {kmax} out of range for "
                         f"key_space={int(key_space)}")
    return int(key_space)


def replay_trace(policy: str, keys: ArrayLike, us: ArrayLike,
                 capacity: int, *, key_space: int | None = None,
                 pad_to: int | None = None, **params: Any) -> ReplayResult:
    """Replay one trace through one policy instance as a compiled scan.

    ``us`` is the admission-coin stream (uniform [0,1)); pass the same
    values to the py_ref oracle for element-wise comparison.  ``pad_to``
    sizes the slot arrays (>= capacity) so differently-sized caches share
    a compiled program.
    """
    key_space = _resolve_key_space(keys, key_space)
    state = POLICIES[policy].init(int(capacity), key_space, pad_to=pad_to,
                                  **params)
    k, u = _as_device(keys, us)
    hits, evicted, ops = _replay_one(policy, state, k, u)
    return ReplayResult(np.asarray(hits), np.asarray(evicted, np.int64),
                        np.asarray(ops, np.int64))


def _count_leq_before(x: np.ndarray, span: int) -> np.ndarray:
    """c[t] = #{s < t : x[s] <= x[t]}, by bottom-up merge counting.

    O(T log^2 T) in vectorized numpy: at each level, elements of every
    right half-block are ranked into their sorted left half-block with one
    global ``searchsorted`` (rows made disjoint by adding ``i * span``,
    which requires every value to sit in [0, span - 1]).
    """
    T = len(x)
    n = 1 << max(1, int(T - 1).bit_length())
    pad_val = span - 1  # sorts after every real value, never counted
    xp = np.full(n, pad_val, np.int64)
    xp[:T] = x
    counts = np.zeros(n, np.int64)
    w = 1
    while w < n:
        npair = n // (2 * w)
        blocks = xp.reshape(npair, 2 * w)
        left_sorted = np.sort(blocks[:, :w], axis=1)
        offs = np.arange(npair, dtype=np.int64)[:, None] * span
        flat_left = (left_sorted + offs).ravel()
        pos = np.searchsorted(flat_left, (blocks[:, w:] + offs).ravel(),
                              side="right")
        c = pos - np.repeat(np.arange(npair, dtype=np.int64) * w, w)
        idx = (np.arange(npair)[:, None] * 2 * w + w
               + np.arange(w)[None, :]).ravel()
        counts[idx] += c
        w *= 2
    return counts[:T]


def lru_sweep(keys: ArrayLike,
              capacities: ArrayLike) -> tuple[np.ndarray, np.ndarray]:
    """Exact LRU replay of one trace at EVERY capacity in one pass.

    LRU is a stack algorithm (Mattson et al. 1970): the cache of size C is
    always the top C entries of the recency stack, so a request hits at
    capacity C iff its stack distance d (distinct keys touched since its
    previous access) satisfies d < C.  One O(T log^2 T) distance
    computation therefore yields the hit sequence of *all* capacities —
    the whole cache-size -> hit-ratio sweep without replaying per size.

    With P[t] the previous occurrence of key_t and D_t the number of
    distinct keys seen before t, ``d_t = D_t - P[t] - 1 + C_t`` where
    ``C_t = #{s < t : 0 <= P[s] <= P[t]}`` counts stack positions below
    P[t] that have already expired (their key was re-accessed).  C_t is
    the merge-count above.

    Returns (hits, ops) shaped (len(capacities), T) / (..., 4), matching
    the scan engine and py_ref bit for bit (LRU op vectors are determined
    by hit/miss and warmup: hit -> (1,1,0,0), miss -> (0,1,evict,0)).
    Evicted keys are not tracked here — use :func:`replay_trace` /
    :func:`replay_grid` when they matter.
    """
    keys = np.asarray(keys, np.int64)
    T = len(keys)
    order = np.lexsort((np.arange(T), keys))
    sk = keys[order]
    P = np.full(T, -1, np.int64)
    same = sk[1:] == sk[:-1]
    P[order[1:][same]] = order[:-1][same]
    first = P < 0
    D = np.cumsum(first) - first  # distinct keys seen strictly before t
    # first occurrences get a sentinel above every real P so they are never
    # counted as expired stack positions (and never produce hits anyway).
    x = np.where(first, np.int64(T + 1), P)
    C = _count_leq_before(x, span=T + 4)
    d = D - P - 1 + C

    caps = np.asarray(list(capacities), np.int64)[:, None]
    hits = (~first)[None, :] & (d[None, :] < caps)
    evict = (~hits) & (D[None, :] >= caps)
    ops = np.zeros((len(caps), T, 4), np.int64)
    ops[..., 0] = hits  # delink on every hit
    ops[..., 1] = 1  # head update on every request
    ops[..., 2] = evict  # tail update when a miss evicts
    return hits, ops


def replay_grid(policy: str, keys: ArrayLike, us: ArrayLike,
                capacities: ArrayLike, *, key_space: int | None = None,
                pad_to: int | None = None, **params: Any) -> ReplayResult:
    """Replay a (capacity x seed) measurement grid in one dispatch.

    ``keys``/``us`` are (T,) for a single stream or (S, T) for S seed
    streams; ``capacities`` is the cache-size grid.  Returns arrays shaped
    (len(capacities), S, T[, 4]) — one full sweep per compiled call.
    """
    keys = np.atleast_2d(np.asarray(keys))
    us = np.atleast_2d(np.asarray(us))
    key_space = _resolve_key_space(keys, key_space)
    states = POLICIES[policy].batched_init(capacities, key_space,
                                           pad_to=pad_to, **params)
    k, u = _as_device(keys, us)
    hits, evicted, ops = _replay_grid(policy, states, k, u)
    return ReplayResult(np.asarray(hits), np.asarray(evicted, np.int64),
                        np.asarray(ops, np.int64))


# ---------------------------------------------------------------------------
# Delayed-hit (in-flight window) classification — prong C of the
# miss-coalescing scenario.
# ---------------------------------------------------------------------------

TRUE_MISS, TRUE_HIT, DELAYED_HIT = 0, 1, 2
_FAR_PAST = np.int32(-(2**30))  # "no fetch ever" sentinel for last-fetch times


def _classify_lane(keys: jax.Array, hits: jax.Array, windows: jax.Array,
                   key_space_arr: jax.Array) -> jax.Array:
    """Scan one (T,) lane: per-request {true miss, true hit, delayed hit}.

    The carried state is the per-key fetch *expiry* index (the fetch that
    started at t with window w stays outstanding through t + w) — for a
    scalar window this is exactly the original last-fetch-time semantics,
    and it lets every true miss carry its own window (per-request miss
    latencies drawn from the disk service distribution).
    """
    T = keys.shape[0]

    def step(expiry: jax.Array,
             x: tuple[jax.Array, ...]) -> tuple[jax.Array, jax.Array]:
        t, k, h, w = x
        outstanding = t <= expiry[k]
        cls = jnp.where(outstanding, DELAYED_HIT,
                        jnp.where(h, TRUE_HIT, TRUE_MISS))
        starts_fetch = (~outstanding) & (~h)
        # write one selected scalar, not a select between whole tables:
        # under vmap the latter copies every lane's (K,) table per request
        expiry = expiry.at[k].set(jnp.where(starts_fetch, t + w, expiry[k]))
        return expiry, cls.astype(jnp.int8)

    exp0 = jnp.full_like(key_space_arr, _FAR_PAST)
    ts = jnp.arange(T, dtype=jnp.int32)
    _, cls = lax.scan(step, exp0, (ts, keys, hits, windows))
    return cls


_classify_grid = jax.jit(jax.vmap(_classify_lane, in_axes=(0, 0, None, None)))


def refetch_attempts(n: int, fail_prob: float, seed: int = 0) -> np.ndarray:
    """Per-request fetch attempt counts under TTL-style failure/re-issue.

    A backing-store fetch fails (times out, returns stale, is dropped)
    with probability ``fail_prob`` and is immediately re-issued, so the
    number of attempts behind request ``t``'s fetch — *if* ``t`` turns
    out to start one — is Geometric(1 - fail_prob) >= 1.  The stream is
    drawn up front from a dedicated SeedSequence substream (independent
    of the trace/coin/window streams at the same seed, reproducible
    alongside them) and consumed identically by the JAX and the py
    classifiers, so the twins stay bit-identical by construction.
    ``fail_prob=0`` yields all-ones.
    """
    if not 0.0 <= fail_prob < 1.0:
        raise ValueError("fail_prob must be in [0, 1)")
    if fail_prob == 0.0:
        return np.ones(n, dtype=np.int64)
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(4)[3])
    return rng.geometric(1.0 - fail_prob, size=n).astype(np.int64)


def _window_stream(window: ArrayLike | None, n_t: int, fail_prob: float,
                   fail_seed: int) -> np.ndarray:
    """Shared window plumbing: scalar or (T,) windows, validated and
    stretched by TTL re-issue attempts, resolved to an int32 (T,) stream.

    This is the single source of the fetch-expiry semantics — the host
    classifier below, the device classifier, and the fused pallas replay
    kernel (:mod:`repro.kernels.replay`) all consume windows through it,
    so the three stay bit-identical by construction."""
    windows = np.asarray(0 if window is None else window, dtype=np.int64)
    if windows.ndim > 1:
        raise ValueError(f"window must be a scalar or (T,), got {windows.shape}")
    if np.any(windows < 0):
        raise ValueError("window must be >= 0")
    if windows.ndim == 1 and windows.shape[0] != n_t:
        raise ValueError(f"per-request windows {windows.shape} vs "
                         f"{n_t} requests")
    out = np.broadcast_to(windows, (n_t,))
    if fail_prob:
        out = out * refetch_attempts(n_t, fail_prob, fail_seed)
    return out.astype(np.int32)


def _classify_inflight_device(keys: ArrayLike, hits: jax.Array,
                              window: ArrayLike, key_space: int | None,
                              fail_prob: float, fail_seed: int) -> jax.Array:
    """Device-resident classification — no host round-trip.

    The pallas replay engine (:mod:`repro.kernels.replay`) returns device
    arrays; pulling them through ``np.asarray`` just to push them back for
    the vmapped classifier costs a device->host->device bounce per call.
    Here ``hits`` stays on device end to end: the host only does shape
    plumbing and the (host-input) window stream.  ``key_space`` must be
    explicit — inferring it from the trace would force a device sync,
    which is the bounce this path exists to avoid."""
    if key_space is None or int(key_space) <= 0:
        raise ValueError("device-resident hits need an explicit key_space "
                         "(inferring it from the trace would sync the device)")
    if not isinstance(keys, jax.Array):
        _resolve_key_space(np.asarray(keys), int(key_space))
    kj = jnp.asarray(keys, jnp.int32)
    if kj.ndim == 1:
        kj = kj[None, :]
    elif kj.ndim != 2:
        raise ValueError(f"keys must be (T,) or (S, T), got {kj.shape}")
    n_t = int(kj.shape[-1])
    if int(hits.shape[-1]) != n_t:
        raise ValueError(f"hits {hits.shape} vs keys {kj.shape}: "
                         "trailing request axes differ")
    windows = _window_stream(window, n_t, fail_prob, fail_seed)
    n_s = int(kj.shape[0])
    flat_h = hits.astype(bool).reshape(-1, n_t)
    if n_s > 1:
        if hits.ndim < 2 or int(hits.shape[-2]) != n_s:
            raise ValueError(f"hits {hits.shape} second-to-last axis "
                             f"must match {n_s} key streams")
        key_lane = np.tile(np.arange(n_s), flat_h.shape[0] // n_s)
    else:
        key_lane = np.zeros(flat_h.shape[0], np.int64)
    lanes = _classify_grid(
        kj[jnp.asarray(key_lane)], flat_h, jnp.asarray(windows, jnp.int32),
        jnp.zeros((int(key_space),), jnp.int32),
    )
    return lanes.reshape(hits.shape)


def classify_inflight(keys: ArrayLike, hits: ArrayLike, window: ArrayLike,
                      key_space: int | None = None,
                      fail_prob: float = 0.0,
                      fail_seed: int = 0) -> np.ndarray | jax.Array:
    """Classify each replayed request as true hit / delayed hit / true miss.

    Overlays an MSHR-style in-flight window on an *already replayed* trace:
    a miss at request index ``t`` initiates a backing-store fetch that
    stays outstanding for the next ``window`` requests (``window`` is the
    miss latency expressed in requests — in a closed system running at
    throughput X with fetch latency L, ``window ~= X * L``).  ``window``
    is a scalar, or a ``(T,)`` array of per-request windows (each true
    miss's fetch carries its own latency, e.g. drawn from the disk service
    distribution via ``repro.core.harness.miss_window_stream``); an
    all-``W`` array classifies identically to the scalar ``W``.  Any request
    for the same key at index ``s`` with ``s - t <= window`` — whether the
    policy calls it a hit (the fill has not landed yet, so the "hit" in
    fact waits on the in-flight fetch) or a miss (the key was already
    re-evicted: the would-be second I/O coalesces onto the outstanding
    one) — is a **delayed hit** (Manohar et al. 2020).  Requests outside
    any window keep their policy classification: hit → ``TRUE_HIT``,
    miss → ``TRUE_MISS`` (and each true miss starts a fresh fetch).

    The classification is a pure post-pass: the policy's cache state and
    hit sequence are exactly those of :func:`replay_trace` /
    :func:`replay_grid` (which insert at miss time), so with ``window=0``
    the classes reduce bit-identically to the plain hit/miss split.

    ``keys`` is (T,) or (S, T); ``hits`` is (..., T) with any leading grid
    axes (e.g. the (capacity, seed, T) output of :func:`replay_grid` —
    when ``keys`` is (S, T) the second-to-last hits axis must be S).  All
    lanes classify in one vmapped dispatch.  Returns int8 classes shaped
    like ``hits`` with values {TRUE_MISS=0, TRUE_HIT=1, DELAYED_HIT=2}.

    ``fail_prob`` models TTL-style fetch failure with re-issue (the
    ROADMAP open item): the fetch a true miss starts fails with that
    probability and is retried, so its in-flight window stretches to
    ``window * attempts`` with ``attempts ~ Geometric(1 - fail_prob)``
    (drawn via :func:`refetch_attempts` at ``fail_seed``, identically in
    the py twin) — requests landing inside the extended window are
    delayed hits waiting on the eventually-successful fetch.
    ``fail_prob=0`` (and any ``window=0``) keeps the classification
    bit-identical to the no-failure path.

    The per-window coalescing factor sigma — the fraction of
    fill-requiring requests that found a fetch in flight, i.e.
    ``n_delayed / (n_delayed + n_true_miss)`` — plugs directly into
    :func:`repro.core.queueing.coalesced_network` as the measured
    ``sigma``, with the *true-hit* ratio as its ``p_hit``.

    When ``hits`` is a device-resident ``jax.Array`` (e.g. straight off
    :func:`repro.kernels.replay.replay_grid_pallas`) the classification
    runs end-to-end on device and returns a ``jax.Array`` — no
    device->host->device bounce; ``key_space`` must then be explicit,
    since inferring it from the trace would force a device sync.
    """
    if isinstance(hits, jax.Array):
        return _classify_inflight_device(keys, hits, window, key_space,
                                         fail_prob, fail_seed)
    keys = np.asarray(keys)
    hits_np = np.asarray(hits)
    windows = _window_stream(window, int(keys.shape[-1]), fail_prob, fail_seed)
    key_space = _resolve_key_space(keys, key_space)
    if keys.ndim == 1:
        keys2 = keys[None, :]
    elif keys.ndim == 2:
        keys2 = keys
    else:
        raise ValueError(f"keys must be (T,) or (S, T), got {keys.shape}")
    if hits_np.shape[-1] != keys2.shape[-1]:
        raise ValueError(f"hits {hits_np.shape} vs keys {keys.shape}: "
                         "trailing request axes differ")
    S = keys2.shape[0]
    flat = hits_np.reshape(-1, hits_np.shape[-1])
    if S > 1:
        if hits_np.ndim < 2 or hits_np.shape[-2] != S:
            raise ValueError(f"hits {hits_np.shape} second-to-last axis "
                             f"must match {S} key streams")
        key_lane = np.tile(np.arange(S), len(flat) // S)
    else:
        key_lane = np.zeros(len(flat), np.int64)

    kj = jnp.asarray(keys2, jnp.int32)
    hj = jnp.asarray(flat, bool)
    lanes = _classify_grid(
        kj[jnp.asarray(key_lane)], hj, jnp.asarray(windows, jnp.int32),
        jnp.zeros((key_space,), jnp.int32),
    )
    return np.asarray(lanes).reshape(hits_np.shape)

"""Flat-array cache policy state — the kernel-resident layout.

The dlist policies in :mod:`repro.cache.policies` encode recency as
doubly-linked-list *pointers* (``nxt``/``prv`` arrays plus head/tail
registers).  That layout is ideal for an O(1)-per-op CPU scan but hostile
to a Pallas kernel: every list splice is a chain of dependent scalar
scatters, and the state does not decompose into the handful of uniform
vectors a scratch allocation wants.

This module re-expresses every policy over a **timestamp layout**: list
order *is* descending push-timestamp.  One monotone ``now`` counter is
bumped on every (re-)push, so

* the list *tail* is the occupied slot with minimum ``ts``,
* the neighbour *toward the head* of slot ``h`` is the occupied slot with
  the smallest ``ts`` strictly greater than ``ts[h]``,
* two lists sharing one slot array (SLRU's B/T, S3-FIFO's S/M) are just
  membership masks over the same ``ts`` vector.

Victim search becomes a masked argmin over the padded slot axis — O(P)
vector work instead of O(1) pointer chasing, but *vectorizable*, which is
what both the batched ``lax.scan`` twin and the Pallas kernel need (and
measured on the 8-capacity x 60k-request grid the masked-argmin scan
already beats the dlist scan on CPU).

Every policy is a step with one uniform signature::

    state, hit, evicted, ops = FLAT_STEPS[policy](state, key, u, p, q)

written once against the indexed-state interface of
:mod:`repro.indexed_state`: it reads and writes single slots of named
tables (``K2S``, ``S2K``, ``TS``, ``BIT``, ``AUX``, ``GHOST``, ``REGS``) and
runs masked argmins over the slot tables.  The twin passes an
:class:`~repro.indexed_state.ArrayState` (jnp arrays, functional updates),
the Pallas kernel a :class:`~repro.kernels.state.RefState` (scratch refs,
in-place stores of the touched slots), so both execute the same policy
code.  ``p`` is the per-lane ``int32[N_PARAMS]`` parameter vector (indexed
by the static ``P_*`` constants, so a tuple of scalars works too) and
``q`` the scalar float coin threshold.  Capacity-derived parameters are
*traced* per-lane values, so one compiled program serves the whole
(capacity x seed) grid.  ``ops`` is a (delink, head, tail, scan) tuple of
int32 scalars.

Bit-identity with :mod:`repro.cache.policies` (and therefore with the
``py_ref`` oracles) is pinned by ``tests/test_pallas_replay.py``: hits,
evicted keys and op vectors must match element-wise, padded and exact.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.indexed_state import INT32_MAX, ArrayState, i32

# numpy scalars, not jnp: the Pallas kernel body closes over these, and a
# jnp scalar would be a captured device constant (pallas_call rejects those)
NIL = np.int32(-1)
# bias for collapsing a cyclic hand scan into one argmin (see _sieve_step);
# timestamps stay far below this (at most a couple of bumps per request)
_WRAP_BIAS = np.int32(2**30)

# -- regs vector layout (per-lane scalar registers) -------------------------
R_SIZE = 0      # slots ever filled, saturating at capacity
R_NOW = 1       # monotone push counter (list order == descending ts)
R_SIZET = 2     # SLRU: protected-list population
R_SIZES = 3     # S3-FIFO: small-queue population
R_SIZEM = 4     # S3-FIFO: main-queue population
R_GPOS = 5      # S3-FIFO: ghost-ring write cursor
R_HAND = 6      # SIEVE: hand slot, NIL when unset
N_REGS = 8

# -- per-lane parameter vector layout ---------------------------------------
P_CAP = 0
P_MAX_SCAN = 1
P_PROT_CAP = 2
P_S_CAP = 3
P_M_CAP = 4
P_GHOST_CAP = 5
N_PARAMS = 6

# Packed op-vector bit layout (delink, head, tail, scan) -> one int32.
# head is bounded by max_scan + 2 per access, tail by 2, scan by the
# capacity (SIEVE's hand walk); the 20 scan bits reach into the sign bit
# and cover every capacity below 2**20.
_OPS_HEAD_SHIFT = 1
_OPS_TAIL_SHIFT = 9
_OPS_SCAN_SHIFT = 12
_OPS_HEAD_MASK = 0xFF      # 8 bits
_OPS_TAIL_MASK = 0x7       # 3 bits
_OPS_SCAN_MASK = 0xFFFFF   # 20 bits

_PARAM_NAMES = {
    "lru": (),
    "fifo": (),
    "prob_lru": ("q",),
    "clock": ("max_scan",),
    "slru": ("protected_frac",),
    "s3fifo": ("small_frac", "max_scan"),
    "sieve": (),
}


# -- table names (the indexed-state layout every step is written against) ---
K2S = "key2slot"   # (K,) slot of each key, NIL when absent
S2K = "slot2key"   # (P,) key in each slot, NIL when free
TS = "ts"          # (P,) push timestamp (list position)
BIT = "bit"        # (P,) CLOCK/SIEVE/S3 reference bit
AUX = "aux"        # (P,) secondary membership bit (SLRU in_T, S3 in_M)
GHOST = "ghost"    # (P,) evicted-key ring (S3-FIFO), NIL-filled
REGS = "regs"      # (N_REGS,) scalar registers (see the R_* indices)

# the slot-indexed tables: the vector group the masked argmins walk
SLOT_TABLES = (S2K, TS, BIT, AUX, GHOST)


def flat_state_init(key_space: int, pad: int) -> ArrayState:
    """Zero state shared by every policy (SIEVE's hand starts at NIL),
    as the twin's array-backed indexed state."""
    regs = jnp.zeros((N_REGS,), jnp.int32).at[R_HAND].set(NIL)
    return ArrayState(tabs={
        K2S: jnp.full((key_space,), NIL, jnp.int32),
        S2K: jnp.full((pad,), NIL, jnp.int32),
        TS: jnp.zeros((pad,), jnp.int32),
        BIT: jnp.zeros((pad,), jnp.int32),
        AUX: jnp.zeros((pad,), jnp.int32),
        GHOST: jnp.full((pad,), NIL, jnp.int32),
        REGS: regs,
    }, vec=TS)


def flat_lane_params(policy: str, capacity: int,
                     **params: Any) -> Tuple[np.ndarray, float]:
    """Derive one lane's ``(p_vec, q)`` from the policy's init kwargs.

    Mirrors the ``<policy>_init`` derivations in policies.py exactly
    (``prot_cap = max(1, int(C * protected_frac))`` etc.) so the flat
    engine and the dlist engine agree on every rounded-down boundary.
    """
    if policy not in _PARAM_NAMES:
        raise KeyError(f"unknown policy {policy!r}")
    unknown = set(params) - set(_PARAM_NAMES[policy])
    if unknown:
        raise TypeError(
            f"policy {policy!r} got unexpected params {sorted(unknown)}"
        )
    cap = int(capacity)
    if policy == "s3fifo" and cap < 2:
        # mirror s3fifo_init: m_cap == 0 has no main list to evict from
        raise ValueError(
            "s3fifo needs capacity >= 2 (one small + one main slot)")
    s_cap = max(1, int(cap * float(params.get("small_frac", 0.1))))
    vec = np.zeros((N_PARAMS,), np.int32)
    vec[P_CAP] = cap
    vec[P_MAX_SCAN] = int(params.get("max_scan", 3))
    vec[P_PROT_CAP] = max(1, int(cap * float(params.get("protected_frac", 0.5))))
    vec[P_S_CAP] = s_cap
    vec[P_M_CAP] = cap - s_cap
    vec[P_GHOST_CAP] = max(1, cap - s_cap)
    # stored as float32 by prob_lru_init; replicate the rounding so the
    # coin comparison is bit-identical
    q = float(np.float32(params.get("q", 0.5)))
    return vec, q


def pack_ops(ops: Sequence[Any]) -> jnp.ndarray:
    """Pack a (delink, head, tail, scan) op tuple into one int32."""
    delink, head, tail, scan = (i32(o) for o in ops)
    return (
        delink
        | (head << _OPS_HEAD_SHIFT)
        | (tail << _OPS_TAIL_SHIFT)
        | (scan << _OPS_SCAN_SHIFT)
    ).astype(jnp.int32)


def unpack_ops(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_ops`; appends a trailing length-4 axis."""
    packed = jnp.asarray(packed, jnp.int32)
    return jnp.stack(
        [
            packed & 1,
            (packed >> _OPS_HEAD_SHIFT) & _OPS_HEAD_MASK,
            (packed >> _OPS_TAIL_SHIFT) & _OPS_TAIL_MASK,
            (packed >> _OPS_SCAN_SHIFT) & _OPS_SCAN_MASK,
        ],
        axis=-1,
    )


Ops = Tuple[Any, Any, Any, Any]   # (delink, head, tail, scan) int32 scalars


def _ops4(delink: Any = 0, head: Any = 0, tail: Any = 0,
          scan: Any = 0) -> Ops:
    return (i32(delink), i32(head), i32(tail), i32(scan))


def _ops_add(a: Ops, b: Ops) -> Ops:
    return (a[0] + b[0], a[1] + b[1], a[2] + b[2], a[3] + b[3])


def _occ(v: Any) -> Any:
    return v.slot2key != NIL


def _in_aux(v: Any) -> Any:
    return _occ(v) & (v.aux != 0)


def _not_aux(v: Any) -> Any:
    return _occ(v) & (v.aux == 0)


def _min_slot(st: Any, mask: Callable[[Any], Any]) -> Tuple[Any, Any]:
    """Slot with minimum ts among ``mask`` — the masked list's tail — and
    that ts (``INT32_MAX`` when the mask is empty)."""
    return st.argmin(lambda v: (mask(v), v.ts))


def _clear_key(st: Any, old_key: Any) -> Any:
    """``_table_evict``'s guarded mapping clear (no-op when old_key is NIL)."""
    return st.set_if(K2S, jnp.maximum(old_key, 0), old_key != NIL, NIL)


def _keep(st: Any, *outs: Any) -> Tuple[Any, Any]:
    return st, outs


# ---------------------------------------------------------------------------
# LRU family (LRU / FIFO / Prob-LRU) — mirrors
# policies._list_cache_access write for write.
# ---------------------------------------------------------------------------


def _make_list_step(reorder_of: Callable[[Any, Any], Any]):
    def step(st: Any, key: Any, u: Any, p: Any, q: Any):
        slot = st.get(K2S, key)
        hit = slot != NIL
        reorder = reorder_of(u, q)
        miss = ~hit
        size = st.get(REGS, R_SIZE)
        now = st.get(REGS, R_NOW)
        cap = p[P_CAP]
        full = size >= cap
        evict = miss & full
        st, victim = st.cond(evict, lambda st: (st, _min_slot(st, _occ)[0]),
                             lambda st: (st, NIL))
        s = jnp.where(hit, slot, jnp.where(full, victim, size))
        old_key = st.get(S2K, s)
        evicted = jnp.where(evict, old_key, NIL)
        idx_clear = jnp.where(evict, jnp.maximum(old_key, 0), key)
        st = st.set_if(K2S, idx_clear, miss, NIL)
        st = st.set_if(K2S, key, miss, s)
        st = st.set_if(S2K, s, miss, key)
        act = miss | (hit & reorder)
        st = st.set_if(TS, s, act, now)
        st = st.set(REGS, R_SIZE, jnp.minimum(size + i32(miss), cap))
        st = st.set(REGS, R_NOW, now + i32(act))
        ops = _ops4(delink=hit & reorder, head=act, tail=evict)
        return st, hit, evicted, ops

    return step


_lru_step = _make_list_step(lambda u, q: np.bool_(True))
_fifo_step = _make_list_step(lambda u, q: np.bool_(False))
_prob_lru_step = _make_list_step(
    lambda u, q: jnp.asarray(u, jnp.float32) >= q)


# ---------------------------------------------------------------------------
# CLOCK — bounded tail scan, reinsert 1-bit items.
# ---------------------------------------------------------------------------


def _clock_scan_evict(st: Any, mask: Callable[[Any], Any], max_scan: Any):
    """Shared CLOCK/S3-M eviction scan over a fixed membership mask.

    The victim stays *in* the mask for the whole loop (the dlist code only
    pops it as the loop's final act), so the mask never changes — only the
    timestamps of reinserted slots move.  Advances ``R_NOW`` past the
    reinsertions; returns (st, victim, n_reinsert).
    """

    def cond(st: Any, carry: Any):
        _, scans, done, _ = carry
        return (~done) & (scans <= max_scan)

    def body(st: Any, carry: Any):
        now, scans, done, victim = carry
        s, _ = _min_slot(st, mask)
        give_chance = (st.get(BIT, s) != 0) & (scans < max_scan)
        st = st.set_if(TS, s, give_chance, now)
        st = st.set_if(BIT, s, give_chance, 0)
        return st, (now + i32(give_chance), scans + 1, ~give_chance,
                    jnp.where(give_chance, victim, s))

    st, (now, scans, _, victim) = st.while_loop(
        cond, body,
        (st.get(REGS, R_NOW), np.int32(0), np.bool_(False), NIL),
    )
    st = st.set(REGS, R_NOW, now)
    return st, victim, scans - 1


def _fill(st: Any, key: Any, new_slot: Any, cap: Any) -> Any:
    """Push ``key`` into ``new_slot`` at the list head with a clear bit."""
    now = st.get(REGS, R_NOW)
    size = st.get(REGS, R_SIZE)
    st = st.set(K2S, key, new_slot)
    st = st.set(S2K, new_slot, key)
    st = st.set(TS, new_slot, now)
    st = st.set(BIT, new_slot, 0)
    st = st.set(REGS, R_NOW, now + 1)
    return st.set(REGS, R_SIZE, jnp.minimum(size + 1, cap))


def _fresh(st: Any):
    """The next never-used slot while the cache fills (no eviction)."""
    return st, (st.get(REGS, R_SIZE), NIL, _ops4())


def _set_bit(st: Any, slot: Any):
    return st.set(BIT, jnp.maximum(slot, 0), 1), (NIL, _ops4())


def _clock_step(st: Any, key: Any, u: Any, p: Any, q: Any):
    del u, q
    slot = st.get(K2S, key)
    hit = slot != NIL
    cap = p[P_CAP]

    def on_miss(st: Any):
        def evict(st: Any):
            st, victim, n_re = _clock_scan_evict(st, _occ, p[P_MAX_SCAN])
            old_key = st.get(S2K, victim)
            st = _clear_key(st, old_key)
            st = st.set(S2K, victim, NIL)
            return st, (victim, old_key, _ops4(head=n_re, tail=1, scan=n_re))

        st, (new_slot, old_key, ops) = st.cond(
            st.get(REGS, R_SIZE) < cap, _fresh, evict)
        st = _fill(st, key, new_slot, cap)
        return st, (old_key, _ops_add(ops, _ops4(head=1)))

    st, (evicted, ops) = st.cond(hit, lambda st: _set_bit(st, slot), on_miss)
    return st, hit, evicted, ops


# ---------------------------------------------------------------------------
# SLRU — probationary (aux=0) + protected (aux=1) masks over one ts vector.
# ---------------------------------------------------------------------------


def _slru_step(st: Any, key: Any, u: Any, p: Any, q: Any):
    del u, q
    slot0 = st.get(K2S, key)
    hit = slot0 != NIL
    slot = jnp.maximum(slot0, 0)
    hit_T = hit & (st.get(AUX, slot) != 0)
    cap = p[P_CAP]
    prot_cap = p[P_PROT_CAP]

    def on_hit_T(st: Any):
        now = st.get(REGS, R_NOW)
        st = st.set(TS, slot, now)
        st = st.set(REGS, R_NOW, now + 1)
        return st, (NIL, _ops4(delink=1, head=1))

    def on_hit_B(st: Any):
        now = st.get(REGS, R_NOW)
        size_t = st.get(REGS, R_SIZET) + 1
        st = st.set(AUX, slot, 1)
        st = st.set(TS, slot, now)
        now = now + 1
        # demote the protected tail back to B when T overflows; the slot
        # we just promoted carries the newest ts, so it is never the tail
        # (size_t > prot_cap >= 1 implies at least one older T member).
        demote = size_t > prot_cap

        def do_demote(st: Any):
            t_tail, _ = _min_slot(st, _in_aux)
            st = st.set(AUX, t_tail, 0)
            return st.set(TS, t_tail, now), ()

        st, _ = st.cond(demote, do_demote, _keep)
        st = st.set(REGS, R_NOW, now + i32(demote))
        st = st.set(REGS, R_SIZET, size_t - i32(demote))
        ops = _ops4(delink=1, head=1 + i32(demote), tail=demote)
        return st, (NIL, ops)

    def on_miss(st: Any):
        def evict(st: Any):
            # dlist order: evict B's tail, falling back to T's tail only
            # when B is empty.
            b_tail, b_ts = _min_slot(st, _not_aux)
            st, victim = st.cond(
                b_ts != INT32_MAX, lambda st: (st, b_tail),
                lambda st: (st, _min_slot(st, _in_aux)[0]))
            old_key = st.get(S2K, victim)
            st = _clear_key(st, old_key)
            st = st.set(S2K, victim, NIL)
            return st, (victim, old_key, _ops4(tail=1))

        st, (new_slot, old_key, ops) = st.cond(
            st.get(REGS, R_SIZE) < cap, _fresh, evict)
        now = st.get(REGS, R_NOW)
        size = st.get(REGS, R_SIZE)
        # the victim may have come from T (B empty): shrink sizeT using
        # the *pre-clear* membership bit, then mark the slot probationary.
        size_t = st.get(REGS, R_SIZET) - i32(st.get(AUX, new_slot) != 0)
        st = st.set(K2S, key, new_slot)
        st = st.set(S2K, new_slot, key)
        st = st.set(TS, new_slot, now)
        st = st.set(AUX, new_slot, 0)
        st = st.set(REGS, R_NOW, now + 1)
        st = st.set(REGS, R_SIZET, size_t)
        st = st.set(REGS, R_SIZE, jnp.minimum(size + 1, cap))
        return st, (old_key, _ops_add(ops, _ops4(head=1)))

    def on_hit_any(st: Any):
        return st.cond(hit_T, on_hit_T, on_hit_B)

    st, (evicted, ops) = st.cond(hit, on_hit_any, on_miss)
    return st, hit, evicted, ops


# ---------------------------------------------------------------------------
# S3-FIFO — small (aux=0) + main (aux=1) masks + ghost ring.
# ---------------------------------------------------------------------------


def _s3_evict_m(st: Any, p: Any):
    """Evict from M with the CLOCK scan; returns (st, old_key, ops)."""
    st, victim, n_re = _clock_scan_evict(st, _in_aux, p[P_MAX_SCAN])
    old_key = st.get(S2K, victim)
    st = _clear_key(st, old_key)
    st = st.set(S2K, victim, NIL)
    st = st.set(AUX, victim, 0)
    st = st.set(REGS, R_SIZEM, st.get(REGS, R_SIZEM) - 1)
    return st, old_key, _ops4(head=n_re, tail=1, scan=n_re)


def _s3fifo_step(st: Any, key: Any, u: Any, p: Any, q: Any):
    del u, q
    slot = st.get(K2S, key)
    hit = slot != NIL
    cap = p[P_CAP]

    def mk_room_m(st: Any, ops: Ops, evicted: Any):
        st, old_key, eops = _s3_evict_m(st, p)
        return st, (_ops_add(ops, eops), old_key)

    def on_miss(st: Any):
        _, ghost_at = st.argmin(lambda v: (v.ghost == key, v.slot))
        in_ghost = ghost_at != INT32_MAX

        need_m = in_ghost & (st.get(REGS, R_SIZEM) >= p[P_M_CAP])
        st, (ops, evicted) = st.cond(need_m, mk_room_m, _keep, _ops4(), NIL)

        def mk_room_s(st: Any, ops: Ops, evicted: Any):
            s_tail, _ = _min_slot(st, _not_aux)
            promote = st.get(BIT, s_tail) != 0

            def do_promote(st: Any, ops: Ops, evicted: Any):
                st, (ops, evicted) = st.cond(
                    st.get(REGS, R_SIZEM) >= p[P_M_CAP], mk_room_m, _keep,
                    ops, evicted)
                now = st.get(REGS, R_NOW)
                st = st.set(TS, s_tail, now)
                st = st.set(AUX, s_tail, 1)
                st = st.set(BIT, s_tail, 0)
                st = st.set(REGS, R_NOW, now + 1)
                st = st.set(REGS, R_SIZES, st.get(REGS, R_SIZES) - 1)
                st = st.set(REGS, R_SIZEM, st.get(REGS, R_SIZEM) + 1)
                return st, (_ops_add(ops, _ops4(head=1, tail=1)), evicted)

            def do_evict(st: Any, ops: Ops, evicted: Any):
                old_key = st.get(S2K, s_tail)
                st = _clear_key(st, old_key)
                st = st.set(S2K, s_tail, NIL)
                gpos = st.get(REGS, R_GPOS)
                st = st.set(GHOST, gpos, old_key)
                st = st.set(REGS, R_GPOS, (gpos + 1) % p[P_GHOST_CAP])
                st = st.set(REGS, R_SIZES, st.get(REGS, R_SIZES) - 1)
                return st, (_ops_add(ops, _ops4(tail=1)), old_key)

            return st.cond(promote, do_promote, do_evict, ops, evicted)

        need_s = (~in_ghost) & (st.get(REGS, R_SIZES) >= p[P_S_CAP])
        st, (ops, evicted) = st.cond(need_s, mk_room_s, _keep, ops, evicted)

        # place: next warmup slot while filling, else first freed slot
        # (room-making above guarantees one exists).
        size = st.get(REGS, R_SIZE)
        st, new_slot = st.cond(
            size < cap, lambda st: (st, size),
            lambda st: (st, st.argmin(lambda v: (v.slot2key == NIL,
                                                 v.slot))[0]))
        now = st.get(REGS, R_NOW)
        to_m = in_ghost
        st = st.set(K2S, key, new_slot)
        st = st.set(S2K, new_slot, key)
        st = st.set(TS, new_slot, now)
        st = st.set(AUX, new_slot, i32(to_m))
        st = st.set(BIT, new_slot, 0)
        st = st.set(REGS, R_NOW, now + 1)
        st = st.set(REGS, R_SIZES, st.get(REGS, R_SIZES) + i32(~to_m))
        st = st.set(REGS, R_SIZEM, st.get(REGS, R_SIZEM) + i32(to_m))
        st = st.set(REGS, R_SIZE, jnp.minimum(size + 1, cap))
        return st, (evicted, _ops_add(ops, _ops4(head=1)))

    st, (evicted, ops) = st.cond(hit, lambda st: _set_bit(st, slot), on_miss)
    return st, hit, evicted, ops


# ---------------------------------------------------------------------------
# SIEVE — lazy promotion; the hand is a slot index, NIL when unset.
# ---------------------------------------------------------------------------


def _sieve_step(st: Any, key: Any, u: Any, p: Any, q: Any):
    del u, q
    slot = st.get(K2S, key)
    hit = slot != NIL
    cap = p[P_CAP]

    def on_miss(st: Any):
        def evict(st: Any):
            tail, _ = _min_slot(st, _occ)
            hand = st.get(REGS, R_HAND)
            start = jnp.where(hand == NIL, tail, hand)

            # The hand walk visits occupied slots in cyclic ts order from
            # ``start`` (toward the head, wrapping to the tail), clearing
            # bits until the first clear-bit slot — which makes the victim
            # and the cleared set computable in ONE vectorized pass instead
            # of an O(P)-per-step while loop: the victim is the first
            # original-bit-0 slot in cyclic order (upper segment
            # ts >= ts[start] first, then the wrapped lower segment), or
            # ``start`` itself after a full clearing cycle; the cleared
            # slots are exactly the cyclic prefix strictly before it.
            ts_start = st.get(TS, start)

            # Cyclic order collapses to one argmin by biasing the wrapped
            # lower segment (ts < ts[start]) above the upper one; ts stays
            # far below the bias (one bump per push), so no overflow.
            def cyclic(ts: Any) -> Any:
                return ts + jnp.where(ts < ts_start, _WRAP_BIAS, 0)

            idx, ck_min = st.argmin(
                lambda v: (_occ(v) & (v.bit == 0), cyclic(v.ts)))
            found = ck_min != INT32_MAX
            victim = jnp.where(found, idx, start)
            ts_v = st.get(TS, victim)
            ck_v = cyclic(ts_v)
            # Cleared set = cyclic prefix strictly before the victim; a
            # full clearing cycle (no clear bit anywhere) clears the lot.
            st, scans = st.update(
                BIT, lambda v: (_occ(v) & (~found | (cyclic(v.ts) < ck_v)),
                                0))
            # hand moves one step past the victim (NIL at the head ->
            # restart from the tail next eviction), computed *before* the
            # victim leaves the list, exactly like dl.prv[victim].
            nh, ts_nh = _min_slot(st, lambda v: _occ(v) & (v.ts > ts_v))
            new_hand = jnp.where(ts_nh != INT32_MAX, nh, NIL)
            old_key = st.get(S2K, victim)
            st = _clear_key(st, old_key)
            st = st.set(S2K, victim, NIL)
            st = st.set(REGS, R_HAND, new_hand)
            return st, (victim, old_key, _ops4(tail=1, scan=scans))

        st, (new_slot, old_key, ops) = st.cond(
            st.get(REGS, R_SIZE) < cap, _fresh, evict)
        st = _fill(st, key, new_slot, cap)
        return st, (old_key, _ops_add(ops, _ops4(head=1)))

    st, (evicted, ops) = st.cond(hit, lambda st: _set_bit(st, slot), on_miss)
    return st, hit, evicted, ops


FLAT_STEPS: Dict[str, Callable[..., Any]] = {
    "lru": _lru_step,
    "fifo": _fifo_step,
    "prob_lru": _prob_lru_step,
    "clock": _clock_step,
    "slru": _slru_step,
    "s3fifo": _s3fifo_step,
    "sieve": _sieve_step,
}

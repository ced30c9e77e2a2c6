"""Pallas replay engine: the whole (capacity x seed) grid in one dispatch.

This is the promotion of :func:`repro.kernels.cache_update.lru_batch_update`
from demo to engine.  That kernel showed the layout move — recency as a
timestamp array, victim search as a masked argmin — on a single batched
update; here the same flat layout (:mod:`repro.cache.flat`) carries a
*full trace replay* for every policy in the suite:

* the grid is (lane, trace chunk): the first axis enumerates
  (capacity x seed) lanes, the second (``"arbitrary"``) streams each
  lane's requests through SMEM in ``CHUNK``-request blocks,
* each lane's cache state (key->slot table, timestamp/presence/bit
  tables, scalar registers) lives in kernel scratch across all of its
  chunks — nothing round-trips through HBM between requests — and each
  request loads and stores only the slots it touches
  (:class:`~repro.kernels.state.RefState`),
* a ``fori_loop`` walks the chunk, calling the *same* per-policy step
  functions the CPU twin scans over, and
* the delayed-hit classifier (prong C's ``classify_inflight``) is fused
  into the same loop via a per-key fetch-expiry table in scratch, so the
  Mattson-style sweep + classification pipeline is ONE dispatch instead
  of replay -> host -> classify -> host.

The scan-policy evictions (CLOCK / SIEVE / S3-FIFO) run their hand scans
*inside* the kernel body: CLOCK/S3 as ``lax.while_loop``s bounded by
``max_scan``, SIEVE's bit clearing as one masked pass, emitting the exact
(hit, evicted, op-vector) outputs of the dlist engine.

Three executables share the step functions, so they agree by construction
and are pinned bit-identical in ``tests/test_pallas_replay.py``:

``interpret=None``  auto: the compiled vmapped ``lax.scan`` twin on CPU
                    (single jitted dispatch), the real kernel on TPU
``interpret=True``  the pallas interpreter — the CI fallback that runs the
                    actual kernel body on CPU (slow: grid cells execute
                    sequentially; tests only)
``interpret=False`` force ``pallas_call`` compilation (TPU)

:func:`pallas_grid` is the jitted kernel dispatch alone;
``pallas_grid.lower(...)`` compiles it ahead of time (the described-chip
compile tests use that).

Op vectors are returned *packed* (one int32 per request, see
``flat.pack_ops``) to keep the kernel's output streams narrow; unpack at
the host boundary with ``flat.unpack_ops``.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.cache import flat
from repro.cache.replay import (DELAYED_HIT, TRUE_HIT, TRUE_MISS, _FAR_PAST,
                                _resolve_key_space, _window_stream)
from repro.cache.policies import _padded
from repro.kernels import on_tpu
from repro.kernels.state import LANES, RefState, vmem_block_rows, vmem_rows


class PallasReplayResult(NamedTuple):
    """Device-resident replay grid output, shaped (C, S, T).

    ``ops`` is packed (``flat.unpack_ops`` appends the length-4 op axis);
    ``cls`` is the fused delayed-hit classification (int8, the
    ``classify_inflight`` classes) or None when no window was given.
    Everything stays on device — feed ``hits``/``cls`` straight into the
    downstream jitted reductions without a host bounce.
    """

    hits: jax.Array          # (C, S, T) bool
    evicted: jax.Array       # (C, S, T) int32, -1 if none
    ops: jax.Array           # (C, S, T) int32, packed op vectors
    cls: Optional[jax.Array]  # (C, S, T) int8, or None


EXPIRY = "expiry"  # (K,) per-key fetch expiry: the fused classifier's table

# requests per grid step along the streamed ("arbitrary") trace axis
CHUNK = 2048
# rows of 128 slots the kernel's masked argmins walk per loop step
BLOCK_ROWS = 32


def _lane_step(policy: str, st, t, k, u, w, p, q):
    """One request on one lane: policy step + fused classification."""
    st, hit, evicted, ops4 = flat.FLAT_STEPS[policy](st, k, u, p, q)
    outstanding = t <= st.get(EXPIRY, k)
    cls = jnp.where(outstanding, DELAYED_HIT,
                    jnp.where(hit, TRUE_HIT, TRUE_MISS)).astype(jnp.int32)
    starts_fetch = (~outstanding) & (~hit)
    st = st.set_if(EXPIRY, k, starts_fetch, t + w)
    return st, (hit, evicted, flat.pack_ops(ops4), cls)


@functools.partial(jax.jit, static_argnames=("policy", "key_space", "pad"))
def _twin_grid(policy: str, pvecs: jax.Array, qs: jax.Array,
               keys: jax.Array, us: jax.Array, windows: jax.Array,
               key_space: int, pad: int):
    """The CPU twin: vmapped lax.scan over lanes, same step as the kernel."""
    state0 = flat.flat_state_init(key_space, pad)
    state0 = state0._replace(tabs={
        **state0.tabs, EXPIRY: jnp.full((key_space,), _FAR_PAST, jnp.int32)})
    ts_idx = jnp.arange(keys.shape[-1], dtype=jnp.int32)

    def lane(pvec, q, k, u, w):
        def body(st, x):
            return _lane_step(policy, st, *x, pvec, q)

        _, out = lax.scan(body, state0, (ts_idx, k, u, w))
        return out

    hits, evicted, ops, cls = jax.vmap(lane)(pvecs, qs, keys, us, windows)
    return hits, evicted, ops, cls.astype(jnp.int8)


def _fill_rows(ref, value, n_blocks, rows: int) -> None:
    """Fill the first ``n_blocks`` blocks of ``rows`` rows of a VMEM table."""
    block = jnp.full((rows, LANES), value, jnp.int32)

    def body(b, carry):
        ref[pl.ds(pl.multiple_of(b * rows, rows), rows), :] = block
        return carry

    lax.fori_loop(0, n_blocks, body, 0)


def _replay_kernel(pvec_ref, q_ref, keys_ref, us_ref, win_ref,
                   hits_ref, ev_ref, ops_ref, cls_ref,
                   k2s_s, exp_s, s2k_s, ts_s, bit_s, aux_s, ghost_s, regs_s,
                   *, policy: str, block_rows: int):
    """Grid cell (lane, chunk): one chunk of one (capacity, seed) lane.

    The cache state lives in scratch for the whole lane: the first chunk
    of each lane re-initialises it (grid cells of different lanes share
    the allocation), later chunks continue from it.  Only the slot blocks
    below the lane's capacity are ever read, so only those are reset.
    """
    lane = pl.program_id(0)
    chunk = keys_ref.shape[-1]
    p = tuple(pvec_ref[lane, i] for i in range(flat.N_PARAMS))
    q = q_ref[lane]
    span = block_rows * LANES
    n_blocks = (p[flat.P_CAP] + (span - 1)) // span

    @pl.when(pl.program_id(1) == 0)
    def _init():
        k_blocks = k2s_s.shape[0] // 8
        _fill_rows(k2s_s, flat.NIL, k_blocks, 8)
        _fill_rows(exp_s, _FAR_PAST, k_blocks, 8)
        for ref, value in ((s2k_s, flat.NIL), (ts_s, 0), (bit_s, 0),
                           (aux_s, 0), (ghost_s, flat.NIL)):
            _fill_rows(ref, value, n_blocks, block_rows)
        for r in range(flat.N_REGS):
            regs_s[r] = flat.NIL if r == flat.R_HAND else np.int32(0)

    st = RefState(
        {flat.K2S: k2s_s, EXPIRY: exp_s, flat.S2K: s2k_s, flat.TS: ts_s,
         flat.BIT: bit_s, flat.AUX: aux_s, flat.GHOST: ghost_s,
         flat.REGS: regs_s},
        smem=(flat.REGS,), block_rows=block_rows, n_blocks=n_blocks,
    )
    base = pl.program_id(1) * chunk

    def body(i, carry):
        _, (hit, evicted, packed, cls) = _lane_step(
            policy, st, base + i, keys_ref[0, i], us_ref[0, i],
            win_ref[0, i], p, q)
        hits_ref[0, i] = hit.astype(jnp.int32)
        ev_ref[0, i] = evicted
        ops_ref[0, i] = packed
        cls_ref[0, i] = cls
        return carry

    lax.fori_loop(0, chunk, body, 0)


# Mosaic's default scoped-VMEM limit on TPU v5e, and the room left beside
# the scratch for the compiler's own temporaries when asking for more
_DEFAULT_SCOPED_VMEM = 16 << 20
_VMEM_HEADROOM = 1 << 20


def _vmem_limit(key_space: int, pad: int) -> Optional[int]:
    """``vmem_limit_bytes`` for one lane's scratch: the compiler default
    while it fits, else exactly the scratch plus headroom (2^20 keys and
    2^19 slots take 18 MiB)."""
    br = vmem_block_rows(pad, BLOCK_ROWS)
    scratch = 4 * LANES * (2 * vmem_rows(key_space)
                           + len(flat.SLOT_TABLES) * vmem_rows(pad, br))
    need = scratch + _VMEM_HEADROOM
    return None if need <= _DEFAULT_SCOPED_VMEM else need


@functools.partial(jax.jit,
                   static_argnames=("policy", "key_space", "pad", "interpret"))
def pallas_grid(policy: str, pvecs, qs, keys, us, windows, *,
                key_space: int, pad: int, interpret: bool = False):
    """The replay kernel over a (lanes, T) request grid: one dispatch.

    Inputs are the per-lane arrays :func:`replay_grid_pallas` builds:
    ``pvecs`` (L, N_PARAMS) int32, ``qs`` (L,) float32 and the (L, T)
    ``keys`` (int32), ``us`` (float32) and ``windows`` (int32) streams.
    Returns (hits bool, evicted, packed ops, cls int8), each (L, T).
    Jitted, so ``pallas_grid.lower(...)`` AOT-compiles the kernel alone.
    """
    n_lanes, n_t = keys.shape
    chunk = min(CHUNK, -(-n_t // LANES) * LANES)
    n_chunks = -(-n_t // chunk)
    # trailing filler requests run after every real one, so they cannot
    # change a real output; they are sliced off below
    fill = ((0, 0), (0, n_chunks * chunk - n_t))
    streams = [jnp.pad(a, fill)[:, None, :] for a in (keys, us, windows)]
    br = vmem_block_rows(pad, BLOCK_ROWS)
    kernel = functools.partial(_replay_kernel, policy=policy, block_rows=br)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    chunk_spec = pl.BlockSpec((None, 1, chunk), lambda i, c: (i, 0, c),
                              memory_space=pltpu.SMEM)
    out_shape = jax.ShapeDtypeStruct((n_lanes, 1, n_chunks * chunk),
                                     jnp.int32)
    k_rows, p_rows = vmem_rows(key_space), vmem_rows(pad, br)
    hits, evicted, ops, cls = pl.pallas_call(
        kernel,
        grid=(n_lanes, n_chunks),
        in_specs=[smem, smem, chunk_spec, chunk_spec, chunk_spec],
        out_specs=[chunk_spec] * 4,
        out_shape=[out_shape] * 4,
        scratch_shapes=[
            pltpu.VMEM((k_rows, LANES), jnp.int32),   # key2slot
            pltpu.VMEM((k_rows, LANES), jnp.int32),   # fetch expiry
            *[pltpu.VMEM((p_rows, LANES), jnp.int32)
              for _ in flat.SLOT_TABLES],
            pltpu.SMEM((flat.N_REGS,), jnp.int32),    # scalar registers
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(key_space, pad),
        ),
        interpret=interpret,
    )(pvecs, qs, *streams)
    hits, evicted, ops, cls = (a[:, 0, :n_t] for a in (hits, evicted, ops, cls))
    return hits != 0, evicted, ops, cls.astype(jnp.int8)


def _lane_inputs(policy: str, keys, us, capacities, key_space, pad_to,
                 params) -> Tuple[np.ndarray, ...]:
    """Host-side lane setup: validate, normalise to (S, T), build per-lane
    parameter vectors, and tile everything to the (C*S,) lane axis
    (lane = c * S + s, so outputs reshape to (C, S, T))."""
    keys = np.asarray(keys)
    us = np.asarray(us)
    if keys.shape != us.shape:
        raise ValueError(f"keys {keys.shape} vs us {us.shape} shape mismatch")
    if keys.ndim == 1:
        keys = keys[None, :]
        us = us[None, :]
    elif keys.ndim != 2:
        raise ValueError(f"keys must be (T,) or (S, T), got {keys.shape}")
    key_space = _resolve_key_space(keys, key_space)
    caps = [int(c) for c in np.atleast_1d(np.asarray(capacities))]
    if not caps:
        raise ValueError("need at least one capacity")
    pad = _padded(max(caps), pad_to)
    per_cap = [flat.flat_lane_params(policy, c, **params) for c in caps]
    pvecs = np.stack([v for v, _ in per_cap])
    qs = np.asarray([q for _, q in per_cap], np.float32)
    n_s = keys.shape[0]
    keys_l = np.tile(keys, (len(caps), 1)).astype(np.int32)
    us_l = np.tile(us, (len(caps), 1)).astype(np.float32)
    pvecs_l = np.repeat(pvecs, n_s, axis=0)
    qs_l = np.repeat(qs, n_s)
    return keys_l, us_l, pvecs_l, qs_l, key_space, pad, len(caps), n_s


def replay_grid_pallas(policy: str, keys, us, capacities, *,
                       key_space: Optional[int] = None,
                       pad_to: Optional[int] = None,
                       window=None, fail_prob: float = 0.0,
                       fail_seed: int = 0,
                       interpret: Optional[bool] = None,
                       **params: Any) -> PallasReplayResult:
    """Replay a (capacity x seed) grid with the flat-state engine, fusing
    the delayed-hit classification into the same dispatch.

    Drop-in grid semantics of :func:`repro.cache.replay.replay_grid` (same
    hits / evicted keys / op counts, bit-identical, pinned by tests) plus
    the ``classify_inflight`` post-pass computed in the same pass over the
    stream when ``window`` is given (scalar or per-request (T,) array;
    ``fail_prob`` stretches windows by geometric re-issue attempts exactly
    like the classifier).

    ``interpret=None`` picks the fastest correct executable for the
    backend: the real pallas kernel on TPU, the jitted scan twin on CPU
    (same step functions, one dispatch).  ``True`` forces the pallas
    interpreter (the kernel body itself, run on CPU — the CI fallback).
    """
    (keys_l, us_l, pvecs_l, qs_l, key_space, pad,
     n_caps, n_s) = _lane_inputs(policy, keys, us, capacities, key_space,
                                 pad_to, params)
    win_l = np.broadcast_to(
        _window_stream(window, keys_l.shape[1], fail_prob, fail_seed),
        keys_l.shape,
    )
    args = (jnp.asarray(pvecs_l), jnp.asarray(qs_l), jnp.asarray(keys_l),
            jnp.asarray(us_l), jnp.asarray(win_l))
    if interpret is None and not on_tpu():
        hits, evicted, ops, cls = _twin_grid(
            policy, *args, key_space=key_space, pad=pad
        )
    else:
        hits, evicted, ops, cls = pallas_grid(
            policy, *args, key_space=key_space, pad=pad,
            interpret=bool(interpret) if interpret is not None else False,
        )
    shape = (n_caps, n_s, keys_l.shape[1])
    return PallasReplayResult(
        hits=hits.reshape(shape),
        evicted=evicted.reshape(shape),
        ops=ops.reshape(shape),
        cls=cls.reshape(shape) if window is not None else None,
    )


def unpack_grid_ops(res: PallasReplayResult) -> np.ndarray:
    """Host-side (C, S, T, 4) int64 op counts, matching ReplayResult.ops."""
    return np.asarray(flat.unpack_ops(res.ops), np.int64)

"""Batched LRU metadata update — the paper's bottleneck, TPU-adapted.

The paper shows LRU throughput collapses because every *hit* serializes a
delink + head-update on a global linked list (demand = p_hit · S_delink per
request).  A linked list is the wrong structure for a TPU: the adaptation
(DESIGN.md §3) replaces it with a recency-timestamp array and performs a
whole batch of N accesses as ONE vectorized sweep:

    timestamps[slot in batch] <- now ;  victim = argmin(timestamps)

The sweep is tiled over VMEM (grid over slot tiles, each tile compared
against the access batch), so its cost is O(C / membw) *per batch* instead
of O(N · S_delink) serialized — the per-request demand on the serialized
resource drops by ~N·S_delink / (C/membw), which pushes the critical hit
ratio p* -> 1 (quantified in benchmarks/serving_integration.py).

Eviction semantics match LRU exactly: argmin of last-access time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


INT_MAX = jnp.int32(2**31 - 1)


def _sweep_kernel(ts_ref, acc_ref, now_ref, new_ts_ref, min_ref, arg_ref, *,
                  tile: int):
    gi = pl.program_id(0)
    ts = ts_ref[...]  # (tile,)
    accessed = acc_ref[...]  # (N,)
    now = now_ref[0]

    ids = gi * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)[:, 0]
    hit = jnp.any(ids[:, None] == accessed[None, :], axis=1)
    new_ts = jnp.where(hit, now, ts)
    new_ts_ref[...] = new_ts

    # per-tile min + argmin (final cross-tile reduction happens in ops.py)
    tile_min = jnp.min(new_ts)
    min_ref[0] = tile_min
    arg_ref[0] = ids[jnp.argmin(new_ts)]


def lru_batch_update(timestamps, accessed, now, *, tile: int = 512,
                     interpret: bool = False):
    """timestamps: (C,) int32; accessed: (N,) int32 slot ids (pad with -1);
    now: scalar int32.  Returns (new_timestamps, victim_slot).

    victim = least-recently-used slot AFTER the batch is applied.
    """
    C = timestamps.shape[0]
    N = accessed.shape[0]
    tile = min(tile, C)
    # Pad to the next tile multiple with INT_MAX sentinels.  Slot ids past C
    # never appear in `accessed` (ids are < C, padding is -1), so sentinels
    # survive the sweep untouched and can never win the argmin victim search
    # (any real slot's timestamp is < INT_MAX).
    pad = (-C) % tile
    if pad:
        timestamps = jnp.concatenate(
            [timestamps, jnp.full((pad,), INT_MAX, jnp.int32)]
        )
    n_tiles = (C + pad) // tile

    kernel = functools.partial(_sweep_kernel, tile=tile)
    new_ts, mins, args = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((N,), lambda i: (0,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C + pad,), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles,), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(timestamps, accessed, jnp.asarray([now], jnp.int32))

    best = jnp.argmin(mins)
    return new_ts[:C], args[best]

"""Jitted public wrappers around the Pallas kernels.

Model code calls these (layout adaptation + padding + jit) with
``interpret=not repro.kernels.on_tpu()``: on the TPU the calls compile to
Mosaic kernels, elsewhere the kernels run in the Pallas interpreter.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import cache_update as _cache
from repro.kernels import flash_attention as _flash
from repro.kernels import linear_scan as _scan
from repro.kernels import paged_attention as _paged


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, bq=128, bk=128,
                    interpret=False):
    """(B, T, H, dh) x (B, S, KV, dh) -> (B, T, H, dh) (model layout)."""
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    out = _flash.flash_attention_fwd(
        qt, kt, vt, causal=causal, window=window, bq=bq, bk=bk,
        interpret=interpret,
    )
    return out.swapaxes(1, 2)


@partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, pages_k, pages_v, block_table, seq_lens, *,
                    interpret=False):
    return _paged.paged_attention(
        q, pages_k, pages_v, block_table, seq_lens, interpret=interpret
    )


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_scan(r, k, v, w, u, *, chunk=128, interpret=False):
    T = r.shape[1]
    c = min(chunk, T)
    pad = (-T) % c
    if pad:
        # w=1 on padding keeps the state invariant; outputs are sliced off
        zeros = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = zeros(r), zeros(k), zeros(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
    y = _scan.wkv6_scan(r, k, v, w, u, chunk=c, interpret=interpret)
    return y[:, :T]


@partial(jax.jit, static_argnames=("tile", "interpret"))
def lru_batch_update(timestamps, accessed, now, *, tile=512, interpret=False):
    return _cache.lru_batch_update(
        timestamps, accessed, now, tile=min(tile, timestamps.shape[0]),
        interpret=interpret,
    )

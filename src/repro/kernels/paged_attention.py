"""Paged decode attention as a Pallas TPU kernel.

The serving layer stores KV in fixed-size pages; a request's pages are
scattered (block table indirection).  The kernel uses **scalar prefetch**:
the block table rides in SMEM and the K/V BlockSpec index maps dereference
it, so Pallas' pipeline logic issues the HBM->VMEM page copies for exactly
the pages each sequence owns — the TPU-native analogue of a gather.

Grid = (B, KV, n_pages); pages are the sequential axis with online-softmax
state in VMEM scratch.  All `group` query heads of a KV head are processed
together (GQA).  Padded pages (beyond seq_len) are masked to -inf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0e38


def _paged_kernel(block_table, seq_lens, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale, page: int, n_pages: int):
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)  # (page, dh)
    v = v_ref[0, 0].astype(jnp.float32)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (G, page)
    pos = pi * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = pos < seq_lens[b]
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )

    @pl.when(pi == n_pages - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def paged_attention(
    q, pages_k, pages_v, block_table, seq_lens, *, interpret: bool = False
):
    """Decode attention over paged KV.

    q:           (B, H, dh)        one query token per sequence
    pages_k/v:   (P, page, KV, dh) global page pool
    block_table: (B, n_pages) int32 — page ids per sequence (pad with 0)
    seq_lens:    (B,) int32 — valid token count per sequence
    Returns (B, H, dh).
    """
    B, H, dh = q.shape
    P, page, KV, _ = pages_k.shape
    n_pages = block_table.shape[1]
    group = H // KV
    scale = dh**-0.5

    qg = q.reshape(B, KV, group, dh)
    # (P, page, KV, dh) -> (P, KV, page, dh) so a block is one page x head
    kt = pages_k.swapaxes(1, 2)
    vt = pages_v.swapaxes(1, 2)

    kernel = functools.partial(
        _paged_kernel, scale=scale, page=page, n_pages=n_pages
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, group, dh),
                         lambda b, kv, pi, bt, sl: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, page, dh),
                         lambda b, kv, pi, bt, sl: (bt[b, pi], kv, 0, 0)),
            pl.BlockSpec((1, 1, page, dh),
                         lambda b, kv, pi, bt, sl: (bt[b, pi], kv, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, dh),
                               lambda b, kv, pi, bt, sl: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, group, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_table, seq_lens, qg, kt, vt)
    return out.reshape(B, H, dh)

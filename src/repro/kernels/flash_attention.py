"""Flash attention (forward) as a Pallas TPU kernel.

Tiling: grid = (batch, q_heads, T/bq, S/bk); the KV axis is the innermost
(sequential) grid dimension, with the online-softmax running state (m, l,
acc) held in VMEM scratch across KV steps.  Block shapes are MXU-aligned
(bq, bk multiples of 128; d_head padded by the caller if needed).  GQA is
handled in the K/V index maps (kv_head = q_head // group), so grouped K/V
blocks are fetched once per group without materializing a repeat.

Causal and sliding-window (local) masks are applied from global indices.
Validated on CPU via interpret=True against kernels/ref.py; on TPU the same
call lowers to a pipelined VMEM kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, bq: int, bk: int,
                  nk: int, seq_q: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, dh)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, dh)
    v = v_ref[0, 0].astype(jnp.float32)  # (bk, dh)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bq, bk)

    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = cols < seq_k
    if causal:
        valid = valid & (cols <= rows)
    if window > 0:
        valid = valid & (cols > rows - window)
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_ref[...]  # (bq, 1)
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def flash_attention_fwd(
    q, k, v, *, causal: bool = True, window: int = 0,
    bq: int = 128, bk: int = 128, interpret: bool = False,
):
    """q: (B, H, T, dh); k, v: (B, KV, S, dh).  Returns (B, H, T, dh)."""
    B, H, T, dh = q.shape
    KV, S = k.shape[1], k.shape[2]
    assert H % KV == 0, "GQA requires H % KV == 0"
    group = H // KV
    scale = dh**-0.5

    bq = min(bq, T)
    bk = min(bk, S)
    nq = -(-T // bq)
    nk = -(-S // bk)
    if T % bq or S % bk:
        # pad sequence dims to block multiples; masked out via seq_k
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nq * bq - T), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, nk * bk - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, nk * bk - S), (0, 0)))

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk, seq_q=T, seq_k=S,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :T]

"""Grouped-query attention with chunked (flash-style) softmax.

Covers the pool's attention variants: GQA (all), qk-norm (qwen3), local
sliding-window / global mixes (gemma3), MHA (zamba2 shared block, whisper),
bidirectional (whisper encoder) and cross attention (whisper decoder).

The jnp chunked implementation is the reference semantics for the Pallas
flash kernel (kernels/flash_attention.py); `use_pallas=True` swaps it in
(interpret mode on CPU).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import sharding
from repro.models.config import ModelConfig
from repro.models.layers import MODEL, Initializer, apply_rope, rms_norm

NEG_INF = -2.0e38


def init_attention(init: Initializer, cfg: ModelConfig, n_heads=None, n_kv=None):
    H = n_heads or cfg.n_heads
    KV = n_kv or cfg.n_kv_heads
    D, dh = cfg.d_model, cfg.d_head
    m = MODEL if cfg.tensor_parallel else None
    p = {
        "wq": init.normal((D, H * dh), (None, m)),
        "wk": init.normal((D, KV * dh), (None, m)),
        "wv": init.normal((D, KV * dh), (None, m)),
        "wo": init.normal((H * dh, D), (m, None)),
    }
    if cfg.qk_norm:
        p["q_norm"] = init.ones((dh,), (None,), dtype="float32")
        p["k_norm"] = init.ones((dh,), (None,), dtype="float32")
    return p


class KVCache(NamedTuple):
    """Dense per-layer KV cache for decode.

    `index` is PER SEQUENCE (continuous batching: each slot has its own
    length).  Prefill (T > 1) requires all batch entries at equal index
    (the serving engine prefills one slot at a time); decode (T = 1)
    scatters at per-slot positions.
    """

    k: jax.Array  # (B, S, KV, dh)
    v: jax.Array  # (B, S, KV, dh)
    index: jax.Array  # (B,) int32 — next write position (= current length)


def init_kv_cache(batch: int, max_seq: int, n_kv: int, d_head: int, dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, max_seq, n_kv, d_head), dtype),
        v=jnp.zeros((batch, max_seq, n_kv, d_head), dtype),
        index=jnp.zeros((batch,), jnp.int32),
    )


def _split_heads(x, n, dh):
    return x.reshape(*x.shape[:-1], n, dh)


def chunked_attention(
    q, k, v, q_pos, k_valid_len, causal: bool, window: int = 0, chunk: int = 1024
):
    """Online-softmax attention, scanning KV in chunks (flash algorithm).

    q: (B, T, H, dh); k/v: (B, S, KV, dh); q_pos: (B, T) absolute positions.
    k positions are arange(S); entries >= k_valid_len (scalar or per-batch
    (B,)) are masked out.  window > 0 => sliding-window (local) attention.
    Returns (B, T, H, dh) in q.dtype.
    """
    B, T, H, dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = dh**-0.5
    qg = (q * scale).reshape(B, T, KV, G, dh).astype(jnp.float32)

    if T == 1 or S <= chunk:
        # Decode / short-KV: one-shot masked softmax.  No chunk scan means
        # no reshape/dynamic-slice of the (possibly sequence-sharded) KV —
        # GSPMD partitions the contraction and all-reduces the softmax
        # stats instead of rematerializing the cache.  K/V are read in their
        # storage dtype with f32 MXU accumulation (a full-cache f32 cast
        # would triple decode HBM traffic).  See EXPERIMENTS.md §Perf.
        logits = jnp.einsum(
            "btkgd,bskd->btkgs", qg.astype(k.dtype), k,
            preferred_element_type=jnp.float32,
        )
        kpos = jnp.arange(S, dtype=jnp.int32)
        kv_lim = jnp.atleast_1d(jnp.asarray(k_valid_len))[:, None, None]
        valid = kpos[None, None, :] < kv_lim  # (B|1, 1, S)
        if causal:
            valid = valid & (kpos[None, None, :] <= q_pos[:, :, None])
        if window > 0:
            valid = valid & (kpos[None, None, :] > q_pos[:, :, None] - window)
        logits = jnp.where(valid[:, :, None, None, :], logits, NEG_INF)
        m = logits.max(axis=-1, keepdims=True)
        p = jnp.exp(logits - m)
        out = jnp.einsum(
            "btkgs,bskd->btkgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        out = out / jnp.maximum(p.sum(axis=-1), 1e-30)[..., None]
        return out.reshape(B, T, H, dh).astype(q.dtype)

    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, KV, dh).swapaxes(0, 1)
    vc = v.reshape(B, n_chunks, chunk, KV, dh).swapaxes(0, 1)

    def step(carry, inputs):
        m, l, acc = carry
        ci, kb, vb = inputs
        kpos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)  # (C,)
        logits = jnp.einsum(
            "btkgd,bckd->btkgc", qg.astype(kb.dtype), kb,
            preferred_element_type=jnp.float32,
        )  # (B,T,KV,G,C)
        kv_lim = jnp.atleast_1d(jnp.asarray(k_valid_len))[:, None, None]  # (B|1,1,1)
        valid = kpos[None, None, :] < kv_lim  # (B|1,1,C)
        if causal:
            valid = valid & (kpos[None, None, :] <= q_pos[:, :, None])
        if window > 0:
            valid = valid & (kpos[None, None, :] > q_pos[:, :, None] - window)
        logits = jnp.where(valid[:, :, None, None, :], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "btkgc,bckd->btkgd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, T, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, T, KV, G), jnp.float32)
    acc0 = jnp.zeros((B, T, KV, G, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (jnp.arange(n_chunks, dtype=jnp.int32), kc, vc)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, T, H, dh).astype(q.dtype)


def attention(
    x,
    p,
    cfg: ModelConfig,
    kind: str = "global",
    positions=None,
    kv_cache: Optional[KVCache] = None,
    cross_kv=None,
    use_rope: bool = True,
    n_heads=None,
    n_kv=None,
    use_pallas: bool = False,
):
    """Full attention block (projections + attention + output proj).

    Modes:
      * train/prefill (kv_cache None): causal (kind: global/local) or
        bidirectional (kind="bidir"), optionally writing a fresh cache.
      * decode (kv_cache given): x is (B, 1, D), append and attend.
      * cross (cross_kv given): attend over precomputed encoder K/V.
    """
    H = n_heads or cfg.n_heads
    KV = n_kv or cfg.n_kv_heads
    dh = cfg.d_head
    B, T, D = x.shape

    q = _split_heads(x @ p["wq"].astype(x.dtype), H, dh)
    if cross_kv is None:
        k = _split_heads(x @ p["wk"].astype(x.dtype), KV, dh)
        v = _split_heads(x @ p["wv"].astype(x.dtype), KV, dh)
    else:
        k, v = cross_kv

    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        if cross_kv is None:
            k = rms_norm(k, p["k_norm"])

    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    if use_rope and cross_kv is None:
        q = apply_rope(q, positions, cfg.rope_base)
        k = apply_rope(k, positions, cfg.rope_base)

    q = sharding.constrain(q, "batch", None, "model", None)

    new_cache = None
    if kv_cache is not None and cross_kv is None:
        if T == 1:
            # decode: write each sequence's token at its own position.  A
            # one-hot masked select, NOT a batched scatter: GSPMD cannot
            # prove at[arange(B), index] batch-local and emits an all-reduce
            # of the WHOLE cache (found via the whisper decode_32k cell —
            # see EXPERIMENTS.md §Perf).
            pos = jnp.arange(kv_cache.k.shape[1], dtype=jnp.int32)
            hit = (pos[None, :] == kv_cache.index[:, None])[:, :, None, None]
            k_full = jnp.where(hit, k[:, 0][:, None].astype(kv_cache.k.dtype),
                               kv_cache.k)
            v_full = jnp.where(hit, v[:, 0][:, None].astype(kv_cache.v.dtype),
                               kv_cache.v)
        else:
            # prefill: contiguous write (all batch entries at equal index)
            k_full = jax.lax.dynamic_update_slice(
                kv_cache.k, k.astype(kv_cache.k.dtype), (0, kv_cache.index[0], 0, 0)
            )
            v_full = jax.lax.dynamic_update_slice(
                kv_cache.v, v.astype(kv_cache.v.dtype), (0, kv_cache.index[0], 0, 0)
            )
        new_cache = KVCache(k_full, v_full, kv_cache.index + T)
        k, v = k_full, v_full
        k_valid = kv_cache.index + T  # (B,)
        S = k.shape[1]
    else:
        k_valid = jnp.full((B,), k.shape[1], jnp.int32)
        S = k.shape[1]

    causal = kind in ("global", "local") and cross_kv is None
    window = cfg.local_window if kind == "local" else 0

    if use_pallas and kv_cache is None and cross_kv is None:
        from repro.kernels import on_tpu
        from repro.kernels import ops as kops

        out = kops.flash_attention(
            q, k, v, causal=causal, window=window, interpret=not on_tpu()
        )
    else:
        chunk = min(1024, max(128, S)) if S >= 128 else S
        out = chunked_attention(
            q, k, v, positions, k_valid, causal=causal, window=window, chunk=chunk
        )

    out = sharding.constrain(out, "batch", None, "model", None)
    out = out.reshape(B, T, H * dh) @ p["wo"].astype(x.dtype)
    return sharding.constrain(out, "batch", None, None), new_cache

"""Chunked WKV6 linear-attention scan as a Pallas TPU kernel.

The RWKV6 recurrence S' = diag(w_t)·S + k_t⊗v_t is memory-bound when run
step-by-step from HBM.  The TPU adaptation keeps the (dh × dh) state
resident in VMEM scratch while streaming (r,k,v,w) chunks HBM->VMEM:
grid = (B·H, T/chunk) with the chunk axis sequential, inner fori_loop over
the chunk.  This is the optimized counterpart of the lax.scan reference in
repro/models/rwkv.py (_wkv_scan), which is its correctness oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_ref, *,
                chunk: int, dh: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)  # (1, dh) -> broadcast over k-dim
    u_col = u.reshape(dh, 1)

    def step(t, S):
        rt = r_ref[0, t].astype(jnp.float32).reshape(dh, 1)  # (dh,1)
        kt = k_ref[0, t].astype(jnp.float32).reshape(dh, 1)
        vt = v_ref[0, t].astype(jnp.float32).reshape(1, dh)
        wt = w_ref[0, t].astype(jnp.float32).reshape(dh, 1)
        kv = kt * vt  # (dh, dh) outer product
        y = jnp.sum(rt * (S + u_col * kv), axis=0)  # (dh,)
        y_ref[0, t] = y.astype(y_ref.dtype)
        return wt * S + kv

    s_ref[...] = jax.lax.fori_loop(0, chunk, step, s_ref[...])


def wkv6_scan(r, k, v, w, u, *, chunk: int = 128, interpret: bool = False):
    """r,k,v,w: (B, T, H, dh); u: (H, dh).  Returns y: (B, T, H, dh).

    State starts at zero (training/prefill from scratch); T must be a
    multiple of `chunk` (the wrapper in ops.py pads).
    """
    B, T, H, dh = r.shape
    assert T % chunk == 0

    def to_bh(x):  # (B,T,H,dh) -> (B*H, T, dh)
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, dh)

    rb, kb, vb, wb = map(to_bh, (r, k, v, w))
    n_chunks = T // chunk

    kernel = functools.partial(_wkv_kernel, chunk=chunk, dh=dh)
    yb = pl.pallas_call(
        kernel,
        grid=(B * H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, dh), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, dh), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, dh), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, dh), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, dh), lambda bh, ci, H=H: (bh % H, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dh), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, dh), r.dtype),
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(rb, kb, vb, wb, u)
    return yb.reshape(B, H, T, dh).transpose(0, 2, 1, 3)

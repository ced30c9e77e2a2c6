"""Pallas-ref implementations of the indexed-state interface.

:class:`RefState` runs the step code written against
:mod:`repro.indexed_state` inside the kernel bodies, with in-place
loads and stores of the touched slots only; :class:`LaneRefState` runs it
for up to 128 lanes at once, one per vector lane (its own docstring gives
that layout).

Kernel layout (:class:`RefState`): a table is either an SMEM ref (scalar
reads and writes only; 1-D, or ``(1, n)`` for a per-lane block) or a VMEM
ref laid out as ``(rows, 128)`` — element ``i`` lives at row ``i >> 7``,
lane ``i & 127``.  A scalar read loads one row and reduces the selected
lane; a write stores that row back with one lane replaced.  Vector-group
operations walk the group ``block_rows`` rows at a time over the first
``n_blocks`` blocks, so their cost scales with the live part of a table
and not with its allocation.  Elements past the live blocks must be
invisible to every ``fn`` mask — the callers' invariant (no slot at or
beyond the capacity is ever occupied).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from repro.indexed_state import INT32_MAX, i32

LANES = 128


def vmem_rows(n: int, block_rows: int = 8) -> int:
    """Rows of a ``(rows, 128)`` VMEM table holding ``n`` elements, rounded
    up to a whole number of ``block_rows`` blocks (the (8, 128) tile)."""
    rows = max(1, -(-int(n) // LANES))
    return -(-rows // block_rows) * block_rows


def vmem_block_rows(n: int, max_rows: int = 32) -> int:
    """Block height for a vector group of ``n`` elements: whole tiles, at
    most ``max_rows`` (the walk's unroll), never past the table."""
    rows = -(-max(1, -(-int(n) // LANES)) // 8) * 8
    return min(max_rows, rows)


class _RefView:
    """Elementwise view of one ``block_rows``-row block of the group."""

    def __init__(self, refs: Dict[str, Any], start: Any, rows: int):
        self._refs = refs
        self._start = start
        self._rows = rows

    def __getattr__(self, name: str) -> jax.Array:
        if name == "slot":
            shape = (self._rows, LANES)
            row = lax.broadcasted_iota(jnp.int32, shape, 0)
            lane = lax.broadcasted_iota(jnp.int32, shape, 1)
            return (self._start + row) * LANES + lane
        return self._refs[name][pl.ds(self._start, self._rows), :]


class RefState:
    """In-place implementation over Pallas refs (the kernel bodies).

    ``smem`` tables take scalar access only; every other ref is a
    ``(rows, 128)`` VMEM table.  The vector-group operations walk the
    tables their ``fn`` reads (all of one shape) in ``block_rows``-row
    blocks over the first ``n_blocks`` blocks (a Python int or a traced
    scalar).
    """

    def __init__(self, refs: Dict[str, Any], *, smem: Iterable[str] = (),
                 block_rows: int = 8, n_blocks: Any = 1):
        self.refs = refs
        self.smem = frozenset(smem)
        self.block_rows = block_rows
        self.n_blocks = n_blocks

    # -- scalar access -----------------------------------------------------

    def _smem_index(self, name: str, i: Any) -> tuple:
        return (0, i) if len(self.refs[name].shape) == 2 else (i,)

    def _row(self, i: Any):
        i = i32(i)
        lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        return pl.ds(i >> 7, 1), lane == (i & (LANES - 1))

    def get(self, name: str, i: Any) -> jax.Array:
        ref = self.refs[name]
        if name in self.smem:
            return ref[self._smem_index(name, i)]
        rows, sel = self._row(i)
        zero = jnp.zeros((), ref.dtype)
        return jnp.sum(jnp.where(sel, ref[rows, :], zero))

    def set(self, name: str, i: Any, v: Any) -> "RefState":
        ref = self.refs[name]
        v = jnp.asarray(v, ref.dtype)
        if name in self.smem:
            ref[self._smem_index(name, i)] = v
            return self
        rows, sel = self._row(i)
        ref[rows, :] = jnp.where(sel, v, ref[rows, :])
        return self

    def set_if(self, name: str, i: Any, c: Any, v: Any) -> "RefState":
        ref = self.refs[name]
        v = jnp.asarray(v, ref.dtype)
        if name in self.smem:
            idx = self._smem_index(name, i)
            ref[idx] = jnp.where(c, v, ref[idx])
            return self
        rows, sel = self._row(i)
        ref[rows, :] = jnp.where(sel & c, v, ref[rows, :])
        return self

    # -- vector group ------------------------------------------------------

    def _walk(self, body: Callable, init: Any) -> Any:
        br = self.block_rows

        def step(b, carry):
            start = pl.multiple_of(b * br, br)
            return body(start, _RefView(self.refs, start, br), carry)

        return lax.fori_loop(0, self.n_blocks, step, init)

    def argmin(self, fn: Callable) -> Tuple[jax.Array, jax.Array]:
        shape = (self.block_rows, LANES)

        def body(start, view, carry):
            best, at = carry
            mask, key = fn(view)
            vals = jnp.where(mask, i32(key), INT32_MAX)
            # strict: an earlier block keeps ties, so each position holds
            # the first index reaching its minimum
            better = vals < best
            return (jnp.where(better, vals, best),
                    jnp.where(better, view.slot, at))

        best, at = self._walk(body, (jnp.full(shape, INT32_MAX, jnp.int32),
                                     jnp.full(shape, INT32_MAX, jnp.int32)))
        m = jnp.min(best)
        idx = jnp.min(jnp.where(best == m, at, INT32_MAX))
        return jnp.where(m == INT32_MAX, 0, idx), m

    def update(self, name: str, fn: Callable) -> Tuple["RefState", Any]:
        ref = self.refs[name]
        br = self.block_rows

        def body(start, view, count):
            pred, value = fn(view)
            rows = pl.ds(start, br)
            ref[rows, :] = jnp.where(pred, jnp.asarray(value, ref.dtype),
                                     ref[rows, :])
            return count + i32(pred)

        count = self._walk(body, jnp.zeros((br, LANES), jnp.int32))
        return self, jnp.sum(count)

    def vmath(self, fn: Callable, *xs: Any) -> jax.Array:
        # the scalar unit has no transcendentals: evaluate on the vector
        # unit with every lane equal, then read one lane back
        y = fn(*(jnp.full((1, LANES), x) for x in xs))
        return jnp.min(y)

    def count_less(self, name: str, x: Any) -> jax.Array:
        n = self.refs[name].shape[-1]
        return lax.fori_loop(
            0, n, lambda i, acc: acc + i32(self.get(name, i) < x),
            jnp.zeros((), jnp.int32))

    # -- control flow --------------------------------------------------------

    def cond(self, pred: Any, t: Callable, f: Callable, *ops: Any):
        out = lax.cond(pred, lambda *o: t(self, *o)[1],
                       lambda *o: f(self, *o)[1], *ops)
        return self, out

    def while_loop(self, c: Callable, b: Callable, init: Any):
        out = lax.while_loop(lambda x: c(self, x), lambda x: b(self, x)[1],
                             init)
        return self, out


def _static(i: Any) -> bool:
    return isinstance(i, (int, np.integer))


class _LaneView:
    """Elementwise view of the vector group: whole ``(rows, 128)`` tables,
    ``slot`` the row (element) index."""

    def __init__(self, refs: Dict[str, Any], shape: Tuple[int, int]):
        self._refs = refs
        self._shape = shape

    def __getattr__(self, name: str) -> jax.Array:
        if name == "slot":
            return lax.broadcasted_iota(jnp.int32, self._shape, 0)
        return self._refs[name][...]


class LaneRefState:
    """Lane-packed implementation over Pallas refs: one step of up to 128
    lanes at once, one lane per vector lane.

    Every table is a VMEM ref ``(rows, 128)``: element ``i`` of lane ``l``
    lives at ``[i, l]``.  A per-lane scalar is a ``(1, 128)`` value; an index
    is either such a value (a one-hot row select) or a static int (one
    row).  ``sizes`` gives a table's element count where its rows are
    padded past it (``count_less`` ignores the padding rows); ``vec`` names
    a member of the vector group.

    Control flow follows ``vmap``'s batching rule, so the step code agrees
    with its vmapped :class:`~repro.indexed_state.ArrayState` twin by
    construction: the state carries ``mask``, the active lanes; every
    store and every carry is selected by it; ``cond`` runs both branches,
    under ``mask & pred`` and ``mask & ~pred``; ``while_loop`` iterates
    while any lane is active, with ``mask = mask & c``, and records its
    trip count in the returned state's ``trips``.
    """

    def __init__(self, refs: Dict[str, Any], *, vec: str, mask: Any,
                 sizes: Dict[str, int] | None = None, trips: Any = None):
        self.refs = refs
        self.vec = vec
        self.mask = mask
        self.sizes = sizes or {}
        self.trips = trips

    def _with(self, mask: Any, trips: Any = None) -> "LaneRefState":
        return LaneRefState(self.refs, vec=self.vec, mask=mask,
                            sizes=self.sizes, trips=trips)

    @staticmethod
    def _rows(ref: Any) -> jax.Array:
        return lax.broadcasted_iota(jnp.int32, ref.shape, 0)

    # -- element access ----------------------------------------------------

    def get(self, name: str, i: Any) -> jax.Array:
        ref = self.refs[name]
        if _static(i):
            return ref[pl.ds(int(i), 1), :]
        sel = self._rows(ref) == i
        return jnp.sum(jnp.where(sel, ref[...], jnp.zeros((), ref.dtype)),
                       axis=0, keepdims=True)

    def set(self, name: str, i: Any, v: Any) -> "LaneRefState":
        return self.set_if(name, i, True, v)

    def set_if(self, name: str, i: Any, c: Any, v: Any) -> "LaneRefState":
        ref = self.refs[name]
        v = jnp.asarray(v, ref.dtype)
        on = self.mask & c
        if _static(i):
            rows = pl.ds(int(i), 1)
            ref[rows, :] = jnp.where(on, v, ref[rows, :])
        else:
            ref[...] = jnp.where((self._rows(ref) == i) & on, v, ref[...])
        return self

    # -- vector group ------------------------------------------------------

    def _view(self) -> _LaneView:
        return _LaneView(self.refs, self.refs[self.vec].shape)

    def argmin(self, fn: Callable) -> Tuple[jax.Array, jax.Array]:
        view = self._view()
        mask, key = fn(view)
        vals = jnp.where(mask, i32(key), INT32_MAX)
        m = jnp.min(vals, axis=0, keepdims=True)
        idx = jnp.min(jnp.where(vals == m, view.slot, INT32_MAX), axis=0,
                      keepdims=True)
        return jnp.where(m == INT32_MAX, 0, idx), m

    def update(self, name: str, fn: Callable) -> Tuple["LaneRefState", Any]:
        ref = self.refs[name]
        pred, value = fn(self._view())
        pred = jnp.broadcast_to(pred, ref.shape)
        ref[...] = jnp.where(pred & self.mask,
                             jnp.asarray(value, ref.dtype), ref[...])
        return self, jnp.sum(i32(pred), axis=0, keepdims=True)

    def count_less(self, name: str, x: Any) -> jax.Array:
        ref = self.refs[name]
        rows = self._rows(ref)
        live = rows < self.sizes.get(name, ref.shape[0])
        return jnp.sum(i32(live & (ref[...] < x)), axis=0, keepdims=True)

    def vmath(self, fn: Callable, *xs: Any) -> jax.Array:
        return fn(*xs)

    # -- control flow --------------------------------------------------------

    def cond(self, pred: Any, t: Callable, f: Callable, *ops: Any):
        pred = jnp.asarray(pred)
        _, out_t = t(self._with(self.mask & pred), *ops)
        _, out_f = f(self._with(self.mask & ~pred), *ops)
        out = jax.tree_util.tree_map(lambda a, b: jnp.where(pred, a, b),
                                     out_t, out_f)
        return self, out

    def while_loop(self, c: Callable, b: Callable, init: Any):
        init = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(jnp.asarray(x), (1, LANES)), init)

        def live(mask, carry):
            # an int32 carry: Mosaic keeps no boolean vector across a loop
            return i32(mask & c(self._with(mask), carry))

        def cond(loop):
            return jnp.max(loop[0]) > 0

        def body(loop):
            on, trips, carry = loop
            on = on != 0
            _, new = b(self._with(on), carry)
            carry = jax.tree_util.tree_map(
                lambda n, o: jnp.where(on, n, o), new, carry)
            return live(on, carry), trips + 1, carry

        _, trips, out = lax.while_loop(
            cond, body, (live(self.mask, init), jnp.zeros((), jnp.int32),
                         init))
        return self._with(self.mask, trips), out

"""Indexed state: the interface the engines' per-step code is written against.

The replay policies (:mod:`repro.cache.flat`) and the event loop
(:mod:`repro.kernels.event_sim`) each write their step ONCE against this
small interface, and run on two implementations of it:

:class:`ArrayState`  named jnp arrays, functional updates (``.at[].set``) —
                     the vmapped ``lax.scan`` / ``while_loop`` twins that
                     run off-TPU;
:class:`~repro.kernels.state.RefState`
                     named Pallas refs, in-place loads and stores of the
                     touched slots only — the kernel bodies.

Because the step code is shared, the twin and the kernel agree by
construction.  The interface is:

``get(name, i)`` / ``set(name, i, v)`` / ``set_if(name, i, c, v)``
    one element of a 1-D table (``set_if`` keeps the old value when ``c``
    is False).  Every method that changes state returns the state; step
    code always rebinds (``st = st.set(...)``), which is a no-op for refs.
``argmin(fn)``
    ``fn(view) -> (mask, key)`` over the *vector group* (tables of one
    common length whose fields ``view.<name>`` the step reads elementwise,
    plus ``view.slot``, the element index).  Returns ``(idx, min_key)``
    with ``jnp.argmin`` semantics: the first index of the least masked
    key, and ``(0, INT32_MAX)`` when nothing is masked.  A key equal to
    ``INT32_MAX`` counts as unmasked.
``update(name, fn)``
    ``fn(view) -> (pred, value)``: ``name[pred] = value`` over the vector
    group; returns ``(state, count of pred)``.
``count_less(name, x)``
    how many elements of a table are ``< x`` (a searchsorted-left).
``vmath(fn, *xs)``
    ``fn`` applied to scalars; the kernel evaluates it on the vector unit,
    which has the transcendentals (``log``, ``pow``) the scalar unit lacks.
``cond(pred, t, f, *ops)`` / ``while_loop(c, b, init)``
    control flow whose branches and bodies take and return the state:
    ``t(st, *ops) -> (st, out)``, ``c(st, carry) -> bool``,
    ``b(st, carry) -> (st, carry)``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# numpy scalars, not jnp: kernel bodies close over these, and a jnp scalar
# would be a captured device constant (pallas_call rejects those)
INT32_MAX = np.int32(2**31 - 1)


def i32(x: Any) -> jax.Array:
    return jnp.asarray(x).astype(jnp.int32)


class _ArrayView:
    """Elementwise view of the vector group: whole arrays."""

    def __init__(self, tabs: Dict[str, jax.Array], length: int):
        self._tabs = tabs
        self._length = length

    def __getattr__(self, name: str) -> jax.Array:
        if name == "slot":
            return jnp.arange(self._length, dtype=jnp.int32)
        return self._tabs[name]


@jax.tree_util.register_pytree_node_class
class ArrayState:
    """Functional implementation over a dict of 1-D jnp arrays (the twins).

    ``vec`` names one member of the vector group; its length is the
    group's.  A pytree (``vec`` is static), so it rides through
    ``lax.cond``/``while_loop``/``scan`` carries.
    """

    def __init__(self, tabs: Dict[str, jax.Array], vec: str):
        self.tabs = tabs
        self.vec = vec

    def tree_flatten(self):
        return (self.tabs,), self.vec

    @classmethod
    def tree_unflatten(cls, vec: str, children: Any) -> "ArrayState":
        return cls(children[0], vec)

    def _replace(self, tabs: Dict[str, jax.Array]) -> "ArrayState":
        return ArrayState(tabs, self.vec)

    def get(self, name: str, i: Any) -> jax.Array:
        return self.tabs[name][i]

    def set(self, name: str, i: Any, v: Any) -> "ArrayState":
        tab = self.tabs[name]
        return self._replace(
            tabs={**self.tabs, name: tab.at[i].set(jnp.asarray(v, tab.dtype))})

    def set_if(self, name: str, i: Any, c: Any, v: Any) -> "ArrayState":
        tab = self.tabs[name]
        new = jnp.where(c, jnp.asarray(v, tab.dtype), tab[i])
        return self._replace(tabs={**self.tabs, name: tab.at[i].set(new)})

    def _view(self) -> _ArrayView:
        return _ArrayView(self.tabs, self.tabs[self.vec].shape[0])

    def argmin(self, fn: Callable) -> Tuple[jax.Array, jax.Array]:
        mask, key = fn(self._view())
        vals = jnp.where(mask, key, INT32_MAX)
        return jnp.argmin(vals).astype(jnp.int32), jnp.min(vals)

    def update(self, name: str, fn: Callable) -> Tuple["ArrayState", Any]:
        pred, value = fn(self._view())
        tab = self.tabs[name]
        new = jnp.where(pred, jnp.asarray(value, tab.dtype), tab)
        st = self._replace(tabs={**self.tabs, name: new})
        return st, jnp.sum(i32(pred))

    def count_less(self, name: str, x: Any) -> jax.Array:
        return jnp.sum(i32(self.tabs[name] < x))

    def vmath(self, fn: Callable, *xs: Any) -> jax.Array:
        return fn(*xs)

    def cond(self, pred: Any, t: Callable, f: Callable, *ops: Any):
        return lax.cond(pred, t, f, self, *ops)

    def while_loop(self, c: Callable, b: Callable, init: Any):
        return lax.while_loop(lambda sc: c(*sc), lambda sc: b(*sc),
                              (self, init))

"""LaneRefState (lane-packed, inside a Pallas kernel) == ArrayState vmapped.

Each case is a step written once against the indexed-state interface and
run two ways over the same random tables: by the vmapped twin, one
ArrayState per lane, and by one kernel cell under the Pallas interpreter,
where every table is ``(rows, 128)`` with lane ``l`` in column ``l``.
Indices and operands are per lane, so the one-hot selects, the row-walking
reductions and the masked control flow are checked lane by lane; lanes
past ``LIVE`` start inactive and must come out untouched.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.indexed_state import INT32_MAX, ArrayState, i32
from repro.kernels.state import LANES, LaneRefState

ROWS = 24        # the vector group: tables "a" and "b"
CUM = 5          # "f": a float table of 5 elements, padded to 8 rows
LIVE = 100       # active lanes; the rest start masked off


def _tables(seed):
    rng = np.random.default_rng(seed)
    return {
        "a": rng.integers(0, 6, (ROWS, LANES)).astype(np.int32),  # ties
        "b": (rng.random((ROWS, LANES)) < 0.3).astype(np.int32),
        "f": np.concatenate([
            np.sort(rng.random((CUM, LANES)), axis=0),
            np.full((8 - CUM, LANES), -1.0)]).astype(np.float32),
    }


def _get(st, i, x):
    return st, (st.get("a", i), st.get("f", i % CUM), st.get("b", 3))


def _set(st, i, x):
    st = st.set("a", i, x)
    return st.set("b", 2, x), ()


def _set_if(st, i, x):
    return st.set_if("a", i, x > 2, x), ()


def _argmin_ties(st, i, x):
    return st, st.argmin(lambda v: (v.b != 0, v.a))


def _argmin_none(st, i, x):
    return st, st.argmin(lambda v: (v.slot < 0, v.a))


def _argmin_slot(st, i, x):
    # masked by a per-lane operand, keyed by it: ties broken by the slot
    return st, st.argmin(lambda v: ((v.slot != i) & (v.a >= 1), v.a + x))


def _update(st, i, x):
    st, n = st.update("a", lambda v: (v.a > x, v.a - x))
    return st, (n,)


def _count_less(st, i, x):
    return st, (st.count_less("f", x.astype(jnp.float32) / 5.0),)


def _cond(st, i, x):
    def yes(st, y):
        return st.set("a", i, y), y + 1

    def no(st, y):
        return st.set("b", i, y), y * 3

    return st.cond(x > 2, yes, no, x)


def _while(st, i, x):
    # lanes stop after their own trip counts (x + 1 .. 6), a cond inside
    def body(st, carry):
        k, acc = carry
        st = st.set("a", k, acc)
        st, acc = st.cond(st.get("b", k) != 0, lambda st, a: (st, a + 7),
                          lambda st, a: (st, a * 2), acc)
        return st, (k + 1, acc)

    return st.while_loop(lambda st, c: c[0] <= x, body,
                         (np.int32(0), np.int32(1)))


CASES = {"get": _get, "set": _set, "set_if": _set_if,
         "argmin_ties": _argmin_ties, "argmin_none": _argmin_none,
         "argmin_slot": _argmin_slot, "update": _update,
         "count_less": _count_less, "cond": _cond, "while_loop": _while}


def _twin(op, tabs, i, x):
    """The vmapped ArrayState run: tables and results per lane."""
    def lane(t, i, x):
        st, out = op(ArrayState({**t, "f": t["f"][:CUM]}, "a"), i, x)
        return {k: st.tabs[k] for k in ("a", "b")}, out

    return jax.vmap(lane, in_axes=(1, 0, 0), out_axes=(1, 0))(
        {k: jnp.asarray(v) for k, v in tabs.items()},
        jnp.asarray(i), jnp.asarray(x))


def _kernel(op, tabs, i, x, outs_like):
    """One kernel cell on LaneRefState, lanes past LIVE inactive."""
    names = list(tabs)
    leaves = jax.tree_util.tree_leaves(outs_like)

    def kernel(*refs):
        n = len(names)
        ins, tab_out, res = refs[:n + 2], refs[n + 2:2 * n + 2], refs[2 * n + 2:]
        refs_by_name = dict(zip(names, tab_out))
        for name, src in zip(names, ins):
            refs_by_name[name][...] = src[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        st = LaneRefState(refs_by_name, vec="a", mask=lane < LIVE,
                          sizes={"f": CUM})
        _, out = op(st, ins[n][...], ins[n + 1][...])
        for ref, v in zip(res, jax.tree_util.tree_leaves(out)):
            ref[...] = jnp.broadcast_to(v, (1, LANES)).astype(ref.dtype)

    out_shape = ([jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for v in tabs.values()]
                 + [jax.ShapeDtypeStruct((1, LANES), v.dtype) for v in leaves])
    got = pl.pallas_call(kernel, out_shape=out_shape, interpret=True)(
        *(jnp.asarray(v) for v in tabs.values()),
        jnp.asarray(i)[None], jnp.asarray(x)[None])
    return dict(zip(names, got[:len(names)])), got[len(names):]


@pytest.mark.parametrize("case", list(CASES))
def test_lane_state_matches_array_state(case):
    op = CASES[case]
    tabs = _tables(seed=sorted(CASES).index(case))
    rng = np.random.default_rng(99)
    i = rng.integers(0, ROWS, LANES).astype(np.int32)
    x = rng.integers(0, 6, LANES).astype(np.int32)
    want_tabs, want_out = _twin(op, tabs, i, x)
    got_tabs, got_out = _kernel(op, tabs, i, x, want_out)
    live = slice(0, LIVE)
    for name in ("a", "b"):
        np.testing.assert_array_equal(np.asarray(got_tabs[name])[:, live],
                                      np.asarray(want_tabs[name])[:, live],
                                      err_msg=name)
        # masked-off lanes are never written
        np.testing.assert_array_equal(np.asarray(got_tabs[name])[:, LIVE:],
                                      tabs[name][:, LIVE:], err_msg=name)
    want = jax.tree_util.tree_leaves(want_out)
    assert len(got_out) == len(want)
    for g, w in zip(got_out, want):
        np.testing.assert_array_equal(np.asarray(g)[0, live],
                                      np.asarray(w)[live])
    if case == "argmin_none":
        idx, key = (np.asarray(g)[0, live] for g in got_out)
        assert (idx == 0).all() and (key == INT32_MAX).all()
    if case == "argmin_ties":
        # ties exist, so the first-index rule is what is checked
        a, b = tabs["a"][:, live], tabs["b"][:, live] != 0
        keys = np.where(b, a, INT32_MAX)
        assert ((keys == keys.min(axis=0)).sum(axis=0) > 1).any()
    if case == "while_loop":
        assert len(set(x[live].tolist())) > 1  # lanes stop apart


def test_lane_state_while_loop_counts_its_trips():
    """``trips`` is the loop's trip count: the longest lane's."""
    tabs = _tables(seed=0)
    x = np.arange(LANES, dtype=np.int32) % 7

    def op(st, i, x):
        st, out = st.while_loop(lambda st, c: c < x, lambda st, c: (st, c + 1),
                                np.int32(0))
        return st, (out, i32(st.trips))

    _, (out, trips) = _kernel(op, tabs, np.zeros(LANES, np.int32), x,
                              (jnp.zeros(LANES, jnp.int32),) * 2)
    np.testing.assert_array_equal(np.asarray(out)[0, :LIVE], x[:LIVE])
    assert int(np.asarray(trips)[0, 0]) == int(x[:LIVE].max())

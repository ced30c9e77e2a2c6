"""Differential tests for the pallas replay & event-sim backend.

Three executables share the per-policy step functions — the pallas kernel
body (``interpret=True``, the CI fallback that runs on CPU), the compiled
vmapped scan twin (``interpret=None`` off-TPU), and the dlist scan engine
— and must be *bit-identical* on every policy: hits, evicted keys, op
vectors, and the fused delayed-hit classification, including padded
states (pad_to > capacity) and capacities that are not a multiple of any
tile.  The py_ref oracle pins the whole stack to the pure-Python ground
truth, and the harness must report identical measurements whichever
backend it is pointed at.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.cache import classify_inflight, classify_inflight_py
from repro.cache.py_ref import PY_POLICIES
from repro.cache.replay import replay_grid
from repro.core import lru_network
from repro.core.harness import (
    coin_stream,
    measure_cache,
    run_cache_trace,
    sweep_cache_sizes,
    zipf_trace,
)
from repro.core.simulator import simulate_network
from repro.kernels import ops, ref
from repro.kernels.event_sim import simulate_grid_pallas
from repro.kernels.replay import replay_grid_pallas, unpack_grid_ops

KEY_SPACE = 24

JAX_PARAMS = {
    "lru": {},
    "fifo": {},
    "prob_lru": {"q": 0.5},
    "clock": {"max_scan": 3},
    "slru": {"protected_frac": 0.5},
    "s3fifo": {"small_frac": 0.25, "max_scan": 3},
    "sieve": {},
}
PY_PARAMS = {**JAX_PARAMS, "s3fifo": {"small_frac": 0.25}}


def _trace(n=1500, seed=0):
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, KEY_SPACE + 1)
    probs = (1.0 / ranks**0.99) / np.sum(1.0 / ranks**0.99)
    keys = rng.choice(KEY_SPACE, size=n, p=probs)
    us = rng.random(n, dtype=np.float32)
    return keys, us


def _oracle(policy, capacity, keys, us):
    py = PY_POLICIES[policy](capacity, **PY_PARAMS[policy])
    hits, evicted, ops4 = [], [], []
    for k, u in zip(keys, us):
        a = py.access(int(k), float(u))
        hits.append(a.hit)
        evicted.append(a.evicted_key)
        ops4.append(a.ops)
    return (np.asarray(hits), np.asarray(evicted, np.int64),
            np.asarray(ops4, np.int64))


@pytest.mark.parametrize("policy", sorted(PY_POLICIES))
@pytest.mark.parametrize("capacity,pad_to", [(7, 16), (8, 8)])
def test_twin_matches_scan_and_py_ref(policy, capacity, pad_to):
    """The compiled twin == dlist scan engine == py_ref oracle."""
    keys, us = _trace()
    res = replay_grid_pallas(policy, keys, us, [capacity],
                             key_space=KEY_SPACE, pad_to=pad_to,
                             **JAX_PARAMS[policy])
    hits, evicted, ops4 = _oracle(policy, capacity, keys, us)
    np.testing.assert_array_equal(np.asarray(res.hits)[0, 0], hits,
                                  err_msg=f"{policy} hits")
    np.testing.assert_array_equal(np.asarray(res.evicted)[0, 0], evicted,
                                  err_msg=f"{policy} evicted")
    np.testing.assert_array_equal(unpack_grid_ops(res)[0, 0], ops4,
                                  err_msg=f"{policy} ops")
    assert res.cls is None  # no window requested

    scan = replay_grid(policy, keys, us, [capacity], key_space=KEY_SPACE,
                       pad_to=pad_to, **JAX_PARAMS[policy])
    np.testing.assert_array_equal(np.asarray(res.hits), scan.hits)
    np.testing.assert_array_equal(unpack_grid_ops(res), scan.ops)


@pytest.mark.parametrize("policy", sorted(PY_POLICIES))
def test_kernel_interpreter_bit_identical(policy):
    """interpret=True runs the actual kernel body on CPU and must equal
    the twin bit-for-bit — the CI fallback contract, with pad > capacity
    and a window so the fused classifier path is exercised too."""
    keys, us = _trace(400, seed=1)
    kw = dict(key_space=KEY_SPACE, pad_to=16, window=8,
              **JAX_PARAMS[policy])
    twin = replay_grid_pallas(policy, keys, us, [7, 11], **kw)
    kern = replay_grid_pallas(policy, keys, us, [7, 11], interpret=True,
                              **kw)
    for field in ("hits", "evicted", "ops", "cls"):
        np.testing.assert_array_equal(
            np.asarray(getattr(kern, field)),
            np.asarray(getattr(twin, field)),
            err_msg=f"{policy} {field}")


@pytest.mark.parametrize("policy", sorted(PY_POLICIES))
def test_kernel_interpreter_multi_block_evictions(policy):
    """Evictions whose victim searches walk several ``BLOCK_ROWS``-row
    blocks, with capacity below the pad: the kernel body == the twin ==
    py_ref.  The first lane fills all three allocated blocks; the second
    re-initialises only the two its capacity reaches, so the first lane's
    third block is still in scratch and must stay invisible."""
    from repro.kernels.replay import BLOCK_ROWS
    from repro.kernels.state import LANES

    pad = 3 * BLOCK_ROWS * LANES
    caps = [9000, 5000]
    rng = np.random.default_rng(5)
    # distinct keys first, so both capacities fill and then evict
    keys = np.concatenate([rng.permutation(pad)[:9500],
                           rng.integers(0, pad, 2500)])
    us = rng.random(keys.size, dtype=np.float32)
    kw = dict(key_space=pad, pad_to=pad, window=16, **JAX_PARAMS[policy])
    twin = replay_grid_pallas(policy, keys, us, caps, **kw)
    kern = replay_grid_pallas(policy, keys, us, caps, interpret=True, **kw)
    for field in ("hits", "evicted", "ops", "cls"):
        np.testing.assert_array_equal(
            np.asarray(getattr(kern, field)),
            np.asarray(getattr(twin, field)),
            err_msg=f"{policy} {field}")
    evicted = np.asarray(kern.evicted)
    assert np.all((evicted >= 0).sum(axis=-1) > 1000)
    for c, cap in enumerate(caps):
        hits, ev, ops4 = _oracle(policy, cap, keys, us)
        np.testing.assert_array_equal(np.asarray(kern.hits)[c, 0], hits)
        np.testing.assert_array_equal(evicted[c, 0], ev)
        np.testing.assert_array_equal(unpack_grid_ops(kern)[c, 0], ops4)


def test_non_tile_multiple_capacity():
    """C=700-class shapes: capacity not a multiple of any tile/pad size,
    pad rounding above it, seeds > 1."""
    rng = np.random.default_rng(2)
    S, T = 2, 500
    keys = rng.integers(0, KEY_SPACE, size=(S, T))
    us = rng.random((S, T), dtype=np.float32)
    caps = [5, 13]
    kw = dict(key_space=KEY_SPACE, pad_to=32, max_scan=3)
    twin = replay_grid_pallas("clock", keys, us, caps, **kw)
    kern = replay_grid_pallas("clock", keys, us, caps, interpret=True, **kw)
    assert twin.hits.shape == (len(caps), S, T)
    np.testing.assert_array_equal(np.asarray(kern.hits),
                                  np.asarray(twin.hits))
    scan = replay_grid("clock", keys, us, caps, key_space=KEY_SPACE,
                       pad_to=32, max_scan=3)
    np.testing.assert_array_equal(np.asarray(twin.hits), scan.hits)
    np.testing.assert_array_equal(unpack_grid_ops(twin), scan.ops)


def test_lru_batch_update_non_tile_multiple():
    """The demo kernel handles n not a multiple of the tile (700/512)."""
    rng = np.random.default_rng(3)
    ts = jnp.asarray(rng.integers(0, 10_000, 700, dtype=np.int32))
    acc = jnp.asarray(rng.choice(700, 96, replace=False).astype(np.int32))
    new_ts, victim = ops.lru_batch_update(ts, acc, jnp.int32(99_999),
                                          tile=512, interpret=True)
    ref_ts, ref_victim = ref.lru_batch_update_ref(ts, acc, jnp.int32(99_999))
    np.testing.assert_array_equal(np.asarray(new_ts), np.asarray(ref_ts))
    assert int(victim) == int(ref_victim)


def test_fused_classification_matches_classifier():
    """The in-kernel expiry table == classify_inflight == the py oracle,
    with retry stretching (fail_prob > 0) and per-request windows."""
    keys, us = _trace(1200, seed=4)
    per_req = (np.arange(1200) % 7 + 2).astype(np.int32)
    for window in (9, per_req):
        res = replay_grid_pallas("lru", keys, us, [6, 10],
                                 key_space=KEY_SPACE, window=window,
                                 fail_prob=0.3, fail_seed=5)
        cls_ref = classify_inflight(keys, np.asarray(res.hits)[:, 0],
                                    window, key_space=KEY_SPACE,
                                    fail_prob=0.3, fail_seed=5)
        np.testing.assert_array_equal(np.asarray(res.cls)[:, 0], cls_ref)
        cls_py = classify_inflight_py(keys, np.asarray(res.hits)[0, 0],
                                      window, fail_prob=0.3, fail_seed=5)
        np.testing.assert_array_equal(np.asarray(res.cls)[0, 0], cls_py)


def test_device_resident_classification():
    """classify_inflight accepts device hits without a host round-trip:
    returns a jax.Array, equal to the host path, and insists on an
    explicit key_space (inference would sync the device)."""
    keys, us = _trace(800, seed=6)
    res = replay_grid_pallas("lru", keys, us, [8], key_space=KEY_SPACE)
    cls_dev = classify_inflight(keys, res.hits[:, 0], 6,
                                key_space=KEY_SPACE)
    assert isinstance(cls_dev, jax.Array)
    cls_host = classify_inflight(keys, np.asarray(res.hits)[:, 0], 6,
                                 key_space=KEY_SPACE)
    np.testing.assert_array_equal(np.asarray(cls_dev), cls_host)
    with pytest.raises(ValueError, match="key_space"):
        classify_inflight(keys, res.hits[:, 0], 6)


def _engine_outputs(monkeypatch, name):
    """Record what ``event_sim.<name>`` returns while it stays in use."""
    from repro.kernels import event_sim

    outs = []
    real = getattr(event_sim, name)

    def keep(*a, **kw):
        outs.append(real(*a, **kw))
        return outs[-1]

    monkeypatch.setattr(event_sim, name, keep)
    return outs


@pytest.mark.parametrize("net_kw,p,seeds", [
    (dict(disk_us=100.0), [0.5, 0.9], (0,)),
    (dict(disk_us=100.0), [0.9], (3,)),                      # one lane
    (dict(disk_us=100.0), [0.6, 0.8], (0, 1)),               # 2 p x 2 seeds
    (dict(disk_us=100.0), [0.5, 0.99], (2,)),                # lanes end apart
    (dict(disk_us=100.0), np.linspace(0.5, 0.99, 10), range(13)),  # 2 blocks
    (dict(disk_us=20.0, mpl=12, disk_servers=2), [0.3, 0.7], (0, 1)),
], ids=["2p", "1lane", "2p2s", "far_apart", "130lanes", "disk_servers"])
def test_event_sim_kernel_matches_twin(monkeypatch, net_kw, p, seeds):
    """The event-sim kernel body (interpreter) == its compiled twin, lane
    for lane: throughput, completions, events and measured time; the
    lane-packed loop's trip count per 128-lane block is its longest
    lane's event count."""
    net = lru_network(**net_kw)
    seeds = tuple(seeds)
    twin_out = _engine_outputs(monkeypatch, "_twin_grid")
    kern_out = _engine_outputs(monkeypatch, "pallas_grid")
    twin = simulate_grid_pallas(net, p, n_requests=300, seeds=seeds)
    kern = simulate_grid_pallas(net, p, n_requests=300, seeds=seeds,
                                interpret=True)
    np.testing.assert_array_equal(twin.throughput, kern.throughput)
    np.testing.assert_array_equal(twin.p_hit, kern.p_hit)
    (t_out,), (k_out,) = twin_out, kern_out
    lanes = len(p) * len(seeds)
    for name, a, b in zip(("x", "completed", "events", "t_measured_us"),
                          t_out[:4], k_out[:4]):
        assert a.shape == b.shape == (lanes,), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    events = np.asarray(k_out[2])
    steps = np.asarray(k_out[5])
    assert steps.shape == (-(-lanes // 128),)
    for blk, n in enumerate(steps):
        assert n == events[blk * 128:(blk + 1) * 128].max()


def test_event_sim_traced_kernel_matches_twin():
    """With tracing on, the kernel's record rings == the twin's."""
    net = lru_network(disk_us=100.0)
    kw = dict(n_requests=300, seeds=(0, 1), trace=16)
    twin = simulate_grid_pallas(net, [0.5, 0.9], **kw)
    kern = simulate_grid_pallas(net, [0.5, 0.9], interpret=True, **kw)
    np.testing.assert_array_equal(twin.throughput, kern.throughput)
    for row_t, row_k in zip(twin.traces, kern.traces):
        for tr_t, tr_k in zip(row_t, row_k):
            assert tr_t.n_dropped == tr_k.n_dropped > 0
            for field in ("req", "branch", "cls", "nvis", "enter_us",
                          "leave_us"):
                np.testing.assert_array_equal(
                    getattr(tr_k, field), getattr(tr_t, field),
                    err_msg=field)


@pytest.mark.parametrize("mask_kind", ["ties", "empty", "tail"])
def test_indexed_state_argmin_semantics(mask_kind):
    """RefState's blocked argmin (inside a kernel) == ArrayState's
    jnp.argmin: first index of the least masked key across blocks, and
    (0, INT32_MAX) when nothing is masked."""
    from jax.experimental import pallas as pl

    from repro.indexed_state import ArrayState
    from repro.kernels.state import RefState

    n = 4 * 1024
    rng = np.random.default_rng(8)
    key = rng.integers(0, 50, n).astype(np.int32)  # many ties
    mask = {"ties": rng.random(n) < 0.5, "empty": np.zeros(n, bool),
            "tail": np.arange(n) >= n - 700}[mask_kind].astype(np.int32)

    def fn(v):
        return v.aux != 0, v.ts

    def kernel(ts_ref, aux_ref, out_ref):
        st = RefState({"ts": ts_ref, "aux": aux_ref},
                      block_rows=8, n_blocks=4)
        idx, m = st.argmin(fn)
        out_ref[0] = idx
        out_ref[1] = m

    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((2,), jnp.int32),
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM), interpret=True,
    )(jnp.asarray(key.reshape(-1, 128)), jnp.asarray(mask.reshape(-1, 128)))
    want = ArrayState({"ts": jnp.asarray(key), "aux": jnp.asarray(mask)},
                      "ts").argmin(fn)
    assert [int(g) for g in got] == [int(w) for w in want]


def test_event_sim_statistics_match_threefry():
    """Counter-RNG engine agrees with the threefry scan simulator within
    sampling error and preserves the paper's hit-ratio inversion."""
    net = lru_network(disk_us=100.0)
    p = np.array([0.7, 0.9, 0.99])
    a = simulate_network(net, p, n_requests=8000, seeds=(0, 1))
    b = simulate_network(net, p, n_requests=8000, seeds=(0, 1),
                         backend="pallas")
    np.testing.assert_allclose(b.throughput, a.throughput, rtol=0.06)
    assert b.throughput[2] < b.throughput[1]  # 0.99 slower than 0.9


def test_harness_backend_agreement():
    """run/measure/sweep report identical numbers for jax and pallas."""
    trace = zipf_trace(2000, 256, 0.99, 0)
    h_j, o_j = run_cache_trace("sieve", 32, trace, backend="jax",
                               key_space=256)
    h_p, o_p = run_cache_trace("sieve", 32, trace, backend="pallas",
                               key_space=256)
    np.testing.assert_array_equal(h_j, h_p)
    np.testing.assert_array_equal(o_j, o_p)

    m_j = measure_cache("clock", 32, key_space=256, n_requests=2000,
                        backend="jax", miss_latency_requests=5,
                        fetch_fail_prob=0.1, max_scan=3)
    m_p = measure_cache("clock", 32, key_space=256, n_requests=2000,
                        backend="pallas", miss_latency_requests=5,
                        fetch_fail_prob=0.1, max_scan=3)
    assert m_j.hit_ratio == m_p.hit_ratio
    np.testing.assert_allclose(m_p.class_fracs, m_j.class_fracs)

    for mlr in (5, np.array([3, 7])):
        s_j = sweep_cache_sizes("slru", [16, 48], key_space=256,
                                n_requests=2000, backend="jax",
                                miss_latency_requests=mlr,
                                protected_frac=0.5)
        s_p = sweep_cache_sizes("slru", [16, 48], key_space=256,
                                n_requests=2000, backend="pallas",
                                miss_latency_requests=mlr,
                                protected_frac=0.5)
        for k in s_j:
            np.testing.assert_allclose(s_p[k], s_j[k], err_msg=k,
                                       rtol=1e-12)


def test_validation_errors():
    keys, us = _trace(100)
    with pytest.raises(ValueError, match="shape mismatch"):
        replay_grid_pallas("lru", keys, us[:-1], [8], key_space=KEY_SPACE)
    with pytest.raises(ValueError, match="at least one capacity"):
        replay_grid_pallas("lru", keys, us, [], key_space=KEY_SPACE)
    net = lru_network(disk_us=100.0)
    with pytest.raises(ValueError, match="unknown backend"):
        simulate_network(net, [0.5], backend="nope")
    with pytest.raises(ValueError, match="closed loop"):
        simulate_network(net, [0.5], backend="pallas", arrival_rate=0.1)
    with pytest.raises(ValueError, match="closed loop"):
        simulate_network(net, [0.5], backend="pallas", coalesce_flows=4)


@pytest.mark.slow
def test_kernel_interpreter_grid_large():
    """A bigger (capacity x seed) interpreter grid — the pallas-grid
    bench shape, deselected from tier-1 (-m 'not slow')."""
    rng = np.random.default_rng(7)
    S, T = 2, 2500
    keys = rng.integers(0, KEY_SPACE, size=(S, T))
    us = rng.random((S, T), dtype=np.float32)
    caps = [4, 9, 17]
    kw = dict(key_space=KEY_SPACE, window=10, max_scan=3,
              small_frac=0.25)
    twin = replay_grid_pallas("s3fifo", keys, us, caps, **kw)
    kern = replay_grid_pallas("s3fifo", keys, us, caps, interpret=True,
                              **kw)
    for field in ("hits", "evicted", "ops", "cls"):
        np.testing.assert_array_equal(np.asarray(getattr(kern, field)),
                                      np.asarray(getattr(twin, field)))

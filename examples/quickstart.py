"""Quickstart: the paper's three-pronged study in ~50 lines.

    PYTHONPATH=src python examples/quickstart.py

Builds the LRU and S3-FIFO queueing models, derives the analytic throughput
bound, simulates the exact network, drives the real cache implementation
through the compiled replay engine, and prints where LRU's throughput
inverts (the paper's headline).

The script doubles as a smoke test of the replay engine's differential
contract: the compiled ``backend="jax"`` scan and the pure-Python
``backend="py"`` oracle must produce bit-identical (hits, ops) arrays for
the same trace and coin streams.
"""

import numpy as np

from repro import compile_cache
from repro.core import build
from repro.core.harness import measure_cache, run_cache_trace, zipf_trace
from repro.core.simulator import simulate_network

compile_cache.enable()

P = np.array([0.5, 0.7, 0.85, 0.95, 0.99])

# Differential contract first: scan engine == python oracle, bit for bit.
trace = zipf_trace(4_000, key_space=512, seed=0)
for policy in ("lru", "s3fifo"):
    h_jax, ops_jax = run_cache_trace(policy, 64, trace, backend="jax",
                                     key_space=512)
    h_py, ops_py = run_cache_trace(policy, 64, trace, backend="py")
    assert np.array_equal(h_jax, h_py), f"{policy}: hit sequences diverge"
    assert np.array_equal(ops_jax, ops_py), f"{policy}: op vectors diverge"
print("differential contract OK: backend='jax' == backend='py' "
      "(hits and op vectors bit-identical)")

for policy in ("lru", "s3fifo"):
    net = build(policy, disk_us=100.0)  # 72-core closed loop, 100us disk

    # Prong A: analytic upper bound (Thm 7.1) + critical hit ratio
    bound = net.throughput_upper(P)
    p_star = net.p_star()

    # Prong B: event-driven simulation of the exact network
    sim = simulate_network(net, P, n_requests=12_000, seeds=(0,))

    # Prong C: the real (array-based) cache under a Zipf workload, replayed
    # by the compiled scan engine (same numbers as the py oracle, ~10-80x
    # faster)
    meas = measure_cache(policy, capacity=512, key_space=4096,
                         n_requests=30_000, backend="jax")

    print(f"\n=== {policy.upper()}  (p* = {p_star:.3f})")
    print("p_hit      " + "  ".join(f"{p:6.2f}" for p in P))
    print("X theory   " + "  ".join(f"{x:6.3f}" for x in bound))
    print("X sim      " + "  ".join(f"{x:6.3f}" for x in sim.throughput))
    print(f"impl: measured hit ratio {meas.hit_ratio:.3f} at 512 pages, "
          f"X bound {meas.throughput_bound():.3f} Mreq/s")
    if p_star < 0.99:
        print(f"  -> raising hit ratio past {p_star:.2f} HURTS throughput "
              f"(hit-path delink becomes the bottleneck)")
    else:
        print("  -> throughput is monotone in hit ratio (no hit-path ops)")

# Tiered differential: the cross-tier MSHR event kernel and its heapq
# oracle must agree on an L1 -> sharded L2 -> origin hierarchy (throughput
# and the per-tier delayed-hit split -- statistical twins, not bit twins).
from repro.hierarchy import hierarchy_network  # noqa: E402
from repro.hierarchy.sim import (  # noqa: E402
    simulate_hierarchy, simulate_hierarchy_py)

hier = hierarchy_network("lru", "lru", n_clients=2, n_shards=2,
                         mpl=16, disk_us=50.0)
tj = simulate_hierarchy(hier, [0.5], n_requests=12_000, seeds=(0, 1),
                        coalesce_flows=2)
tp = simulate_hierarchy_py(hier, 0.5, n_requests=12_000, seed=0,
                           coalesce_flows=2)
x_jax, x_py = float(tj.throughput[0]), float(tp.throughput[0])
assert abs(x_jax - x_py) / max(x_jax, x_py) < 0.2, (x_jax, x_py)
assert abs(float(tj.delayed_l1_frac[0]) - float(tp.delayed_l1_frac[0])) < 0.1
print(f"\ntiered differential OK: jax X={x_jax:.3f} vs heapq oracle "
      f"X={x_py:.3f} (cross-tier MSHR twins agree)")

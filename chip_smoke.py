#!/usr/bin/env python3
"""Bring-up smoke: the replay and simulation engines on one TPU chip.

    python3 chip_smoke.py [--seed N] [--requests T]

One process holds the chip and starts no other.  It drives the three
engines that answer the paper's question through their user entry
points, checks what comes out, and prints one JSON line per phase (sizes,
compile seconds, wall seconds, the check), then the device line:

A  replay: the seven eviction policies on an 8-capacity (2^13..2^19) x
   2-seed grid over a YCSB-style Zipf trace (theta 0.99, Cooper et al.,
   SoCC 2010) on 2^20 keys, delayed-hit classification fused in, through
   the Pallas replay kernel (``replay_grid_pallas``, the engine under
   ``sweep_cache_sizes(backend="pallas")``, which runs too).  Checked
   bit-identical to ``py_ref`` over the whole trace of the first seed at
   every capacity (evictions included, at every walk width), and to the
   dlist engine (``replay_grid``) and the XLA classifier on the same
   device over the first 2^12 requests of every lane.
   ``--requests`` is the one cut: trace length per seed (default 2^20).
B  closed loop: the paper's LRU network (MPL 72, disk 100 us), 16 p_hit
   in [0.5, 0.99] x 4 seeds x 2e5 requests, on the Pallas event kernel
   and on the XLA event loop.  Checked against exact MVA.
C  open loop with miss coalescing (8 flows) at 0.85 lambda_max on the
   XLA event loop, checked against the coalesced network's Erlang-C mean.

Exits non-zero, printing no result, unless JAX's first device is a TPU.
Every input comes from ``--seed``; nothing outside the checkout is read.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
T0 = time.perf_counter()

KEY_SPACE = 1 << 20
PAD = 1 << 19
FULL_REQUESTS = 1 << 20
# The dlist engine and the XLA classifier copy each lane's (key_space,)
# tables on every request under vmap, so they check a prefix of every
# lane; py_ref checks the whole first-seed lane of every capacity, on a
# host thread while the chip runs.
REF_REQUESTS = 1 << 12
MISS_WINDOW_MEAN = 64  # requests a fetch stays in flight, on average
POLICY_PARAMS = {
    "lru": {},
    "fifo": {},
    "prob_lru": {"q": 0.5},
    "clock": {"max_scan": 3},
    "slru": {"protected_frac": 0.5},
    "s3fifo": {"small_frac": 0.1, "max_scan": 3},
    "sieve": {},
}
SIM_REQUESTS = 200_000
OPEN_REQUESTS = 100_000
MVA_REL_TOL = 0.12       # tests/test_simulator.py::test_simulation_matches_mva
ERLANG_REL_TOL = 0.08    # tests/test_latency.py open-loop sojourn vs Erlang-C
RATE_REL_TOL = 0.05      # tests/test_latency.py throughput == offered rate


def check(ok: bool, what: str) -> str:
    if not ok:
        raise RuntimeError(f"check failed: {what}")
    return what


def progress(**fields) -> None:
    """One line on stderr, so a run cut by a time limit shows how far it
    got (stdout carries only the phase lines and the device line)."""
    print(json.dumps({"t_s": time.perf_counter() - T0, **fields}),
          file=sys.stderr, flush=True)


def timed(fn, *args, **kwargs):
    """(result, compile_s, wall_s): wall ends once the result is on the
    host or ready on the device."""
    import jax
    from benchmarks.common import compile_monitor

    with compile_monitor() as mon:
        out = jax.block_until_ready(fn(*args, **kwargs))
    return out, mon.compile_s, mon.wall_s


def _py_replay(keys: list, us: list, policy: str, cap: int, params: dict):
    """(hits, evicted, ops) of one py_ref replay."""
    import numpy as np

    from repro.cache.py_ref import PY_POLICIES

    py = PY_POLICIES[policy](cap, **params)
    acc = [py.access(k, u) for k, u in zip(keys, us)]
    return (np.array([a.hit for a in acc]),
            np.array([a.evicted_key for a in acc], np.int32),
            np.array([a.ops for a in acc], np.int32))


def py_ref_replays(keys, us, caps):
    """Queue py_ref replays of (keys, us) at every capacity for every
    policy on one background thread, which runs while the main thread
    waits on the chip; returns the executor and ``{(policy, cap):
    Future}``."""
    from concurrent.futures import ThreadPoolExecutor

    keys, us = keys.tolist(), us.tolist()
    pool = ThreadPoolExecutor(max_workers=1)
    return pool, {(policy, cap): pool.submit(_py_replay, keys, us, policy,
                                             cap, params)
                  for policy, params in POLICY_PARAMS.items()
                  for cap in caps}


def has_mosaic_kernel(jitted, *args, **kwargs) -> bool:
    return "tpu_custom_call" in jitted.lower(*args, **kwargs).as_text()


def phase_replay(seed: int, n_req: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.cache import classify_inflight, flat
    from repro.cache.replay import replay_grid
    from repro.core.harness import (coin_stream, miss_window_stream,
                                    sweep_cache_sizes, zipf_trace)
    from repro.kernels import replay

    caps = [int(c) for c in np.geomspace(PAD >> 6, PAD, 8).round()]
    seeds = (seed, seed + 1)
    n_ref = min(n_req, REF_REQUESTS)
    keys = np.stack([zipf_trace(n_req, KEY_SPACE, 0.99, s) for s in seeds])
    us = np.stack([coin_stream(n_req, s) for s in seeds])
    win = miss_window_stream(n_req, MISS_WINDOW_MEAN, seed)
    lanes = len(caps) * len(seeds)
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((lanes, flat.N_PARAMS), jnp.int32), ((lanes,), jnp.float32),
        ((lanes, n_req), jnp.int32), ((lanes, n_req), jnp.float32),
        ((lanes, n_req), jnp.int32))]
    pool, py_jobs = py_ref_replays(keys[0], us[0], caps)
    policies, first_seed = {}, {}
    compile_s = wall_s = 0.0
    try:
        for policy, params in POLICY_PARAMS.items():
            res, c_s, w_s = timed(replay.replay_grid_pallas, policy, keys, us,
                                  caps, key_space=KEY_SPACE, pad_to=PAD,
                                  window=win, **params)
            sweep, sc_s, sw_s = timed(sweep_cache_sizes, policy, caps,
                                      key_space=KEY_SPACE, n_requests=n_req,
                                      theta=0.99, seed=seed, backend="pallas",
                                      miss_latency_requests=win, **params)
            compile_s += c_s + sc_s
            wall_s += w_s + sw_s
            progress(policy=policy, compile_s=c_s, wall_s=w_s,
                     sweep_compile_s=sc_s, sweep_wall_s=sw_s)
            t_ref = time.perf_counter()
            hits = np.asarray(res.hits)
            evicted = np.asarray(res.evicted)
            ops = replay.unpack_grid_ops(res)
            cls = np.asarray(res.cls)
            # a replay's prefix is the replay of the trace's prefix, so the
            # references check the first n_ref requests of every lane
            ref = replay_grid(policy, keys[:, :n_ref], us[:, :n_ref], caps,
                              key_space=KEY_SPACE, pad_to=PAD, **params)
            check(np.array_equal(hits[..., :n_ref], ref.hits)
                  and np.array_equal(evicted[..., :n_ref], ref.evicted)
                  and np.array_equal(ops[:, :, :n_ref], ref.ops),
                  f"{policy}: hits/evicted/ops == dlist engine")
            for i in range(len(seeds)):
                want = classify_inflight(keys[i, :n_ref],
                                         res.hits[:, i, :n_ref], win[:n_ref],
                                         key_space=KEY_SPACE)
                check(np.array_equal(cls[:, i, :n_ref], np.asarray(want)),
                      f"{policy}: fused classes == classify_inflight")
            # compared with py_ref once the chip work is done
            first_seed[policy] = (hits[:, 0].copy(), evicted[:, 0].copy(),
                                  ops[:, 0].astype(np.int32))
            warm = int(n_req * 0.25)  # the sweep measures past its warmup
            check(np.array_equal(sweep["p_hit"],
                                 hits[:, 0, warm:].mean(axis=-1)),
                  f"{policy}: sweep p_hit == grid hit ratio")
            check(replay.pallas_grid._cache_size() > 0
                  and replay._twin_grid._cache_size() == 0,
                  f"{policy}: ran the compiled kernel, never the twin")
            check(has_mosaic_kernel(replay.pallas_grid, policy, *shapes,
                                    key_space=KEY_SPACE, pad=PAD),
                  f"{policy}: kernel lowers to tpu_custom_call")
            progress(policy=policy, checks_s=time.perf_counter() - t_ref)
            policies[policy] = {
                "compile_s": c_s, "wall_s": w_s,
                "sweep_compile_s": sc_s, "sweep_wall_s": sw_s,
                "hit_ratio": hits.mean(axis=(1, 2)).tolist(),
                "delayed_frac": (cls == 2).mean(axis=(1, 2)).tolist(),
            }
        t_py = time.perf_counter()
        for policy, (hits, evicted, ops) in first_seed.items():
            n_evicted = []
            for c, cap in enumerate(caps):
                py_hits, py_evicted, py_ops = py_jobs[policy, cap].result()
                check(np.array_equal(hits[c], py_hits)
                      and np.array_equal(evicted[c], py_evicted)
                      and np.array_equal(ops[c], py_ops),
                      f"{policy} cap {cap}: whole first-seed lane == py_ref")
                n_evicted.append(int((py_evicted >= 0).sum()))
            policies[policy]["py_ref_evictions"] = n_evicted
        py_wait_s = time.perf_counter() - t_py
        progress(py_ref_wait_s=py_wait_s)
    finally:
        # the queue is empty unless a check failed
        pool.shutdown(cancel_futures=True)
    return {
        "phase": "A replay", "key_space": KEY_SPACE, "pad": PAD,
        "capacities": caps, "seeds": list(seeds), "requests": n_req,
        "cut": None if n_req == FULL_REQUESTS
        else f"requests per seed {FULL_REQUESTS} -> {n_req}",
        "dlist_and_classifier_prefix": n_ref,
        "py_ref": "whole trace of the first seed, every capacity",
        "py_ref_wait_s": py_wait_s,
        "miss_window_mean": MISS_WINDOW_MEAN,
        "compile_s": compile_s, "wall_s": wall_s,
        "check": "bit-identical to py_ref (whole first-seed lane of every "
                 "capacity; py_ref_evictions per capacity) and to the dlist "
                 "engine and classify_inflight (first "
                 "dlist_and_classifier_prefix requests of every lane); "
                 "sweep agrees; Mosaic kernel ran",
        "policies": policies,
    }


def phase_closed(seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import lru_network
    from repro.core.simspec import compile_network
    from repro.core.simulator import simulate_network
    from repro.kernels import event_sim

    net = lru_network(disk_us=100.0)
    p = np.linspace(0.5, 0.99, 16)
    seeds = tuple(range(seed, seed + 4))
    mva = net.mva_throughput(p)
    p_mva = float(p[np.argmax(mva)])
    step = float(p[1] - p[0])

    def smoothed_p_star(x):
        # for information: the curve is flat to ~0.2% over three grid
        # points at its peak, about one point's sampling noise
        return float(p[1 + np.argmax(np.convolve(x, np.ones(3), "valid"))])

    out = {"phase": "B closed loop", "network": "lru", "mpl": net.mpl,
           "disk_us": 100.0, "p_hit": p.tolist(), "seeds": list(seeds),
           "requests": SIM_REQUESTS, "p_star_mva": p_mva,
           "compile_s": 0.0, "wall_s": 0.0}
    for backend in ("pallas", "jax"):
        res, c_s, w_s = timed(simulate_network, net, p,
                              n_requests=SIM_REQUESTS, seeds=seeds,
                              backend=backend)
        rel = np.abs(res.throughput - mva) / mva
        p_sim = float(p[np.argmax(res.throughput)])
        check(float(rel.max()) < MVA_REL_TOL,
              f"{backend}: X within {MVA_REL_TOL} of MVA")
        check(abs(p_sim - p_mva) <= step * (1 + 1e-9),
              f"{backend}: p* within one grid step of MVA's")
        out["compile_s"] += c_s
        out["wall_s"] += w_s
        progress(backend=backend, compile_s=c_s, wall_s=w_s)
        out[backend] = {"compile_s": c_s, "wall_s": w_s,
                        "max_rel_err": float(rel.max()), "p_star": p_sim,
                        "p_star_smoothed": smoothed_p_star(res.throughput),
                        "throughput": res.throughput.tolist()}
    check(event_sim.pallas_grid._cache_size() > 0
          and event_sim._twin_grid._cache_size() == 0,
          "pallas backend ran the compiled kernel, never the twin")
    spec = compile_network(net, float(p[0]))
    lanes = len(p) * len(seeds)
    tabs = {name: jax.ShapeDtypeStruct((lanes, np.asarray(a).size),
                                       np.asarray(a).dtype)
            for name, a in ((event_sim.IS_QUEUE, spec.is_queue.astype(np.int32)),
                            (event_sim.SVC_NS, spec.svc_ns),
                            (event_sim.DIST_ID, spec.dist_id),
                            (event_sim.DIST_PAR, spec.dist_params),
                            (event_sim.BRANCH_CUM, spec.branch_cum),
                            (event_sim.VISITS, spec.visits),
                            (event_sim.SERVERS, spec.servers))}
    check(has_mosaic_kernel(
        event_sim.pallas_grid, tabs,
        jax.ShapeDtypeStruct((lanes,), jnp.int32), n_requests=SIM_REQUESTS,
        warmup=SIM_REQUESTS // 4, mpl=net.mpl, max_events=SIM_REQUESTS,
        route_len=int(spec.visits.shape[-1])),
        "event kernel lowers to tpu_custom_call")
    out["check"] = (f"both backends within {MVA_REL_TOL} of exact MVA, p* "
                    "(argmax of the simulated throughput) within one grid "
                    "step of MVA's; Mosaic kernel ran")
    return out


def phase_open(seed: int) -> dict:
    import dataclasses

    import numpy as np

    from repro.core import lru_network
    from repro.core.queueing import coalesced_network, exponential_analogue
    from repro.core.simulator import simulate_network
    from repro.latency import lambda_max, response_time

    # The coalesced Erlang-C forecast parks a delayed hit for half the
    # fetch window, which is exact for a fixed-latency fetch, and solves
    # exponential queues exactly: simulate that form of the LRU network.
    net = exponential_analogue(lru_network(disk_us=100.0))
    net = dataclasses.replace(net, stations=tuple(
        dataclasses.replace(s, dist="det") if s.name == "disk" else s
        for s in net.stations))
    flows = 8
    p = np.array([0.5, 0.7, 0.9])
    lam = 0.85 * float(np.min(lambda_max(net, p, tail_mode="nominal")))
    seeds = tuple(range(seed, seed + 4))
    res, c_s, w_s = timed(simulate_network, net, p, backend="jax",
                          arrival_rate=lam, coalesce_flows=flows,
                          n_requests=OPEN_REQUESTS, seeds=seeds,
                          max_in_system=256)
    want = response_time(coalesced_network(net, flows=flows), p, lam)
    rel = np.abs(res.sojourn_mean - want) / want
    rate = np.abs(res.throughput - lam) / lam
    check(float(rel.max()) < ERLANG_REL_TOL,
          f"mean sojourn within {ERLANG_REL_TOL} of Erlang-C")
    check(float(rate.max()) < RATE_REL_TOL,
          f"throughput within {RATE_REL_TOL} of the offered rate")
    check(bool(np.all(res.drop_frac == 0.0))
          and bool(np.all(res.delayed_frac > 0.0)),
          "no drops, delayed hits present")
    return {
        "phase": "C open loop + coalescing", "network":
        "lru, exponential queues, fixed-latency disk", "disk_us": 100.0,
        "coalesce_flows": flows, "arrival_rate": lam,
        "load": "0.85 lambda_max", "p_hit": p.tolist(), "seeds": list(seeds),
        "requests": OPEN_REQUESTS, "compile_s": c_s, "wall_s": w_s,
        "sojourn_mean_us": res.sojourn_mean.tolist(),
        "erlang_c_us": want.tolist(), "max_rel_err": float(rel.max()),
        "delayed_frac": res.delayed_frac.tolist(),
        "check": f"sojourn within {ERLANG_REL_TOL} of Erlang-C, X within "
                 f"{RATE_REL_TOL} of lambda, no drops",
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=FULL_REQUESTS,
                    help="phase A trace length per seed (the one cut)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is "
              f"{devices[0].platform})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache

    cache_dir = compile_cache.enable()
    for phase in (lambda: phase_replay(args.seed, args.requests),
                  lambda: phase_closed(args.seed),
                  lambda: phase_open(args.seed)):
        print(json.dumps(phase()), flush=True)
    print(json.dumps({"total_s": time.perf_counter() - T0,
                      "compile_cache": cache_dir}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark suite entry point: one module per paper table/figure plus the
beyond-paper serving integration, kernel microbenches, and the roofline
report.  Each prints CSV; failures raise (the paper's qualitative claims
are asserted inside each benchmark).

    PYTHONPATH=src python -m benchmarks.run [--only fig3_lru,...] \
        [--json BENCH_replay.json] [--trace-sample sample.trace.json]

``--json`` writes the perf-trajectory artifact: replay throughput
(requests/s, py vs jax vs pallas backend, from replay_bench) plus
per-bench wall times and wall/compile splits, and — when they ran — the
latency-prong summary (fig_latency), the cluster summary (fig_cluster),
the hierarchy summary (fig_hierarchy), the kernel microbench table
(kernel_bench: interpreter call times + exactness vs the scan twins),
and the dry-run roofline records (roofline), all in one unified payload.
Each payload is stamped with a ``provenance`` block (git sha, versions,
seeds, config hash — see ``repro.obs.provenance``), per-bench failures
land as ``{bench name: traceback}``, and CI validates the schema +
guarded series with ``python -m repro.obs.provenance check``.

``--trace-sample PATH`` additionally runs a small traced closed-loop
simulation and writes its per-request records as a Perfetto
``trace_event`` JSON (openable in ui.perfetto.dev / chrome://tracing).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from benchmarks.common import N_SIM_REQUESTS, compile_monitor
from repro import compile_cache

BENCHES = [
    "replay_bench",  # py_ref loop vs compiled replay fast path
    "fig3_lru",  # Fig. 1/3 + Eq. (1)-(3)
    "fig5_fifo",  # Fig. 5 + Eq. (4)-(6)
    "fig7_8_problru",  # Figs. 7-8
    "fig10_clock",  # Fig. 10
    "fig12_slru",  # Fig. 12 (disk x MPL trends)
    "fig14_s3fifo",  # Fig. 14
    "fig_future_systems",  # Sec. 6: cores x disk speed, c-server disk
    "fig_delayed_hits",  # beyond-paper: miss coalescing / delayed hits
    "fig_latency",  # beyond-paper: open-loop response time / SLO p*
    "fig_cluster",  # beyond-paper: sharded cluster, cluster-level p*
    "fig_hierarchy",  # beyond-paper: tiered L1 -> sharded L2 -> origin
    "fig_drift",  # beyond-paper: streaming estimators / drift / residuals
    "table2_classify",  # Tables 1-2
    "bypass_mitigation",  # Sec. 5.2
    "serving_integration",  # beyond-paper: prefix-cache controller at pod scale
    "kernel_bench",  # Pallas kernels (interpret mode)
    "roofline",  # §Roofline report from the dry-run sweep
]

#: Seeds the sim-backed benches run on (the simulate_* defaults).
BENCH_SEEDS = (0, 1, 2)


def write_trace_sample(path: str) -> None:
    """Run a small traced closed-loop sim and export it for Perfetto."""
    from repro.core import lru_network
    from repro.core.simulator import simulate_network
    from repro.obs.export import write_perfetto

    net = lru_network(disk_us=100.0)
    res = simulate_network(net, [0.7], n_requests=2_000, seeds=(0,),
                           coalesce_flows=4, trace=512)
    names = [s.name for s in net.stations]
    write_perfetto(path, res.traces[0][0], station_names=names)
    print(f"[wrote {path}]")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write the provenance-stamped bench payload")
    ap.add_argument("--trace-sample", default="", metavar="PATH",
                    help="write a sample Perfetto trace from a traced sim")
    args = ap.parse_args()
    only = [s.strip() for s in args.only.split(",") if s.strip()]
    unknown = [n for n in only if n not in BENCHES]
    if unknown:
        sys.exit(f"unknown benchmark(s) {unknown}; choose from {BENCHES}")

    compile_cache.enable()
    failures: dict[str, str] = {}
    bench_seconds = {}
    bench_timings = {}
    # benches whose return value is recorded in the --json payload
    captured = {"replay_bench": "replay", "fig_latency": "latency",
                "fig_cluster": "cluster", "fig_hierarchy": "hierarchy",
                "fig_drift": "drift",
                "kernel_bench": "kernels", "roofline": "roofline"}
    results = {}
    for name in BENCHES:
        if only and name not in only:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            with compile_monitor() as mon:
                mod = __import__(f"benchmarks.{name}", fromlist=["main"])
                result = mod.main()
            bench_seconds[name] = time.time() - t0
            bench_timings[name] = mon.split
            if name in captured:
                # a registered bench that returns nothing would silently
                # drop its series from the payload — and the provenance
                # guard list would only catch it if someone remembered to
                # register the series there too.  Fail loudly at the source.
                if not result:
                    raise RuntimeError(
                        f"{name} is registered to emit the "
                        f"{captured[name]!r} series but returned "
                        f"{result!r} — benches in `captured` must return "
                        f"a non-empty payload dict")
                results[captured[name]] = result
            print(f"[{name}: ok in {bench_seconds[name]:.1f}s "
                  f"({mon.split['compile_s']:.1f}s compile)]", flush=True)
        except Exception:
            bench_seconds[name] = time.time() - t0
            traceback.print_exc()
            failures[name] = traceback.format_exc()

    if args.trace_sample:
        try:
            write_trace_sample(args.trace_sample)
        except Exception:
            traceback.print_exc()
            failures["trace_sample"] = traceback.format_exc()

    if args.json:
        from repro.obs.provenance import stamp

        payload = {"bench_seconds": bench_seconds,
                   "bench_timings": bench_timings,
                   "failures": failures}
        payload.update(results)
        stamp(
            payload,
            config={"only": only or list(BENCHES),
                    "n_sim_requests": N_SIM_REQUESTS},
            seeds=BENCH_SEEDS,
            timings={
                "wall_s": sum(t["wall_s"] for t in bench_timings.values()),
                "compile_s": sum(t["compile_s"]
                                 for t in bench_timings.values()),
            },
        )
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"\n[wrote {args.json}]")

    if failures:
        print(f"\nFAILED: {sorted(failures)}")
        sys.exit(1)
    print("\nall benchmarks passed")


if __name__ == "__main__":
    main()

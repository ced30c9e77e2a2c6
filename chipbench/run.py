#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process holds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1> [--keep-trace DIR]

Set-up makes the cell's inputs from ``--seed``, reads the compiled programs
from the persistent cache in ``<checkout>/.jax_cache`` (or where
``JAX_COMPILATION_CACHE_DIR`` says) and answers one warm-up question of the
cell's own shapes. The window then asks whole questions back to back until
``--seconds`` have passed; ``--trace 1`` records a profiler trace of the
mix's first ``trace_questions`` questions instead and reports the
per-layer metrics. Once the window has closed and the device memory peak
is read, kept answers are compared with the plain references in host
worker processes. The last line of stdout is the result; the last lines of
stderr are the numbers compared, each beside its limit.

Exits 2, printing no result, unless JAX's devices are TPUs and as many as
the cell asks for.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this directory")
    return ap.parse_args(argv)


def place_compile_cache(jax) -> None:
    """The program's placement (``<checkout>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` says otherwise), with every program kept,
    however fast it compiled."""
    from repro import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def window(eng, counter, seconds: float, limit=None):
    """Whole questions back to back until ``seconds`` have passed (or
    ``limit`` questions); returns (questions, span seconds)."""
    from chipbench.bench import span

    counter.armed = True
    t0 = time.perf_counter()
    n = 0
    with span("window"):
        while True:
            eng.question(n)
            n += 1
            if (time.perf_counter() - t0 >= seconds
                    or (limit is not None and n >= limit)):
                break
    t1 = time.perf_counter()
    counter.armed = False
    return n, t1 - t0


def layer_metrics(files, eng, view, kind, n_questions, here):
    from chipbench import bench, tracing

    ctx = {"view": view, "engine": eng, "questions": n_questions,
           "work": eng.work_per_question * n_questions,
           "peaks": lambda: tracing.peaks(kind)}
    out = {}
    for m in files["per_layer"]:
        value = bench.metric_reader(m["name"], here).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class InlinePool:
    """``map`` in this process: what a test passes as ``pool``."""

    @staticmethod
    def map(fn, items, chunksize=1):
        return [fn(x) for x in items]


def compare(eng, limits, pool):
    """The engine's check, in ``pool`` or in spawned worker processes."""
    if pool is not None:
        return eng.check(pool, limits)
    workers = max(1, min(12, (os.cpu_count() or 2) - 1))
    with multiprocessing.get_context("spawn").Pool(workers) as procs:
        out = eng.check(procs, limits)
        procs.close()
        procs.join()
    return out


def main(argv=None, root: Path = ROOT, require_tpu: bool = True,
         pool=None) -> int:
    """``root`` is the checkout whose BENCHMARK.json and benchmark files
    are read. A test drives a run on the CPU with ``require_tpu=False``
    (JAX's compile cache is then left as the test has it) and may pass an
    ``InlinePool`` for the references."""
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import bench, tracing

    here = root / "chipbench"
    files = bench.cell_files(bench.benchmark(root), args.workload, here)
    chips = int(files["cell"]["chips"])
    mix = files["mix"]

    marks = {"files_s": time.perf_counter() - T0}
    import jax

    if require_tpu:
        place_compile_cache(jax)
    devices = jax.devices()
    marks["devices_s"] = time.perf_counter() - T0
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        print(f"chipbench: needs {chips} TPU chip(s); JAX has "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2

    counter = bench.CompileCounter()
    eng = bench.engine(mix, here).Engine(files, args.seed)
    marks["inputs_s"] = time.perf_counter() - T0
    counter.armed = True
    eng.warm()
    counter.armed = False
    setup_s = time.perf_counter() - T0
    # where set-up went: seconds since start at each mark, and the programs
    # the warm-up question lowered, compiled or read from the cache
    marks.update(warm_s=setup_s, warm_programs=counter.count,
                 warm_compile_s=counter.seconds)
    counter.count, counter.seconds = 0, 0.0

    view = trace = None
    if args.trace:
        with tracing.capture() as trace:
            n, span_s = window(eng, counter, args.seconds,
                               limit=int(mix["trace_questions"]))
        view = tracing.load(trace["path"])
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(trace["path"], args.keep_trace)
        tracing.discard(trace)
    else:
        n, span_s = window(eng, counter, args.seconds)
    device = bench.device_record(devices, chips)

    limits = mix["limits"]
    numbers, info = compare(eng, limits, pool)
    # the mix's limits name the numbers compared; the others are reported
    checks = {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}
    info["not_compared"] = {k: v for k, v in numbers.items() if k not in limits}
    checks["compiles_in_window"] = {"value": counter.count, "limit": 0}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    info["setup"] = marks
    print(json.dumps({"info": info}), file=sys.stderr)

    if args.trace:
        lo, hi = view.window()
        ops = [tracing.clip(view.ops(c), lo, hi)
               for c in sorted(view.chips)] or [[]]
        device["busy_s"] = (sum(tracing.busy_ns(o) for o in ops) / len(ops)
                            * 1e-9)
        device["window_s"] = (hi - lo) * 1e-9
        metrics = layer_metrics(files, eng, view, device["kind"], n, here)
        breakdown = {"device_ops": tracing.top_ops(ops[0]),
                     "idle_gaps": tracing.idle_gaps(ops[0], view.spans, lo,
                                                    hi)}
    else:
        values = {"setup_s": setup_s,
                  f"{eng.unit}_rate": eng.work_per_question * n / span_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in files["end_to_end"]}
        breakdown = None

    result = {"correct": correct, "attempted": n,
              "failed": info["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    bench.print_checks(checks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

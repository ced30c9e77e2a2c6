"""What every cell shares: finding its files by name, the compile counter,
host spans, the device record and the result line.

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``)
and a traffic mix (``mixes/<traffic>.json``); the mix names the engine
(``engines/<engine>.py``) that drives the program, and each per-layer
metric is read by ``metrics/<name>.py``. Adding a cell, a mix or a metric
is adding files and entries: nothing here changes.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
SPAN_PREFIX = "chipbench."


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell_files(bench: dict, workload: str, here: Path = HERE) -> dict:
    """The cell's entry, configuration, mix and the per-layer metrics that
    read something in it, each found by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(here / "configs" / f"{cell['config']}.json")
    mix = load_json(here / "mixes" / f"{cell['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if m["moves"] in moved
             and workload in m.get("workloads", [workload])]
    return {"cell": cell, "config_entry": configs[cell["config"]],
            "config": config, "mix": mix, "end_to_end": e2e,
            "per_layer": layer}


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (metric file names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def engine(mix: dict, here: Path = HERE):
    return load_module(here / "engines" / f"{mix['engine']}.py",
                       f"chipbench_engine_{mix['engine']}")


def metric_reader(name: str, here: Path = HERE):
    return load_module(here / "metrics" / f"{name}.py",
                       "chipbench_metric_" + name.replace(".", "_"))


# a program lowered, compiled, or read back from the persistent cache
# (tracing alone is not counted: some entry points retrace a fresh wrapper
# on every call and find the compiled program in the in-memory cache)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileCounter:
    """Counts programs lowered, compiled or read from the persistent cache
    while it is armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1
            self.seconds += duration


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (a no-op while none records)."""
    import jax

    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield


def device_record(devices, n_chips: int) -> dict:
    peaks = []
    for d in devices[:n_chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": n_chips, "memory_peak_bytes": max(peaks)}


def print_checks(checks: dict) -> None:
    """Each number compared beside its limit, as the last lines on stderr."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)

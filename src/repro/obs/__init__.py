"""Observability layer: per-request trace records, per-station timelines,
and provenance-stamped bench lineage.

The paper's second prong is *implementation and measurement*: its
throughput-vs-hit-ratio inversions were found by instrumenting a real
cache.  This package is that instrument for the reproduction:

* :mod:`repro.obs.trace` — the structured per-request trace-record
  schema (request id, class, per-station enter/leave timestamps, MSHR
  parked interval) plus the fixed-capacity ring-buffer helpers the
  jitted simulators fill in-kernel and the collector the heapq oracles
  use, so trace equality is a differential twin contract.
* :mod:`repro.obs.metrics` — a small registry (counters, gauges,
  distribution sketches, unit-suffixed names) and the trace-derived
  per-station occupancy/utilization timelines and busy-period (convoy)
  statistics.
* :mod:`repro.obs.export` — Chrome/Perfetto ``trace_event`` JSON
  rendering for timeline inspection.
* :mod:`repro.obs.provenance` — git-sha / version / seed / config-hash
  stamping of ``benchmarks/run.py --json`` payloads, payload schema
  validation and the BENCH lineage diff.
* :mod:`repro.obs.streaming` — fixed-shape in-kernel streaming
  estimators (windowed/EWMA rates, count-min + SpaceSaving popularity
  sketch) threaded through the simulators behind ``sketch_cap=0``.
* :mod:`repro.obs.spans` — host spans of the program's own steps
  (lane set-up, transfers, decode, network compilation) with their
  counts, written into the JAX profiler's trace on the device's clock.
* :mod:`repro.obs.drift` — CUSUM / Page-Hinkley sequential change
  detectors over the estimator series.
* :mod:`repro.obs.profile` / :mod:`repro.obs.residuals` — online
  measured-profile recovery (sketch → Che cap→hit curve) and the
  model-vs-measured residual monitor.  These two sit *above* the
  cluster / hierarchy / latency layers and are therefore imported
  directly, not re-exported here (the package ``__init__`` must stay
  importable from ``repro.core.simulator``).

Tracing is **off by default** and bit-identical to the untraced
simulators when off; when on, every ring-buffer capacity is a static
(Python-int) shape so the compiled programs stay shape-static
(``tools/analysis/obs_lint.py`` gates this).
"""

from __future__ import annotations

from repro.obs.drift import Cusum, PageHinkley, cusum_scan, page_hinkley_scan
from repro.obs.metrics import DistSketch, Metrics
from repro.obs.streaming import (PyStreamSketch, SketchEstimates,
                                 sketch_trace, sketch_trace_py)
from repro.obs.trace import TraceRecords, make_records, trace_from_rings

__all__ = [
    "Cusum",
    "DistSketch",
    "Metrics",
    "PageHinkley",
    "PyStreamSketch",
    "SketchEstimates",
    "TraceRecords",
    "cusum_scan",
    "make_records",
    "page_hinkley_scan",
    "sketch_trace",
    "sketch_trace_py",
    "trace_from_rings",
]

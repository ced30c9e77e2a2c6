"""The program's own host spans (``repro.*``, written by
``repro.obs.spans``) in a kept profiler trace, and the per-layer numbers
they give.

``tracing.load`` keeps only the benchmark's spans, so these numbers are
read from the ``.xplane.pb`` that ``run.py --trace 1 --keep-trace DIR``
keeps, from the root of a checkout:

    python3 -m chipbench.program_spans DIR/<file>.xplane.pb

prints one JSON line: each number below that the trace holds, the share
of each benchmark span's host time before its program's first run that
program spans cover, and the device's idle gaps by the innermost open
span of either kind (a program span under its full name). Every count a
program span carries feeds one number in ``READERS``.

This module is a stop-gap beside the harness's own trace path. Once
``tracing.Event`` carries ``stats`` and ``tracing.load`` keeps the
``repro.*`` host events in the ``TraceView``, each ``READERS`` entry
becomes a ``metrics/<name>.py`` file calling the same function with the
same ``(view, program)`` arguments, ``run.py`` passes both kinds of span
to ``tracing.idle_gaps``, and ``load``, ``ProgramSpan`` and ``idle_gaps``
here are deleted.
"""

from __future__ import annotations

import json
import math
import sys

from chipbench import tracing
from chipbench.bench import SPAN_PREFIX, metric_reader
from chipbench.tracing import Event

PREFIX = "repro."
# the Pallas event kernel, as event_kernel_ns finds it
EVENT_KERNEL = metric_reader("event_kernel_ns").KERNEL
# benchmark span -> the program it dispatches, as the dispatch_ms metrics
DISPATCHES = {"replay.dispatch": metric_reader("dispatch_ms.replay").PROGRAM,
              "sim.call": metric_reader("dispatch_ms.sim").PROGRAM}


class ProgramSpan(Event):
    """A program span with the counts attached to it."""

    __slots__ = ("stats",)

    def __init__(self, name: str, start: float, end: float, stats: dict):
        super().__init__(name, start, end)
        self.stats = stats


def load(path: str) -> tuple:
    """(the harness's ``TraceView``, the program spans in its window)."""
    from jax.profiler import ProfileData

    view = tracing.load(path)
    program = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                program.extend(
                    ProgramSpan(e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events if e.name.startswith(PREFIX))
    lo, hi = view.window()
    return view, sorted((s for s in program if lo <= s.start and s.end <= hi),
                        key=lambda s: s.start)


def named(program: list, name: str) -> list:
    return [s for s in program if s.name == PREFIX + name]


def mean_ms(spans: list):
    return sum(s.dur for s in spans) / len(spans) * 1e-6 if spans else None


def per_span_ms(view, program: list, name: str, outer: str):
    """Summed time of the program's ``name`` spans inside the benchmark's
    ``outer`` spans, per ``outer`` span (ms)."""
    outers = view.spans_named(outer)
    inner = [s for s in named(program, name)
             if any(o.start <= s.start and s.end <= o.end for o in outers)]
    return sum(s.dur for s in inner) / len(outers) * 1e-6 if inner else None


def counted(program: list, name: str, *keys: str) -> tuple:
    """(summed ns, summed product of the ``keys`` counts) over the
    program's ``name`` spans."""
    spans = named(program, name)
    return (sum(s.dur for s in spans),
            sum(math.prod(s.stats.get(k, 0) for k in keys) for s in spans))


def ns_per(program: list, name: str, *keys: str):
    """Span time per unit counted (ns)."""
    ns, n = counted(program, name, *keys)
    return ns / n if n else None


def gb_per_s(program: list, name: str, key: str):
    """Bytes counted per span nanosecond, which is GB/s."""
    ns, n = counted(program, name, key)
    return n / ns if n and ns else None


def event_ns(view, program: list):
    """Event kernel device time per simulated event (ns)."""
    events = sum(s.stats.get("events_count", 0)
                 for s in named(program, "sim.collect"))
    return tracing.device_ns(tracing.in_window(view), EVENT_KERNEL, events)


READERS = {
    "lane_setup_ms.replay":
        lambda v, p: mean_ms(named(p, "replay.lane_inputs")),
    "lane_setup_ns.replay": lambda v, p: ns_per(
        p, "replay.lane_inputs", "lanes_count", "requests_count"),
    "h2d_ms.replay": lambda v, p: mean_ms(named(p, "replay.h2d")),
    "h2d_gbps.replay": lambda v, p: gb_per_s(p, "replay.h2d", "h2d_bytes"),
    "unpack_ms.replay": lambda v, p: mean_ms(named(p, "replay.unpack")),
    "unpack_gbps.replay": lambda v, p: gb_per_s(
        p, "replay.unpack", "d2h_bytes"),
    "network_ms.replay": lambda v, p: per_span_ms(
        v, p, "harness.empirical_network", "replay.decode"),
    "network_ns.replay": lambda v, p: ns_per(
        p, "harness.empirical_network", "requests_count"),
    "compile_network_ms.sim": lambda v, p: per_span_ms(
        v, p, "sim.compile_network", "sim.call"),
    "tables_ms.sim": lambda v, p: per_span_ms(v, p, "sim.tables", "sim.call"),
    "event_ns.sim": event_ns,
}


def covered_pct(view, program: list, outer: str, pattern: str):
    """Share of the host time from each ``outer`` span's start to its
    program's first run on the device that program spans cover (%)."""
    runs = tracing.matching(tracing.modules_in_window(view), pattern)
    total = covered = 0.0
    for o in view.spans_named(outer):
        run = tracing.first_after(runs, o.start)
        if run is None:
            continue
        total += run.start - o.start
        covered += tracing.busy_ns(tracing.clip(program, o.start, run.start))
    return 100.0 * covered / total if total else None


def idle_gaps(view, program: list) -> list:
    """``tracing.idle_gaps`` over the spans of both kinds: a program span
    under its full name, a benchmark span as the harness names it."""
    lo, hi = view.window()
    tagged = [Event(SPAN_PREFIX + s.name, s.start, s.end) for s in program]
    return tracing.idle_gaps(tracing.in_window(view), view.spans + tagged,
                             lo, hi)


def read(path: str) -> dict:
    view, program = load(path)
    metrics = {name: f(view, program) for name, f in READERS.items()}
    covered = {outer: covered_pct(view, program, outer, pattern)
               for outer, pattern in DISPATCHES.items()}
    return {"metrics": {k: v for k, v in metrics.items() if v is not None},
            "covered_pct": {k: v for k, v in covered.items() if v is not None},
            "idle_gaps": idle_gaps(view, program)}


if __name__ == "__main__":
    for path in sys.argv[1:]:
        print(json.dumps({"trace": path, **read(path)}))

"""From a profiler trace to the numbers the per-layer metrics read.

``capture`` records the profiler's trace around the traced questions;
``load`` turns the written ``.xplane.pb`` into a ``TraceView``: the device
operations of each chip and the benchmark's own host spans, on the
profiler's one clock (nanoseconds). The reductions below (busy time as the
union of device-op intervals, kernel time, idle gaps by the span that was
open, the first device event after a span began) are what every metric
file calls, so every PR computes a number in the same way.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from pathlib import Path

from chipbench.bench import SPAN_PREFIX, load_json

PEAKS = Path(__file__).resolve().parent / "peaks.json"
# the device plane's line whose events are the operations that ran
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def peaks(device_kind: str) -> dict:
    table = load_json(PEAKS)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


@contextlib.contextmanager
def capture():
    """Record a profiler trace of the block; yields a dict whose ``path``
    is the ``.xplane.pb`` once the block has ended. The directory lives
    under the process's temporary directory until ``discard``.

    Host tracing keeps the annotations (level 1) and no Python function
    events."""
    import jax

    out = {"dir": tempfile.mkdtemp(prefix="chipbench-trace-")}
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    jax.profiler.start_trace(out["dir"], profiler_options=options)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(out["dir"], "**", "*.xplane.pb"),
                          recursive=True)
        out["path"] = found[0] if found else None


def discard(out: dict) -> None:
    shutil.rmtree(out["dir"], ignore_errors=True)


class Event:
    __slots__ = ("name", "start", "end")

    def __init__(self, name: str, start: float, end: float):
        self.name, self.start, self.end = name, start, end

    @property
    def dur(self) -> float:
        return self.end - self.start


class TraceView:
    """Device operations per chip, XLA module runs, and benchmark spans."""

    def __init__(self, chips: dict, modules: dict, spans: list):
        self.chips = chips      # plane name -> [Event] sorted by start
        self.modules = modules  # plane name -> [Event]
        self.spans = sorted(spans, key=lambda e: e.start)

    def ops(self, chip: str | None = None) -> list:
        return self.chips[chip or self.first_chip()]

    def first_chip(self) -> str:
        return sorted(self.chips)[0]

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == SPAN_PREFIX + name]

    def window(self):
        (w,) = self.spans_named("window")
        return w.start, w.end


def load(path: str) -> TraceView:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, modules, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chips[plane.name] = sorted(
                        (Event(e.name, e.start_ns, e.end_ns)
                         for e in line.events), key=lambda e: e.start)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = sorted(
                        (Event(e.name, e.start_ns, e.end_ns)
                         for e in line.events), key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return TraceView(chips, modules, spans)


def clip(events, lo: float, hi: float) -> list:
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def union(events) -> list:
    """Merged [start, end) intervals covered by the events."""
    out = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return out


def busy_ns(events) -> float:
    return sum(b - a for a, b in union(events))


def matching(events, pattern: str) -> list:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def first_after(events, t: float):
    """The first event that starts at or after ``t`` (events sorted)."""
    i = bisect.bisect_left([e.start for e in events], t)
    return events[i] if i < len(events) else None


def idle_gaps(events, spans, lo: float, hi: float) -> list:
    """Device idle time in [lo, hi), summed by the innermost benchmark span
    open in each part of each gap ("no span" where none is):
    [[name, seconds]], longest first."""
    spans = [s for s in spans if s.name != SPAN_PREFIX + "window"]
    cuts = sorted({s.start for s in spans} | {s.end for s in spans})
    by_name: dict = defaultdict(float)
    t = lo
    for a, b in union(clip(events, lo, hi)) + [[hi, hi]]:
        if a > t:
            points = [t] + [c for c in cuts if t < c < a] + [a]
            for x, y in zip(points, points[1:]):
                open_ = [s for s in spans if s.start <= x < s.end]
                name = (max(open_, key=lambda s: s.start).name[
                    len(SPAN_PREFIX):] if open_ else "no span")
                by_name[name] += (y - x) * 1e-9
        t = max(t, b)
    return sorted(([k, v] for k, v in by_name.items()),
                  key=lambda kv: -kv[1])[:10]


def top_ops(events, n: int = 10) -> list:
    """[[name, seconds]] of the device operations that took most time."""
    by_name: dict = defaultdict(float)
    for e in events:
        by_name[e.name] += e.dur * 1e-9
    return sorted(([k, v] for k, v in by_name.items()),
                  key=lambda kv: -kv[1])[:n]


def in_window(view: TraceView) -> list:
    """Device operations on the first chip, in the window."""
    lo, hi = view.window()
    return clip(view.ops(), lo, hi) if view.chips else []


def modules_in_window(view: TraceView) -> list:
    """Runs of XLA modules (programs) on the first chip, in the window."""
    if not view.modules:
        return []
    lo, hi = view.window()
    return clip(view.modules[sorted(view.modules)[0]], lo, hi)


def dispatch_ms(view: TraceView, span_name: str, pattern: str):
    """Mean host time from each ``span_name`` span's start to the start of
    the first run of a program matching ``pattern`` after it, in ms."""
    ops = matching(modules_in_window(view), pattern)
    gaps = []
    for s in view.spans_named(span_name):
        e = first_after(ops, s.start)
        if e is not None:
            gaps.append((e.start - s.start) * 1e-6)
    return sum(gaps) / len(gaps) if gaps else None


def device_ns(events, pattern: str, work: int):
    """Summed device time of the events matching ``pattern``, per unit of
    ``work`` (ns)."""
    hit = matching(events, pattern)
    return sum(e.dur for e in hit) / work if hit and work else None


def idle_pct(view: TraceView):
    if not view.chips:
        return None
    lo, hi = view.window()
    return 100.0 * (1.0 - busy_ns(in_window(view)) / (hi - lo))


def span_ms(view: TraceView, span_name: str):
    spans = view.spans_named(span_name)
    return sum(s.dur for s in spans) / len(spans) * 1e-6 if spans else None

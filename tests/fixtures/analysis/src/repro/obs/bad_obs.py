"""Fixture for the obs lint: unit-suffix and ring-static violations."""

import functools

import jax

from repro.obs import spans


class BadSchema:
    parked: float = 0.0  # obs-units: time-like field without a unit
    parked_us: float = 0.0  # clean: carries a time suffix
    branch: int = 0  # clean: not a time-like stem
    win_hits: int = 0  # obs-units: estimator field without a unit
    ewma_hit: float = 0.0  # obs-units: EWMA field without a unit
    win_hit_count: int = 0  # clean: counter suffix
    window_id: int = 0  # clean: identity suffix
    ewma_hit_frac: float = 0.0  # clean: fraction suffix


@functools.partial(jax.jit, static_argnames=("n_requests",))
def bad_ring(x, trace_cap: int = 0, n_requests: int = 0):
    # obs-ring-static: trace_cap missing from static_argnames (flagged
    # at the def line above)
    return x


@functools.partial(jax.jit, static_argnames=("trace_cap",))
def good_ring(x, trace_cap: int = 0):  # clean: trace_cap is static
    return x


@functools.partial(jax.jit, static_argnames=("sketch_cap",))
def bad_sketch(x, sketch_cap: int = 0, window_us: float = 0.0):
    # obs-ring-static: window_us missing from static_argnames (flagged
    # at the def line above)
    return x


@functools.partial(jax.jit, static_argnames=("sketch_cap", "window_us"))
def good_sketch(x, sketch_cap: int = 0, window_us: float = 0.0):
    # clean: both sketch knobs are static
    return x


def emit(metrics):
    metrics.count("events")  # obs-units: metric name without suffix
    metrics.count("events_count")  # clean: counter suffix
    metrics.gauge("depth_count", 1.0)  # clean
    metrics.observe("sojourn_us", 2.0)  # clean


def span_counts(n):
    with spans.span("replay.h2d", lanes=n) as sp:  # obs-units: span count without suffix
        sp.count(h2d=n)  # obs-units: span count call without suffix
        sp.count(h2d_bytes=n)  # clean: byte suffix
    with spans.span("replay.lane_inputs", lanes_count=n):  # clean: counted
        pass

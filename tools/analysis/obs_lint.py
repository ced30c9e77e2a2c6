"""Observability conventions lint (the telemetry prong's gate).

Two rules over ``src/repro``:

``obs-units``
    Metric names are self-describing only if they carry a unit suffix
    (``_ns``/``_us``/``_ms``/``_s``/``_rate``/``_count``/``_frac``/
    ``_ratio``/``_bytes`` — the :data:`repro.obs.metrics.UNIT_SUFFIXES`
    convention).  Flags (a) string-literal metric names passed to
    ``<...>.metrics.count/gauge/observe(...)`` registry calls that lack
    one, and (b) time-like record fields declared in ``repro.obs``
    schema classes (``enter``/``leave``/``parked``/``sojourn``/
    ``elapsed``/``latency``/``duration`` stems) without a time suffix —
    a trace schema whose timestamps don't say their unit is how µs/ns
    bugs get in.

``obs-units`` also covers the counts of the program's host spans: each
keyword name passed to ``repro.obs.spans.span(...)``, or to ``.count(...)``
on the span it yields (``with spans.span(...) as sp: sp.count(...)``),
carries a unit suffix.

``obs-units`` additionally covers estimator state: fields of
``repro.obs`` schema classes whose stem marks them as windowed or EWMA
estimator state (``win``/``window``/``ewma``) must say what they hold —
a unit suffix, or an ``_id``/``_index``/``_key`` identity suffix.

``obs-ring-static``
    Every in-kernel observability buffer must be shape-static under
    jit: a ``jax.jit``-decorated function that takes any of the
    :data:`_STATIC_OBS_PARAMS` (``trace_cap``, ``sketch_cap``,
    ``window_us``) must list it in ``static_argnames`` — a traced
    capacity would make the ring/sketch shapes dynamic (and ``if
    trace_cap:`` gating silently truthy on the tracer); a traced
    ``window_us`` would retrace the tumbling-window arithmetic per
    value anyway.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import List, Mapping, Optional, Tuple

from .base import Note, SourceFile, Violation

# Mirrors repro.obs.metrics.UNIT_SUFFIXES (kept literal: the analysis
# suite is stdlib-only and never imports the code under test).
UNIT_SUFFIXES = ("_ns", "_us", "_ms", "_s", "_rate", "_count", "_frac",
                 "_ratio", "_bytes")

_REGISTRY_METHODS = {"count", "gauge", "observe"}
_TIME_STEMS = ("enter", "leave", "parked", "sojourn", "elapsed", "latency",
               "duration", "start", "end", "wall", "compile")
_TIME_SUFFIXES = ("_ns", "_us", "_ms", "_s")
# Estimator-state stems: windowed / EWMA fields must say what they hold —
# a unit suffix, or an identity suffix for ids and sketch keys.
_ESTIMATOR_STEMS = ("win", "window", "ewma")
_IDENTITY_SUFFIXES = ("_id", "_index", "_key")
# In-kernel observability knobs that size compiled buffers (or, for
# window_us, parameterize shape-adjacent arithmetic): must be static.
_STATIC_OBS_PARAMS = ("trace_cap", "sketch_cap", "window_us")


def _has_unit_suffix(name: str) -> bool:
    return any(name.endswith(s) and len(name) > len(s)
               for s in UNIT_SUFFIXES)


def _is_metrics_registry(node: ast.AST) -> bool:
    """True for ``metrics`` / ``self.metrics`` / ``eng.metrics`` — the
    receiver idiom of :class:`repro.obs.metrics.Metrics` calls."""
    if isinstance(node, ast.Name):
        return node.id in ("metrics", "_metrics")
    if isinstance(node, ast.Attribute):
        return node.attr in ("metrics", "_metrics")
    return False


def _check_metric_calls(src: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    assert src.tree is not None
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (isinstance(fn, ast.Attribute)
                and fn.attr in _REGISTRY_METHODS
                and _is_metrics_registry(fn.value)):
            continue
        if not node.args:
            continue
        name_arg = node.args[0]
        if isinstance(name_arg, ast.Constant) \
                and isinstance(name_arg.value, str) \
                and not _has_unit_suffix(name_arg.value):
            out.append(Violation(
                "obs-units", src.path, node.lineno,
                f"metric name {name_arg.value!r} lacks a unit suffix "
                f"({', '.join(UNIT_SUFFIXES)}) — see repro.obs.metrics",
            ))
    return out


def _span_modules(tree: ast.AST) -> set:
    """Names bound to the ``repro.obs.spans`` module."""
    return {a.asname or a.name for node in ast.walk(tree)
            if isinstance(node, ast.ImportFrom) and node.module == "repro.obs"
            for a in node.names if a.name == "spans"}


def _check_span_counts(src: SourceFile) -> List[Violation]:
    """Count names given to a span, when it opens or through ``count``."""
    out: List[Violation] = []
    assert src.tree is not None
    modules = _span_modules(src.tree)
    if not modules:
        return out

    def opens_span(call: ast.AST) -> bool:
        fn = getattr(call, "func", None)
        return (isinstance(call, ast.Call) and isinstance(fn, ast.Attribute)
                and fn.attr == "span" and isinstance(fn.value, ast.Name)
                and fn.value.id in modules)

    handles = {item.optional_vars.id
               for node in ast.walk(src.tree)
               if isinstance(node, (ast.With, ast.AsyncWith))
               for item in node.items
               if opens_span(item.context_expr)
               and isinstance(item.optional_vars, ast.Name)}
    for node in ast.walk(src.tree):
        fn = getattr(node, "func", None)
        if not (opens_span(node)
                or (isinstance(node, ast.Call)
                    and isinstance(fn, ast.Attribute) and fn.attr == "count"
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id in handles)):
            continue
        for kw in node.keywords:
            if kw.arg is not None and not _has_unit_suffix(kw.arg):
                out.append(Violation(
                    "obs-units", src.path, node.lineno,
                    f"span count {kw.arg!r} lacks a unit suffix "
                    f"({', '.join(UNIT_SUFFIXES)}) — see repro.obs.spans",
                ))
    return out


def _check_schema_fields(src: SourceFile) -> List[Violation]:
    """Time-like fields of obs schema classes must carry a time suffix."""
    out: List[Violation] = []
    assert src.tree is not None
    for cls in src.tree.body:
        if not isinstance(cls, ast.ClassDef):
            continue
        for stmt in cls.body:
            if not (isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)):
                continue
            name = stmt.target.id
            if name.startswith("_"):
                continue
            stem = name.split("_")[0]
            if stem in _TIME_STEMS and not any(
                    name.endswith(s) for s in _TIME_SUFFIXES):
                out.append(Violation(
                    "obs-units", src.path, stmt.lineno,
                    f"time-like schema field '{cls.name}.{name}' lacks a "
                    f"time-unit suffix ({', '.join(_TIME_SUFFIXES)})",
                ))
            elif stem in _ESTIMATOR_STEMS and not (
                    _has_unit_suffix(name)
                    or any(name.endswith(s) for s in _IDENTITY_SUFFIXES)):
                out.append(Violation(
                    "obs-units", src.path, stmt.lineno,
                    f"estimator state field '{cls.name}.{name}' lacks a "
                    f"unit suffix ({', '.join(UNIT_SUFFIXES)}) or identity "
                    f"suffix ({', '.join(_IDENTITY_SUFFIXES)})",
                ))
    return out


def _jit_static_argnames(dec: ast.AST) -> Optional[List[str]]:
    """``static_argnames`` of a jit decorator, or None if not a jit.

    Handles ``@jax.jit``, ``@jit``, ``@functools.partial(jax.jit, ...)``
    and ``@partial(jit, static_argnames=(...))``.
    """
    def leaf(n: ast.AST) -> str:
        if isinstance(n, ast.Attribute):
            return n.attr
        if isinstance(n, ast.Name):
            return n.id
        return ""

    if leaf(dec) == "jit":
        return []
    if isinstance(dec, ast.Call):
        if leaf(dec.func) == "jit":
            call = dec
        elif leaf(dec.func) == "partial" and dec.args \
                and leaf(dec.args[0]) == "jit":
            call = dec
        else:
            return None
        for kw in call.keywords:
            if kw.arg in ("static_argnames", "static_argnums"):
                names: List[str] = []
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) \
                            and isinstance(n.value, str):
                        names.append(n.value)
                return names
        return []
    return None


def _check_ring_static(src: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    assert src.tree is not None
    for node in ast.walk(src.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        params = {a.arg for a in (node.args.posonlyargs + node.args.args
                                  + node.args.kwonlyargs)}
        obs_params = [p for p in _STATIC_OBS_PARAMS if p in params]
        if not obs_params:
            continue
        for dec in node.decorator_list:
            statics = _jit_static_argnames(dec)
            if statics is None:
                continue
            for p in obs_params:
                if p not in statics:
                    out.append(Violation(
                        "obs-ring-static", src.path, node.lineno,
                        f"jit-decorated '{node.name}' takes {p} but does "
                        f"not list it in static_argnames — in-kernel "
                        f"observability buffers must be compile-time "
                        f"static",
                    ))
    return out


def run(
    root: Path, sources: Mapping[Path, SourceFile]
) -> Tuple[List[Violation], List[Note]]:
    violations: List[Violation] = []
    obs_dir = root / "src" / "repro" / "obs"
    checked = 0
    for path in sorted(sources):
        src = sources[path]
        if src.tree is None:
            continue
        checked += 1
        violations.extend(_check_metric_calls(src))
        violations.extend(_check_span_counts(src))
        violations.extend(_check_ring_static(src))
        if str(path).startswith(str(obs_dir)):
            violations.extend(_check_schema_fields(src))
    notes = [Note(f"obs-lint: {checked} files (metric and span-count "
                  f"suffixes, trace-ring static shapes)")]
    return violations, notes

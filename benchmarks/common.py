"""Shared benchmark helpers: CSV emission + default sweep settings."""

from __future__ import annotations

import os
import time

import numpy as np

# keep benchmark wall time sane on 1 CPU core; override for precision runs
N_SIM_REQUESTS = int(os.environ.get("REPRO_BENCH_SIM_REQUESTS", 16_000))
P_GRID = np.array([0.4, 0.55, 0.7, 0.8, 0.9, 0.95, 0.99])
DISKS = (500.0, 100.0, 5.0)


def emit(name: str, us_per_call: float, derived: str) -> None:
    """The scaffold's CSV contract: name,us_per_call,derived."""
    print(f"{name},{us_per_call:.3f},{derived}")


def row(*cols) -> None:
    print(",".join(str(c) for c in cols))


class timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.elapsed = time.time() - self.t0


class compile_monitor:
    """Wall / compile-time split for a benchmark block.

    Sums the durations of JAX's compile-path events (``jax.monitoring``
    ``/jax/core/compile/...``: tracing, lowering, backend compilation or
    the persistent-cache read that replaces it) that fire while the block
    runs, so bench
    artifacts can report how much of a bench's wall time was tracing +
    XLA compilation versus actual execution.  Listener registration is
    process-global and permanent (jax exposes no unregister), so one
    listener is installed lazily and dispatches to whichever monitors
    are currently active; falls back to a zero compile split when the
    monitoring hooks are unavailable.
    """

    _installed = False
    _active: list = []

    def __enter__(self):
        self.compile_s = 0.0
        self.wall_s = 0.0
        self.t0 = time.time()
        cls = type(self)
        if not cls._installed:
            try:
                import jax

                jax.monitoring.register_event_duration_secs_listener(
                    cls._on_event
                )
                cls._installed = True
            except Exception:
                pass
        cls._active.append(self)
        return self

    @classmethod
    def _on_event(cls, event: str, duration: float, **kw) -> None:
        # tracing, lowering and backend compilation (a persistent-cache
        # read included); not the cache's "compile_time_saved" estimate
        if event.startswith("/jax/core/compile/"):
            for mon in cls._active:
                mon.compile_s += duration

    def __exit__(self, *a):
        self.wall_s = time.time() - self.t0
        type(self)._active.remove(self)

    @property
    def split(self) -> dict:
        """``{wall_s, compile_s, run_s}`` for the monitored block."""
        return {
            "wall_s": self.wall_s,
            "compile_s": self.compile_s,
            "run_s": max(self.wall_s - self.compile_s, 0.0),
        }

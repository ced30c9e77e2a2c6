"""Pallas TPU kernels: the replay and event-simulation engines
(:mod:`~repro.kernels.replay`, :mod:`~repro.kernels.event_sim`) on the
indexed-state interface of :mod:`~repro.indexed_state`, plus the model
kernels behind :mod:`~repro.kernels.ops`.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """Whether kernels compile for the chip (else they run interpreted or
    through their twins): the one platform test every caller uses."""
    return jax.default_backend() == "tpu"

"""Traffic generators of the benchmark: everything a cell's inputs are made of.

The key, coin and fetch-window streams follow the program's own generators
(``zipf_trace``, ``coin_stream``, ``miss_window_stream`` of
``repro.core.harness``), copied here so that the inputs of every later check
stay what they are whatever the program does to its copies. Every stream
comes from a ``numpy.random.SeedSequence`` whose entropy is ``[seed, i]``:
any whole number is a seed, and question ``i`` of a run never shares a
stream with another question.
"""

from __future__ import annotations

import numpy as np


def question_seq(seed: int, i: int) -> np.random.SeedSequence:
    """The seed sequence of question ``i`` of a run started with ``seed``."""
    return np.random.SeedSequence([int(seed), int(i)])


def zipf_trace(n: int, key_space: int, theta: float,
               seq: np.random.SeedSequence) -> np.ndarray:
    """(n,) int32 Zipf(theta) keys over ``key_space`` keys, key ids shuffled
    so that an id says nothing of its popularity rank."""
    rng = np.random.default_rng(seq.spawn(3)[0])
    probs = np.arange(1, key_space + 1, dtype=np.float64) ** (-theta)
    probs /= probs.sum()
    perm = rng.permutation(key_space)
    return perm[rng.choice(key_space, size=n, p=probs)].astype(np.int32)


def coin_stream(n: int, seq: np.random.SeedSequence) -> np.ndarray:
    """(n,) float32 admission coins in [0, 1), independent of the keys."""
    return np.random.default_rng(seq.spawn(3)[1]).random(n, dtype=np.float32)


def miss_window_stream(n: int, mean_requests: float,
                       seq: np.random.SeedSequence) -> np.ndarray:
    """(n,) int32 fetch windows: Exp(mean) rounded to whole requests."""
    rng = np.random.default_rng(seq.spawn(3)[2])
    return np.round(rng.exponential(mean_requests, n)).astype(np.int32)


def grid(spec) -> np.ndarray:
    """A parameter grid as a traffic file writes it: a list of values, or
    ``{"lo", "hi", "count", "scale"}`` with ``scale`` "log" (rounded to whole
    numbers) or "linear"."""
    if isinstance(spec, list):
        return np.asarray(spec, np.float64)
    lo, hi, count = float(spec["lo"]), float(spec["hi"]), int(spec["count"])
    if spec.get("scale", "linear") == "log":
        return np.geomspace(lo, hi, count).round()
    return np.linspace(lo, hi, count)


def lane_seeds(seed: int, n_questions: int, per_question: int,
               high: int) -> np.ndarray:
    """(n_questions, per_question) distinct simulation seeds in [0, high):
    no two questions of a run simulate the same lane."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1 << 20]))
    flat = rng.choice(high, size=n_questions * per_question, replace=False)
    return flat.reshape(n_questions, per_question)

"""Simulation questions: the configuration's closed queueing network over a
grid of hit ratios, through ``repro.core.simulator.simulate_network``.

A question is one ``simulate_network`` call over the mix's (p_hit x seed)
grid at the configuration's MPL; its answer is the mean throughput over
the seeds and its 95% confidence half-width, per hit ratio. Each question
takes the next lane seeds drawn from the run's seed, so no two questions
simulate the same lanes.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from chipbench import gen
from chipbench.bench import span

# lane seeds stay below 2^21: the program makes each lane's seed as
# seed * 1000 + p index in int32
SEED_HIGH = 1 << 21


def program_network(config: dict):
    """The configuration as the program's ``ClosedNetwork``."""
    from repro.core.queueing import QUEUE, THINK, Branch, ClosedNetwork, Station

    stations = [Station(
        s["name"], QUEUE if s["kind"] == "queue" else THINK,
        float(s["service_us"]), bound=s.get("bound", "exact"), dist=s["dist"],
        dist_params=tuple(s["pareto"]) if s["dist"] == "pareto" else (),
        servers=int(s.get("servers", 1))) for s in config["stations"]]
    branches = [Branch(b["name"],
                       lambda p, a=float(b["prob"][0]), c=float(b["prob"][1]):
                       a + c * p, tuple(b["visits"]))
                for b in config["branches"]]
    return ClosedNetwork(config["name"], tuple(stations), tuple(branches),
                         int(config["mpl"]))


def _draw(u, mean, did, alpha, lo, hi, raw):
    """A station's service draw (ns) from its uniform, as the event kernel
    computes it on the vector unit."""
    import jax.numpy as jnp

    s_exp = -jnp.log(u)
    ratio = 1.0 - (lo / hi) ** alpha
    s_par = lo * (1.0 - u * ratio) ** (-1.0 / alpha) / raw
    unit = jnp.where(did == 0, np.float32(1.0),
                     jnp.where(did == 1, s_exp, s_par))
    return jnp.maximum(jnp.round(unit * mean), np.float32(1.0))


def _device_draws(params: tuple) -> np.ndarray:
    """(2^24,) int32: one station's draw for every 24-bit uniform, computed
    on the device (a Pallas kernel on a TPU, as the event kernel's)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from chipbench.reference import sim_ref

    rows, block = (1 << 24) // 128, 2048
    z = np.arange(1 << 24, dtype=np.int32).reshape(rows, 128)
    u = jnp.asarray(sim_ref.uniform_of(z))
    par = jnp.asarray(np.asarray([params + (0.0, 0.0)], np.float32))
    if jax.devices()[0].platform != "tpu":
        out = jax.jit(lambda p, u: _draw(u, *(p[0, i] for i in range(6))))(
            par, u)
        return np.asarray(out).astype(np.int32).reshape(-1)

    def kernel(p_ref, u_ref, o_ref):
        u = u_ref[...]
        o_ref[...] = _draw(u, *(jnp.full(u.shape, p_ref[0, i])
                                for i in range(6))).astype(jnp.int32)

    out = pl.pallas_call(
        kernel, grid=(rows // block,),
        in_specs=[pl.BlockSpec((1, 8), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((block, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.int32))(par, u)
    return np.asarray(out).reshape(-1)


DIST_ID = {"det": 0, "exp": 1, "pareto": 2}


@functools.lru_cache(maxsize=None)
def _rounding(dist: str, mean: float, pareto: tuple) -> bytes:
    """The device's correction of each of the 2^24 draws of a station:
    int16 bytes of its whole ns less the reference's float32 formula's."""
    from chipbench.reference import sim_ref

    dev = _device_draws((mean, DIST_ID[dist]) + pareto)
    u = sim_ref.uniform_of(np.arange(1 << 24, dtype=np.int32))
    delta = dev - sim_ref.service_ns(dist, u, mean, tuple(
        np.float32(v) for v in pareto))
    if np.abs(delta).max() > np.iinfo(np.int16).max:
        raise ValueError(f"device draws of a {dist} station differ from "
                         f"float32 ones by {np.abs(delta).max()} ns")
    return delta.astype(np.int16).tobytes()


def chip_rounding(cfg: dict, p_hits) -> list:
    """Per hit ratio, {station: the device's correction of its draws}."""
    from chipbench.reference import sim_ref

    out = []
    for p in p_hits:
        net = sim_ref.F32Network(cfg, float(p))
        out.append({k: _rounding(dist, float(net.mean[k]), tuple(
            float(v) for v in (net.pareto[k] or (1.0, 1.0, 1.0, 1.0))))
            for k, dist in enumerate(net.dist) if dist != "det"})
    return out


def seed_summary(xs: np.ndarray) -> dict:
    """Per-lane float32 throughputs (seeds, p_hits) to the answer a user
    reads: the mean over the seeds and its 95% confidence half-width."""
    n_s = xs.shape[0]
    ci = (1.96 * xs.std(axis=0, ddof=1) / math.sqrt(n_s) if n_s > 1
          else np.zeros(xs.shape[1], xs.dtype))
    return {"throughput": np.asarray(xs.mean(axis=0), np.float64),
            "ci95": np.asarray(ci, np.float64)}


class Engine:
    unit = "sim"

    def __init__(self, files: dict, seed: int):
        self.cfg = cfg = files["config"]
        self.mix = mix = files["mix"]
        self.seed = seed
        self.p_hits = gen.grid(mix["p_hit"])
        self.n = int(mix["requests"])
        self.net = program_network(cfg)
        per_q = int(mix["seeds_per_question"])
        seeds = gen.lane_seeds(seed, int(mix["pool"]) + 1, per_q, SEED_HIGH)
        self.warm_seeds, self.pool = seeds[-1], seeds[:-1]
        self.sample_rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), 1 << 21]))
        self.kept = None  # (answer, lane seeds) of one question
        self.answered = 0
        self.rounding = None  # the device's rounding, made for the check

    @property
    def work_per_question(self) -> int:
        return len(self.p_hits) * len(self.pool[0]) * self.n

    def _ask(self, seeds) -> dict:
        from repro.core.simulator import simulate_network

        with span("sim.call"):
            res = simulate_network(
                self.net, self.p_hits, n_requests=self.n,
                seeds=tuple(int(s) for s in seeds),
                warmup_frac=float(self.cfg["warmup_frac"]),
                backend=self.mix["backend"])
        return {"throughput": np.asarray(res.throughput, np.float64),
                "ci95": np.asarray(res.ci95, np.float64)}

    def warm(self) -> None:
        self._ask(self.warm_seeds)

    def question(self, i: int) -> None:
        seeds = self.pool[i % len(self.pool)]
        answer = self._ask(seeds)
        self.last = (answer, seeds)
        self.answered += 1
        if self.sample_rng.random() * self.answered < 1.0:
            self.kept = self.last

    def keep_last(self) -> None:
        self.kept = self.last

    def _job(self, p: float, **kw) -> dict:
        return {"config": self.cfg, "p_hit": float(p), "n_requests": self.n,
                "warmup_frac": float(self.cfg["warmup_frac"]), **kw}

    def independent_jobs(self) -> list:
        """The mix's ``reference_seeds`` lanes of the reference's own
        random numbers, one job per hit ratio."""
        rng = np.random.default_rng(
            np.random.SeedSequence([int(self.seed), 1 << 22]))
        seeds = rng.integers(0, 1 << 62, int(self.mix["reference_seeds"]))
        return [self._job(p, seeds=[int(s) for s in seeds])
                for p in self.p_hits]

    def counter_jobs(self, seeds, **variant) -> list:
        """The program's own lanes on the stated counter stream, one job per
        hit ratio; ``variant`` makes the control's."""
        if self.rounding is None:
            self.rounding = chip_rounding(self.cfg, self.p_hits)
        return [self._job(p, stream="counter", rounding=self.rounding[i],
                          lane_seeds=[int(s) * 1000 + i for s in seeds],
                          **variant)
                for i, p in enumerate(self.p_hits)]

    @staticmethod
    def summarise_counter(jobs: list) -> dict:
        """Per-hit-ratio lists of lanes to the answer the program gives."""
        xs = np.asarray([[lane["x"] for lane in lanes] for lanes in jobs],
                        np.float32)
        return seed_summary(np.ascontiguousarray(xs.T))

    @staticmethod
    def summarise_independent(jobs: list) -> np.ndarray:
        return np.asarray([np.mean([lane["throughput"] for lane in lanes])
                           for lanes in jobs])

    @staticmethod
    def compare(got: dict, lanes: dict, independent: np.ndarray) -> dict:
        """The numbers compared. ``lane_gap``: the widest gap, over the hit
        ratios, of the mean or the half-width from the same lanes on the
        counter stream, relative to that mean. ``throughput_gap``: the
        widest relative gap of the mean from the reference's own lanes."""
        def worst(gap):
            gap = np.asarray(gap, np.float64)
            return float(np.max(np.where(np.isfinite(gap), gap, np.inf)))

        want = lanes["throughput"]
        return {
            "lane_gap": worst(np.maximum(
                np.abs(got["throughput"] - want),
                np.abs(got["ci95"] - lanes["ci95"])) / want),
            "throughput_gap": worst(np.abs(got["throughput"] - independent)
                                    / independent),
        }

    def _references(self, pool, seeds, **variant) -> tuple:
        """(counter-stream answer, independent means, control's answer or
        None): every job in one map over the pool."""
        from chipbench.reference import sim_ref

        n_p = len(self.p_hits)
        jobs = self.counter_jobs(seeds) + self.independent_jobs()
        if variant:
            jobs += self.counter_jobs(seeds, **variant)
        out = pool.map(sim_ref.run_job, jobs, chunksize=1)
        return (self.summarise_counter(out[:n_p]),
                self.summarise_independent(out[n_p:2 * n_p]),
                self.summarise_counter(out[2 * n_p:]) if variant else None)

    def check(self, pool, limits=None) -> tuple:
        """(numbers compared, information) once the window has closed;
        ``info["failed"]`` is 1 when the kept question is over a limit."""
        answer, seeds = self.kept
        lanes, independent, _ = self._references(pool, seeds)
        numbers = self.compare(answer, lanes, independent)
        info = {"lane_seeds": [int(s) for s in seeds],
                "program": {k: v.tolist() for k, v in answer.items()},
                "counter_stream": {k: v.tolist() for k, v in lanes.items()},
                "independent": independent.tolist(),
                "device_rounding": {
                    str(k): int(np.count_nonzero(np.frombuffer(v, np.int16)))
                    for k, v in self.rounding[0].items()}}
        info["failed"] = int(limits is not None and not all(
            numbers[k] <= v for k, v in limits.items()))
        return numbers, info

    def control(self, pool, **variant) -> dict:
        """The control's numbers: the counter-stream reference with
        ``variant`` (``precision="bf16"``, or a coarser ``clock_ns``) in
        the program's place, on the kept question's lanes."""
        _, seeds = self.kept
        lanes, independent, control = self._references(pool, seeds,
                                                       **variant)
        return self.compare(control, lanes, independent)

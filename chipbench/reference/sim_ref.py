"""Plain references of a simulation question: the closed queueing network
that the configuration file describes, as two event loops.

Semantics, as the configuration states them: think stations serve every
job at once; queue stations serve first come, first served with their
server count; service times are drawn per visit (det, exp or bounded
Pareto with the stated mean) on a clock of whole nanoseconds; a request
samples its route at its start; the first ``warmup_frac`` of completions
are left out of the throughput.

``closed`` draws its own random numbers (a numpy generator), so it agrees
with the program in distribution only. ``counter_lane`` draws the numbers
of the counter stream the mix states, in the order it states, with each
service time in float32 arithmetic, and so follows one lane of the program
event for event: the same lane seed gives the same throughput. A service
time one nanosecond off changes the order of later events, and the lane
then drifts off. The device's log and pow round otherwise than numpy's
(on a TPU v5e the vector unit's log moves 69% of exponential draws of
100 us, by up to 11 ns), so each station's draws take the device's
correction, from a table of all 2^24 uniforms that the benchmark's own
kernel computes (``rounding``). Only numpy is imported: this runs in host
worker processes.
"""

from __future__ import annotations

import heapq
import math
from collections import deque

import numpy as np

F32 = np.float32


def pareto_mean(alpha: float, lo: float, hi: float) -> float:
    if abs(alpha - 1.0) < 1e-9:
        return lo * hi / (hi - lo) * math.log(hi / lo)
    return (lo ** alpha * alpha * (lo ** (1 - alpha) - hi ** (1 - alpha))
            / ((alpha - 1.0) * (1.0 - (lo / hi) ** alpha)))


class Network:
    """The configuration's stations and routes at one hit ratio."""

    def __init__(self, config: dict, p_hit: float, clock_ns: int = 1):
        self.names = [s["name"] for s in config["stations"]]
        idx = {n: i for i, n in enumerate(self.names)}
        self.is_queue = [s["kind"] == "queue" for s in config["stations"]]
        self.servers = [int(s.get("servers", 1)) for s in config["stations"]]
        self.mean_ns = [s["service_us"] * 1e3 for s in config["stations"]]
        self.dist = [s["dist"] for s in config["stations"]]
        self.pareto = []
        for s, d in zip(config["stations"], self.dist):
            if d == "pareto":
                a, lo, hi = s["pareto"]
                self.pareto.append((a, lo, hi, 1.0 - (lo / hi) ** a,
                                    pareto_mean(a, lo, hi)))
            else:
                self.pareto.append(None)
        self.probs = np.maximum([b["prob"][0] + b["prob"][1] * p_hit
                                 for b in config["branches"]], 0.0)
        self.cum = np.cumsum(self.probs / self.probs.sum())
        self.routes = [[idx[v] for v in b["visits"]]
                       for b in config["branches"]]
        self.clock_ns = clock_ns

    def service(self, k: int, u: float) -> int:
        d = self.dist[k]
        if d == "det":
            unit = 1.0
        elif d == "exp":
            unit = -math.log(u)
        else:
            a, lo, _hi, ratio, raw = self.pareto[k]
            unit = lo * (1.0 - u * ratio) ** (-1.0 / a) / raw
        c = self.clock_ns
        return max(round(unit * self.mean_ns[k] / c), 1) * c

    def branch(self, u: float) -> int:
        return int(np.searchsorted(self.cum, u))


class _Uniforms:
    """Uniforms in (0, 1) drawn in blocks from one numpy generator."""

    def __init__(self, seed: int):
        self.rng = np.random.default_rng(seed)
        self.buf, self.i = [], 0

    def __call__(self) -> float:
        if self.i == len(self.buf):
            self.buf = np.clip(self.rng.random(1 << 16), 1e-7,
                               1 - 1e-7).tolist()
            self.i = 0
        self.i += 1
        return self.buf[self.i - 1]


def closed(net: Network, mpl: int, n_requests: int, warmup: int,
           seed: int) -> dict:
    """Closed loop of ``mpl`` jobs; throughput in requests per us."""
    u = _Uniforms(seed)
    heap = []  # (ready_ns, job)
    station = [0] * mpl
    route = [None] * mpl
    pos = [0] * mpl
    busy = [0] * len(net.names)
    waiting = [deque() for _ in net.names]
    for j in range(mpl):
        route[j] = net.routes[net.branch(u())]
        station[j] = route[j][0]
        heapq.heappush(heap, (net.service(station[j], u()), j))
    completed, warm_n, warm_t, now = 0, -1, 0, 0
    while completed < n_requests:
        now, j = heapq.heappop(heap)
        k = station[j]
        if net.is_queue[k]:
            if waiting[k]:
                w = waiting[k].popleft()
                heapq.heappush(heap, (now + net.service(k, u()), w))
            else:
                busy[k] -= 1
        pos[j] += 1
        if pos[j] == len(route[j]):
            completed += 1
            route[j] = net.routes[net.branch(u())]
            pos[j] = 0
        k = station[j] = route[j][pos[j]]
        if not net.is_queue[k] or busy[k] < net.servers[k]:
            busy[k] += net.is_queue[k]
            heapq.heappush(heap, (now + net.service(k, u()), j))
        else:
            waiting[k].append(j)
        if completed >= warmup and warm_n < 0:
            warm_n, warm_t = completed, now
    return {"throughput": (completed - warm_n) / max((now - warm_t) * 1e-3,
                                                     1e-6)}


# -- the counter stream ------------------------------------------------------
# A lane's uniform number ``ctr`` is the top 24 bits of
# fmix(fmix(seed + GOLDEN) + ctr * GOLDEN) over uint32 (fmix: the Murmur3
# finalizer with the constants below), as float32 clipped to
# [1e-7, 1 - 1e-7]. Numbers 0 .. mpl-1 pick the jobs' first routes and
# mpl .. 2 mpl-1 their first service times; event e then owns numbers
# 2 mpl + 3e (the service of the job its departure frees at a queue),
# + 1 (the service of its job's next visit) and + 2 (the next route, when
# the job's request completes).
GOLDEN = np.uint32(0x9E3779B9)
MIX = (np.uint32(0x21F0AAAD), np.uint32(0x735A2D97))
INV24 = F32(1.0 / (1 << 24))
U_LO, U_HI = F32(1e-7), F32(1.0 - 1e-7)


def fmix(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * MIX[0]
    x = x ^ (x >> np.uint32(15))
    x = x * MIX[1]
    return x ^ (x >> np.uint32(15))


def uniform_of(z24: np.ndarray) -> np.ndarray:
    """The float32 uniform of each 24-bit draw."""
    return np.clip(np.asarray(z24, np.int32).astype(F32) * INV24, U_LO, U_HI)


def counter_draws(lane_seed: int, start: int, n: int) -> np.ndarray:
    """(n,) 24-bit draws ``start`` .. ``start + n - 1`` of a lane."""
    base = fmix(np.asarray([lane_seed], np.uint32) + GOLDEN)
    ctr = np.arange(start, start + n, dtype=np.uint32)
    return (fmix(base + ctr * GOLDEN) >> np.uint32(8)).astype(np.int32)


def bf16_round(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even)."""
    bits = np.asarray(x, F32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(F32)


def service_ns(dist: str, u: np.ndarray, mean, pareto=None,
               clock_ns: int = 1, low: bool = False) -> np.ndarray:
    """(n,) int32 service times (whole ns, as the clock allows) for the
    float32 uniforms ``u``, in float32 arithmetic (``low``: the uniform
    and the unit-mean draw rounded to bfloat16)."""
    if low:
        u = bf16_round(u)
    if dist == "det":
        unit = np.ones_like(u)
    elif dist == "exp":
        unit = -np.log(u)
    else:
        alpha, lo, hi, raw = pareto
        ratio = F32(1.0) - (lo / hi) ** alpha
        unit = lo * (F32(1.0) - u * ratio) ** (F32(-1.0) / alpha) / raw
    if low:
        unit = bf16_round(unit)
    c = F32(clock_ns)
    return (np.maximum(np.round(unit * F32(mean) / c), F32(1.0))
            * c).astype(np.int32)


class F32Network:
    """The network at one hit ratio as float32 tables: mean service (ns),
    Pareto parameters and the cumulative route law, each rounded once to
    float32, and the service draws in float32 arithmetic (``precision``
    "bf16": the uniform and the unit draw rounded to bfloat16).

    ``rounding`` maps a station to the device's correction of each of its
    2^24 draws (int16 bytes: the device's whole ns less numpy's), added
    where the draw is the stated float32 one."""

    def __init__(self, config: dict, p_hit: float, clock_ns: int = 1,
                 precision: str = "float32", rounding: dict | None = None):
        net = Network(config, p_hit)
        self.routes, self.is_queue = net.routes, net.is_queue
        self.servers, self.dist = net.servers, net.dist
        self.mean = [F32(m) for m in net.mean_ns]
        self.pareto = [None if p is None else
                       (F32(p[0]), F32(p[1]), F32(p[2]), F32(p[4]))
                       for p in net.pareto]
        self.cum = [float(c) for c in np.cumsum(
            net.probs / net.probs.sum()).astype(F32)]
        self.clock_ns = clock_ns
        self.precision = precision
        stated = precision == "float32" and clock_ns == 1
        self.rounding = {int(k): np.frombuffer(v, np.int16)
                         for k, v in (rounding or {}).items() if stated}

    def draws(self, k: int, z24: np.ndarray) -> np.ndarray:
        """(n,) int32 service times of station ``k`` for the 24-bit draws
        ``z24``."""
        ns = service_ns(self.dist[k], uniform_of(z24), self.mean[k],
                        self.pareto[k], self.clock_ns,
                        self.precision == "bf16")
        if k in self.rounding:
            ns += self.rounding[k][z24]
        return ns

    def branch(self, u: float) -> int:
        return sum(c < u for c in self.cum)


def counter_lane(net: F32Network, mpl: int, n_requests: int, warmup: int,
                 lane_seed: int, block: int = 1 << 15) -> dict:
    """One lane on the counter stream: its float32 throughput (requests
    per us of simulated time after warm-up), requests completed and
    events."""
    n_k = len(net.dist)
    u, draws = [], [[] for _ in range(n_k)]

    def extend():
        fresh = counter_draws(lane_seed, len(u), block)
        u.extend(uniform_of(fresh).tolist())
        for k in range(n_k):
            draws[k].extend(net.draws(k, fresh).tolist())

    extend()
    routes, is_queue, servers = net.routes, net.is_queue, net.servers
    station, branch, pos = [0] * mpl, [0] * mpl, [0] * mpl
    heap = []  # (ready ns, job): the least, and the lowest job on a tie
    for j in range(mpl):
        b = net.branch(u[j])
        station[j], branch[j] = routes[b][0], b
        heap.append((draws[station[j]][mpl + j], j))
    heapq.heapify(heap)
    busy = [0] * n_k
    waiting = [deque() for _ in range(n_k)]
    max_events = n_requests * (max(map(len, routes)) + 2) * 3
    now, completed, events, ctr = 0, 0, 0, 2 * mpl
    elapsed, warm_elapsed, warm_completed = F32(0.0), F32(0.0), -1
    ms = F32(1e-3)
    while completed < n_requests and events < max_events:
        if ctr + 3 > len(u):
            extend()
        t, j = heapq.heappop(heap)
        elapsed = elapsed + F32(t - now) * ms
        now = t
        k = station[j]
        if is_queue[k]:
            if waiting[k]:
                heapq.heappush(heap, (now + draws[k][ctr],
                                      waiting[k].popleft()))
            else:
                busy[k] -= 1
        route = routes[branch[j]]
        if pos[j] + 1 < len(route):
            pos[j] += 1
            k = route[pos[j]]
        else:
            completed += 1
            b = net.branch(u[ctr + 2])
            branch[j], pos[j], k = b, 0, routes[b][0]
        station[j] = k
        if not is_queue[k] or busy[k] < servers[k]:
            busy[k] += is_queue[k]
            heapq.heappush(heap, (now + draws[k][ctr + 1], j))
        else:
            waiting[k].append(j)
        ctr += 3
        events += 1
        if completed >= warmup and warm_completed < 0:
            warm_completed, warm_elapsed = completed, elapsed
    span = max(F32(elapsed - warm_elapsed), F32(1e-6))
    x = F32(completed - warm_completed) / span
    return {"x": float(x), "completed": completed, "events": events}


def run_job(job: dict) -> list:
    """Reference lanes of a simulation question at one hit ratio:
    ``job["stream"]`` "counter" follows the program's lanes
    ``job["lane_seeds"]``; otherwise each lane draws from its own generator,
    one per ``job["seeds"]``."""
    warmup = int(job["n_requests"] * job["warmup_frac"])
    mpl = job["config"]["mpl"]
    if job.get("stream") == "counter":
        net = F32Network(job["config"], job["p_hit"], job.get("clock_ns", 1),
                         job.get("precision", "float32"), job.get("rounding"))
        return [counter_lane(net, mpl, job["n_requests"], warmup, s)
                for s in job["lane_seeds"]]
    net = Network(job["config"], job["p_hit"], job.get("clock_ns", 1))
    return [closed(net, mpl, job["n_requests"], warmup, s)
            for s in job["seeds"]]

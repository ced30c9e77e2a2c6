"""Plain reference of a replay question: the seven eviction policies one
request at a time, the in-flight classifier, and the decode to the answer a
user reads.

The policies keep their lists as doubly-linked lists over dicts and follow
the semantics the configuration states (list order, bounded CLOCK/S3-FIFO
scans, SIEVE's hand, the op counts of every request). They are a copy of
the program's pure-Python oracle made for the benchmark, so that no change
to the program can move the yardstick. Only numpy is imported: the
reference runs in host worker processes that never touch JAX.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

TRUE_MISS, TRUE_HIT, DELAYED_HIT = 0, 1, 2


class _KeyList:
    """Keys ordered head .. tail; every operation is O(1)."""

    def __init__(self):
        self._up: dict = {}    # key -> neighbour toward the head
        self._down: dict = {}  # key -> neighbour toward the tail
        self.head: Optional[int] = None
        self.tail: Optional[int] = None

    def __len__(self):
        return len(self._up)

    def __contains__(self, key):
        return key in self._up

    def push(self, key):
        self._up[key] = None
        self._down[key] = self.head
        if self.head is None:
            self.tail = key
        else:
            self._up[self.head] = key
        self.head = key

    def remove(self, key):
        up = self._up.pop(key)
        down = self._down.pop(key)
        if up is None:
            self.head = down
        else:
            self._down[up] = down
        if down is None:
            self.tail = up
        else:
            self._up[down] = up

    def pop(self):
        key = self.tail
        self.remove(key)
        return key

    def toward_head(self, key):
        return self._up[key]


class LRU:
    def __init__(self, capacity: int):
        self.capacity = capacity
        self.order = _KeyList()

    def access(self, key, u):
        if key in self.order:
            self.order.remove(key)
            self.order.push(key)
            return True, -1, (1, 1, 0, 0)
        evicted, tail = -1, 0
        if len(self.order) >= self.capacity:
            evicted, tail = self.order.pop(), 1
        self.order.push(key)
        return False, evicted, (0, 1, tail, 0)


class FIFO(LRU):
    def access(self, key, u):
        if key in self.order:
            return True, -1, (0, 0, 0, 0)
        evicted, tail = -1, 0
        if len(self.order) >= self.capacity:
            evicted, tail = self.order.pop(), 1
        self.order.push(key)
        return False, evicted, (0, 1, tail, 0)


class ProbLRU(LRU):
    def __init__(self, capacity: int, q: float):
        super().__init__(capacity)
        self.q = float(np.float32(q))  # the coin is float32; so is q

    def access(self, key, u):
        if key in self.order:
            if u >= self.q:
                self.order.remove(key)
                self.order.push(key)
                return True, -1, (1, 1, 0, 0)
            return True, -1, (0, 0, 0, 0)
        return FIFO.access(self, key, u)


class Clock(LRU):
    def __init__(self, capacity: int, max_scan: int):
        super().__init__(capacity)
        self.max_scan = max_scan
        self.bit: dict = {}

    def _evict(self):
        scans = 0
        while True:
            s = self.order.tail
            if self.bit.get(s, False) and scans < self.max_scan:
                self.order.pop()
                self.order.push(s)
                self.bit[s] = False
                scans += 1
            else:
                self.order.pop()
                self.bit.pop(s, None)
                return s, (0, scans, 1, scans)

    def access(self, key, u):
        if key in self.order:
            self.bit[key] = True
            return True, -1, (0, 0, 0, 0)
        evicted, ops = -1, (0, 0, 0, 0)
        if len(self.order) >= self.capacity:
            evicted, ops = self._evict()
        self.order.push(key)
        self.bit[key] = False
        return False, evicted, (ops[0], ops[1] + 1, ops[2], ops[3])


class SLRU:
    def __init__(self, capacity: int, protected_frac: float):
        self.capacity = capacity
        self.protected_cap = max(1, int(capacity * protected_frac))
        self.B = _KeyList()  # probationary
        self.T = _KeyList()  # protected

    def access(self, key, u):
        if key in self.T:
            self.T.remove(key)
            self.T.push(key)
            return True, -1, (1, 1, 0, 0)
        if key in self.B:
            self.B.remove(key)
            self.T.push(key)
            h, t = 1, 0
            if len(self.T) > self.protected_cap:
                self.B.push(self.T.pop())
                h, t = 2, 1
            return True, -1, (1, h, t, 0)
        evicted, tail = -1, 0
        if len(self.B) + len(self.T) >= self.capacity:
            evicted = self.B.pop() if len(self.B) else self.T.pop()
            tail = 1
        self.B.push(key)
        return False, evicted, (0, 1, tail, 0)


class S3FIFO:
    def __init__(self, capacity: int, small_frac: float, max_scan: int):
        if capacity < 2:
            raise ValueError("s3fifo needs capacity >= 2")
        self.s_cap = max(1, int(capacity * small_frac))
        self.m_cap = capacity - self.s_cap
        self.max_scan = max_scan
        self.S = _KeyList()
        self.M = _KeyList()
        self.bit: dict = {}
        self.ghost = [-1] * max(1, self.m_cap)  # ring of recently evicted keys
        self.ghost_set: set = set()
        self.ghost_pos = 0

    def _evict_m(self):
        scans = 0
        while True:
            s = self.M.tail
            if self.bit.get(s, False) and scans < self.max_scan:
                self.M.pop()
                self.M.push(s)
                self.bit[s] = False
                scans += 1
            else:
                self.M.pop()
                self.bit.pop(s, None)
                return s, [0, scans, 1, scans]

    def access(self, key, u):
        if key in self.S or key in self.M:
            self.bit[key] = True
            return True, -1, (0, 0, 0, 0)
        ops, evicted = [0, 0, 0, 0], -1
        in_ghost = key in self.ghost_set
        if in_ghost and len(self.M) >= self.m_cap:
            evicted, e = self._evict_m()
            ops = [a + b for a, b in zip(ops, e)]
        if not in_ghost and len(self.S) >= self.s_cap:
            s_tail = self.S.tail
            if self.bit.get(s_tail, False):
                if len(self.M) >= self.m_cap:
                    evicted, e = self._evict_m()
                    ops = [a + b for a, b in zip(ops, e)]
                self.S.pop()
                self.M.push(s_tail)
                self.bit[s_tail] = False
                ops[1] += 1
                ops[2] += 1
            else:
                self.S.pop()
                self.bit.pop(s_tail, None)
                old = self.ghost[self.ghost_pos]
                if old >= 0:
                    self.ghost_set.discard(old)
                self.ghost[self.ghost_pos] = s_tail
                self.ghost_set.add(s_tail)
                self.ghost_pos = (self.ghost_pos + 1) % len(self.ghost)
                evicted = s_tail
                ops[2] += 1
        (self.M if in_ghost else self.S).push(key)
        self.bit[key] = False
        ops[1] += 1
        return False, evicted, tuple(ops)


class Sieve(LRU):
    def __init__(self, capacity: int):
        super().__init__(capacity)
        self.bit: dict = {}
        self.hand: Optional[int] = None

    def access(self, key, u):
        if key in self.order:
            self.bit[key] = True
            return True, -1, (0, 0, 0, 0)
        evicted, ops = -1, [0, 1, 0, 0]
        if len(self.order) >= self.capacity:
            h = (self.hand if self.hand is not None and self.hand in self.order
                 else self.order.tail)
            scans = 0
            while self.bit.get(h, False):
                self.bit[h] = False
                up = self.order.toward_head(h)
                h = self.order.tail if up is None else up
                scans += 1
            self.hand = self.order.toward_head(h)
            self.order.remove(h)
            self.bit.pop(h, None)
            evicted = h
            ops[2], ops[3] = 1, scans
        self.order.push(key)
        self.bit[key] = False
        return False, evicted, tuple(ops)


POLICIES = {"lru": LRU, "fifo": FIFO, "prob_lru": ProbLRU, "clock": Clock,
            "slru": SLRU, "s3fifo": S3FIFO, "sieve": Sieve}


def bf16_round(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even), kept
    as float32: what a 16-bit coin stream would hold."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def replay(policy: str, capacity: int, params: dict, keys, us):
    """(hits bool, evicted int32, ops (T, 4) int64) of one lane."""
    cache = POLICIES[policy](capacity, **params)
    n = len(keys)
    hits = np.empty(n, bool)
    evicted = np.empty(n, np.int32)
    ops = np.empty((n, 4), np.int64)
    for t, (k, u) in enumerate(zip(np.asarray(keys).tolist(),
                                   np.asarray(us, np.float32).tolist())):
        hits[t], evicted[t], ops[t] = cache.access(k, u)
    return hits, evicted, ops


def classify(keys, hits, windows) -> np.ndarray:
    """In-flight classes: a true miss on key k at t keeps a fetch in flight
    through t + window[t]; a request for k inside it is a delayed hit."""
    expiry: dict = {}
    out = np.empty(len(keys), np.int8)
    for t, (k, h, w) in enumerate(zip(np.asarray(keys).tolist(),
                                      np.asarray(hits).tolist(),
                                      np.asarray(windows).tolist())):
        if t <= expiry.get(k, -1):
            out[t] = DELAYED_HIT
        elif h:
            out[t] = TRUE_HIT
        else:
            out[t] = TRUE_MISS
            expiry[k] = t + w
    return out


def decode(hits, ops, cls, service: dict, mpl: int,
           warmup_frac: float) -> tuple:
    """The answer of one lane: (hit ratio, delayed-hit fraction, throughput
    bound) after the warm-up share of requests.

    The bound is Thm 7.1 of arXiv:2404.16219 on the network whose routes
    are the measured (hit, op-count) profiles: X <= min(N / (D + Z),
    1 / max_k D_k), with one visit of the lookup station per request, one
    of the disk per miss, and as many visits of the delink, head, tail and
    scan queues as the ops counted.
    """
    w = int(len(hits) * warmup_frac)
    h, o = np.asarray(hits[w:], bool), np.asarray(ops[w:], np.int64)
    total, n_hits = len(h), int(h.sum())
    counts = o.sum(axis=0)
    demand = [int(c) / total * service[name]
              for c, name in zip(counts, ("delink", "head", "tail", "scan"))]
    think = service["lookup"] + (total - n_hits) / total * service["disk"]
    terms = [mpl / (sum(demand) + think)] + [1.0 / d for d in demand if d > 0]
    w_cls = int(len(cls) * warmup_frac)
    delayed = float(np.mean(np.asarray(cls[w_cls:]) == DELAYED_HIT))
    return n_hits / total, delayed, min(terms)


def answer_gap(got, want) -> float:
    """Widest relative gap between two answers of a lane."""
    return max(abs(g - r) / max(abs(r), 1e-12) for g, r in zip(got, want))


def check_lane(job: dict) -> dict:
    """Replay one lane in the reference and compare it with what the program
    produced for it. ``job["coin"] == "bf16"`` is the control: the coin
    stream rounded to bfloat16 in the program's place."""
    us = job["us"]
    if job.get("coin") == "bf16":
        us = bf16_round(us)
    hits, evicted, ops = replay(job["policy"], job["capacity"], job["params"],
                                job["keys"], job["us"])
    cls = classify(job["keys"], hits, job["windows"])
    want = decode(hits, ops, cls, job["service"], job["mpl"],
                  job["warmup_frac"])
    got = job.get("program")
    if got is None:  # the control: its own replay in the program's place
        g_hits, g_ev, g_ops = replay(job["policy"], job["capacity"],
                                     job["params"], job["keys"], us)
        g_cls = classify(job["keys"], g_hits, job["windows"])
        got = {"hits": g_hits, "evicted": g_ev, "ops": g_ops, "cls": g_cls,
               "answer": decode(g_hits, g_ops, g_cls, job["service"],
                                job["mpl"], job["warmup_frac"])}
    mismatches = int(
        np.count_nonzero(np.asarray(got["hits"], bool) != hits)
        + np.count_nonzero(np.asarray(got["evicted"]) != evicted)
        + np.count_nonzero(np.asarray(got["ops"]) != ops)
        + np.count_nonzero(np.asarray(got["cls"]) != cls))
    return {"policy": job["policy"], "capacity": job["capacity"],
            "question": job.get("question"), "mismatches": mismatches,
            "answer_gap": answer_gap(got["answer"], want),
            "evictions": int(np.count_nonzero(evicted >= 0))}

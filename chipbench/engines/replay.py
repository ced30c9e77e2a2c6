"""Replay questions: the configuration's eviction policies over one trace
set each, through ``repro.kernels.replay.replay_grid_pallas`` (the Mosaic
replay kernel with the delayed-hit classifier fused in) and the program's
host decode to the answer a user reads.

A question replays, for each policy in turn, one trace set of the pool at
every capacity of the mix (one dispatch per policy), then decodes each lane
to (hit ratio, delayed-hit fraction, Thm 7.1 throughput bound). Each
question takes the next trace set of a pool made from the seed in set-up.
"""

from __future__ import annotations

import numpy as np

from chipbench import gen
from chipbench.bench import span

class TraceSet:
    def __init__(self, seed: int, i: int, n: int, cfg: dict):
        seq = gen.question_seq(seed, i)
        self.keys = gen.zipf_trace(n, cfg["recordcount"],
                                   cfg["zipfian_constant"], seq)
        self.us = gen.coin_stream(n, seq)
        self.windows = gen.miss_window_stream(
            n, cfg["assumed"]["miss_window_mean_requests"], seq)
        self.distinct = int(np.count_nonzero(
            np.bincount(self.keys, minlength=cfg["recordcount"])))


class WarmSet:
    """The warm-up question's inputs: the shapes and types of a trace set,
    one key throughout, so the replay it needs (to bring each program in
    from the cache) is all hits and short."""

    def __init__(self, n: int):
        self.keys = np.zeros(n, np.int32)
        self.us = np.zeros(n, np.float32)
        self.windows = np.zeros(n, np.int32)


class Engine:
    unit = "replay"

    def __init__(self, files: dict, seed: int):
        from repro.core.harness import ServiceTimes

        self.cfg = cfg = files["config"]
        self.mix = mix = files["mix"]
        self.seed = seed
        self.caps = [int(c) for c in gen.grid(mix["capacities"])]
        self.pad = max(self.caps)
        self.n = int(mix["requests"])
        a = cfg["assumed"]
        self.policies = a["policies"]
        self.mpl = int(a["mpl"])
        self.warmup_frac = float(a["warmup_frac"])
        self.services = {p: dict(a["services_us"][p], disk=a["disk_us"])
                         for p in self.policies}
        self.program_services = {p: ServiceTimes(**s)
                                 for p, s in self.services.items()}
        self.pool = [TraceSet(seed, i, self.n, cfg)
                     for i in range(int(mix["pool"]))]
        for ts in self.pool:
            self._hold_rule(ts)
        self.sample_rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), 1 << 21]))
        self.kept = {}  # (policy, cap index) -> (result, answer, set index)
        self.answered = 0

    def _hold_rule(self, ts: TraceSet) -> None:
        """The mix's capacities stay on their side of the working set."""
        rule = self.mix["capacities_vs_distinct_keys"]
        ok = (max(self.caps) < ts.distinct if rule == "below"
              else min(self.caps) > ts.distinct)
        if not ok:
            raise ValueError(f"capacities {self.caps} are not all {rule} the "
                             f"{ts.distinct} distinct keys of a trace set")

    @property
    def work_per_question(self) -> int:
        return len(self.policies) * len(self.caps) * self.n

    def _ask(self, ts: TraceSet):
        """One question: (per policy: device result, per-lane answers)."""
        import jax

        from repro.core.harness import empirical_network
        from repro.kernels.replay import replay_grid_pallas, unpack_grid_ops

        out = []
        w = int(self.n * self.warmup_frac)
        for policy, params in self.policies.items():
            with span("replay.dispatch"):
                res = replay_grid_pallas(
                    policy, ts.keys[None], ts.us[None], self.caps,
                    key_space=self.cfg["recordcount"], pad_to=self.pad,
                    window=ts.windows, **params)
            with span("replay.wait"):
                jax.block_until_ready(res)
            with span("replay.decode"):
                hits = np.asarray(res.hits)[:, 0]
                ops = unpack_grid_ops(res)[:, 0]
                cls = np.asarray(res.cls)[:, 0]
                answers = []
                for c in range(len(self.caps)):
                    m = empirical_network(
                        policy, hits[c], ops[c],
                        service=self.program_services[policy], mpl=self.mpl,
                        warmup_frac=self.warmup_frac)
                    answers.append((m.hit_ratio,
                                    float(np.mean(cls[c, w:] == 2)),
                                    float(m.throughput_bound())))
            out.append((policy, res, answers))
        return out

    def warm(self) -> None:
        self._ask(WarmSet(self.n))

    def question(self, i: int) -> None:
        k = i % len(self.pool)
        out = self._ask(self.pool[k])
        self.last = (out, k, i)
        # one lane of each (policy, capacity) is kept for the check, drawn
        # uniformly over the questions answered (a reservoir of one)
        self.answered += 1
        for policy, res, answers in out:
            for c in range(len(self.caps)):
                if self.sample_rng.random() * self.answered < 1.0:
                    self.kept[policy, c] = (res, answers[c], k, i)

    def keep_last(self) -> None:
        """Keep every lane of the last question for the check."""
        out, k, i = self.last
        self.kept = {(policy, c): (res, answers[c], k, i)
                     for policy, res, answers in out
                     for c in range(len(self.caps))}

    def check_jobs(self, coin: str = "float32") -> list:
        """Reference jobs for the kept lanes (the program's outputs pulled to
        the host); ``coin="bf16"`` makes them the control's jobs."""
        from repro.kernels.replay import unpack_grid_ops

        host = {}
        jobs = []
        for (policy, c), (res, answer, k, i) in sorted(
                self.kept.items(), key=lambda kv: (kv[0][0], kv[0][1])):
            if id(res) not in host:
                host[id(res)] = (np.asarray(res.hits)[:, 0],
                                 np.asarray(res.evicted)[:, 0],
                                 unpack_grid_ops(res)[:, 0].astype(np.int32),
                                 np.asarray(res.cls)[:, 0])
            hits, ev, ops, cls = host[id(res)]
            ts = self.pool[k]
            job = {"policy": policy, "capacity": self.caps[c],
                   "params": self.policies[policy], "keys": ts.keys,
                   "us": ts.us, "windows": ts.windows,
                   "service": self.services[policy], "mpl": self.mpl,
                   "warmup_frac": self.warmup_frac, "question": i}
            if coin == "bf16":
                job["coin"] = "bf16"
            else:
                job["program"] = {"hits": hits[c], "evicted": ev[c],
                                  "ops": ops[c], "cls": cls[c],
                                  "answer": answer}
            jobs.append(job)
        self.kept.clear()
        return jobs

    def check(self, pool, limits=None, coin: str = "float32") -> tuple:
        """(numbers compared, information) once the window has closed;
        ``info["failed"]`` counts the questions with a lane over a limit."""
        from chipbench.reference import replay_ref

        results = pool.map(replay_ref.check_lane, self.check_jobs(coin),
                           chunksize=1)
        numbers = {
            "mismatches": sum(r["mismatches"] for r in results),
            "answer_gap": max(r["answer_gap"] for r in results),
        }
        limits = limits or {"mismatches": 0, "answer_gap": 0.0}
        info = {"lanes_compared": len(results),
                "failed": len({r["question"] for r in results
                               if r["mismatches"] > limits["mismatches"]
                               or not r["answer_gap"]
                               <= limits["answer_gap"]}),
                "evictions": {f"{r['policy']}@{r['capacity']}": r["evictions"]
                              for r in results}}
        return numbers, info

    def control(self, pool, coin: str = "bf16") -> dict:
        """The control's numbers for the kept lanes: the reference with its
        coins rounded to bfloat16, in the program's place."""
        return self.check(pool, coin=coin)[0]

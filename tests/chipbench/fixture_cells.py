"""A checkout of the benchmark with small fixture cells, for driving whole
runs on the CPU. The cells are added as a later PR would add them: a
configuration file, a mix file and a metric file, and entries in
``BENCHMARK.json``. No file the benchmark has is edited."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_CACHE = {
    "name": "tiny_cache", "source": "fixture: a small YCSB C-like cache",
    "recordcount": 4096, "zipfian_constant": 0.99,
    "assumed": {
        "miss_window_mean_requests": 16,
        "policies": {"lru": {}, "prob_lru": {"q": 0.5}},
        "mpl": 72, "disk_us": 100.0, "warmup_frac": 0.25,
        "services_us": {
            "lru": {"lookup": 0.51, "delink": 0.70, "head": 0.59,
                    "tail": 0.59, "scan": 0.30},
            "prob_lru": {"lookup": 0.51, "delink": 0.78, "head": 0.65,
                         "tail": 0.65, "scan": 0.30}},
    },
}

MIXES = {
    "tiny_evict": {
        "engine": "replay", "capacities": [64, 256],
        "capacities_vs_distinct_keys": "below", "requests": 2048, "pool": 3,
        "trace_questions": 1,
        "limits": {"mismatches": 0, "answer_gap": 1e-9}},
    "tiny_closed": {
        "engine": "sim", "backend": "pallas",
        "p_hit": [0.3, 0.95], "seeds_per_question": 2, "requests": 3000,
        "pool": 3, "trace_questions": 1, "reference_seeds": 4,
        "limits": {"lane_gap": 1e-5, "throughput_gap": 0.1}},
}

QUESTIONS_METRIC = '''"""Questions in the traced window (fixture metric)."""


def read(ctx):
    return float(ctx["questions"])
'''


def make(tmp: Path) -> Path:
    """A checkout under ``tmp`` with the fixture cells added."""
    shutil.copytree(ROOT / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    here = tmp / "chipbench"
    (here / "configs" / "tiny_cache.json").write_text(json.dumps(TINY_CACHE))
    for name, mix in MIXES.items():
        (here / "mixes" / f"{name}.json").write_text(json.dumps(mix))
    (here / "metrics" / "questions.fixture.py").write_text(QUESTIONS_METRIC)
    bench["configs"].append({
        "name": "tiny_cache", "source": "fixture",
        "file": "chipbench/configs/tiny_cache.json", "reduced": [],
        "why": "fixture"})
    cells = [("tiny_cache.evict", "tiny_cache", "tiny_evict"),
             ("paper_lru_72core.tiny_closed", "paper_lru_72core",
              "tiny_closed")]
    for name, config, traffic in cells:
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "fixture"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            unit = "replay" if "replay" in m["name"] or m["name"] in (
                "replay_kernel_ns", "replay_kernel_roofline") else "sim"
            m["workloads"] += [c for c, _, t in cells
                               if (t == "tiny_evict") == (unit == "replay")]
    bench["per_layer"].append({
        "name": "questions.fixture", "unit": "questions", "better": "higher",
        "source": "host_clock", "layer": "harness", "moves": "setup_s",
        "workloads": [c for c, _, _ in cells]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp

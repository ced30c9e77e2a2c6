"""The benchmark's files: found by name, legal names and units, contract
limits, traffic made from the seed alone, and configurations that are the
deployments they name."""

import json
import re
from pathlib import Path

import numpy as np
import pytest

from chipbench import bench, gen

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert all((ROOT / p).is_dir() for p in BENCH["paths"])
    assert 1 <= BENCH["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_bounds():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for w in BENCH["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    files = bench.cell_files(BENCH, cell)
    assert files["config"]["name"] == files["cell"]["config"]
    assert (ROOT / files["config_entry"]["file"]).is_file()
    assert set(files["config_entry"]["reduced"]) == set(
        files["config"]["reduced"])
    assert (bench.HERE / "engines" / f"{files['mix']['engine']}.py").is_file()
    e2e = {m["name"] for m in files["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert files["per_layer"], "every cell reports a per-layer metric"
    for m in files["per_layer"]:
        assert m["moves"] in e2e
        assert callable(bench.metric_reader(m["name"]).read)
    assert set(files["mix"]["limits"]) and all(
        v >= 0 for v in files["mix"]["limits"].values())


def test_per_layer_metrics_name_cells_that_report_what_they_move():
    reports = {m["name"]: set(m.get("workloads", CELLS))
               for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= reports[m["moves"]]
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


@pytest.mark.parametrize("seed", [0, 2**31 + 11, 2**40 + 3])
def test_traffic_is_made_from_the_seed(seed):
    def streams(s):
        seq = gen.question_seq(s, 3)
        return (gen.zipf_trace(4096, 1 << 12, 0.99, seq),
                gen.coin_stream(4096, seq),
                gen.miss_window_stream(4096, 64, seq))

    a, b, c = streams(seed), streams(seed), streams(seed + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.int32 and a[1].dtype == np.float32
    seeds = gen.lane_seeds(seed, 17, 4, 1 << 21)
    assert np.array_equal(seeds, gen.lane_seeds(seed, 17, 4, 1 << 21))
    assert len(np.unique(seeds)) == seeds.size and seeds.max() < 1 << 21


def test_capacities_stay_on_their_side_of_the_working_set():
    files = {c: bench.cell_files(BENCH, c) for c in
             ("ycsb_c_1m.evict", "ycsb_c_1m.fits")}
    cfg = files["ycsb_c_1m.evict"]["config"]
    seq = gen.question_seq(2**33 + 1, 0)
    keys = gen.zipf_trace(files["ycsb_c_1m.evict"]["mix"]["requests"],
                          cfg["recordcount"], cfg["zipfian_constant"], seq)
    distinct = len(np.unique(keys))
    assert max(gen.grid(files["ycsb_c_1m.evict"]["mix"]["capacities"])) \
        < distinct < min(gen.grid(files["ycsb_c_1m.fits"]["mix"]["capacities"]))


def test_network_config_is_the_papers_lru_network():
    from repro.core import lru_network

    from chipbench.engines import sim

    cfg = bench.load_json(bench.HERE / "configs" / "paper_lru_72core.json")
    ours, theirs = sim.program_network(cfg), lru_network(disk_us=100.0)
    p = np.linspace(0.3, 0.99, 12)
    assert np.allclose(ours.throughput_upper(p), theirs.throughput_upper(p))
    assert np.allclose([ours.mva(x)[0] for x in p[::4]],
                       [theirs.mva(x)[0] for x in p[::4]])
    assert [s.dist for s in ours.stations] == [s.dist for s in theirs.stations]

"""Whole runs on the CPU of small fixture cells: sound runs come out
correct, runs with the timed path broken underneath do not, and the
controls fail the numbers compared. The fixture cells, a mix and a metric
are added by files and BENCHMARK.json entries alone (fixture_cells)."""

import dataclasses
import importlib.util
import json

import numpy as np
import pytest

import fixture_cells
from chipbench import bench, gen
from chipbench.reference import replay_ref, sim_ref
from chipbench.run import InlinePool

SEED = 2**33 + 5


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = fixture_cells.make(tmp_path_factory.mktemp("checkout"))
    spec = importlib.util.spec_from_file_location(
        "fixture_run", root / "chipbench" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return root, run


def drive(checkout, capsys, cell, trace=0, seconds=0.5):
    root, run = checkout
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  require_tpu=False, pool=run.InlinePool())
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")
    return result


@pytest.mark.parametrize("cell,trace", [
    ("tiny_cache.evict", 0), ("tiny_cache.evict", 1),
    ("paper_lru_72core.tiny_closed", 0), ("paper_lru_72core.tiny_closed", 1)])
def test_sound_run_is_correct(checkout, capsys, cell, trace):
    result = drive(checkout, capsys, cell, trace)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert result["checks"]["compiles_in_window"]["value"] == 0
    if trace:
        # the fixture metric is read from its own file, found by name
        assert result["metrics"]["questions.fixture"]["value"] >= 1
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert "setup_s" in result["metrics"]
        assert {"replay_rate", "sim_rate"} & set(result["metrics"])


def _replay_fault(kind):
    from repro.kernels import replay

    real = replay.replay_grid_pallas

    def broken(policy, keys, us, caps, **kw):
        if kind == "half_batch":
            half = real(policy, keys, us, caps[:len(caps) // 2], **kw)
            return type(half)(*(np.concatenate([a, a]) for a in half))
        res = real(policy, keys, us, caps, **kw)
        if kind == "token_altered":
            hits = np.asarray(res.hits).copy()
            hits[..., hits.shape[-1] // 2] ^= True
            return res._replace(hits=hits)
        return res

    return broken


def _sim_fault(kind):
    from repro.core import simulator

    real = simulator.simulate_network

    def broken(net, p_hits, seeds, n_requests, **kw):
        if kind == "half_batch":  # half the seed lanes run, the mean over them
            half = tuple(seeds[:len(seeds) // 2])
            return real(net, p_hits, seeds=half * 2, n_requests=n_requests,
                        **kw)
        if kind == "half_requests":  # every lane stops halfway
            return real(net, p_hits, seeds=seeds,
                        n_requests=n_requests // 2, **kw)
        res = real(net, p_hits, seeds=seeds, n_requests=n_requests, **kw)
        x = np.asarray(res.throughput, np.float64).copy()
        if kind == "state_unchanged":  # no event ever completes a request
            x[:] = 0.0
        elif kind == "answer_altered":
            x[0] *= 1.0 + 1e-4
        return dataclasses.replace(res, throughput=x)

    return broken


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered"])
def test_broken_replay_is_not_correct(checkout, capsys, monkeypatch, kind):
    import jax

    from repro.cache import flat
    from repro.kernels import replay

    if kind == "state_unchanged":
        for policy, step in list(flat.FLAT_STEPS.items()):
            def unchanged(st, *a, _step=step):
                _, hit, evicted, ops = _step(st, *a)
                return st, hit, evicted, ops
            monkeypatch.setitem(flat.FLAT_STEPS, policy, unchanged)
        jax.clear_caches()
    else:
        monkeypatch.setattr(replay, "replay_grid_pallas", _replay_fault(kind))
    try:
        result = drive(checkout, capsys, "tiny_cache.evict", seconds=0.2)
    finally:
        jax.clear_caches()
    assert not result["correct"] and result["failed"] >= 1
    assert result["checks"]["mismatches"]["value"] > 0


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "half_requests", "answer_altered"])
def test_broken_simulation_is_not_correct(checkout, capsys, monkeypatch,
                                          kind):
    from repro.core import simulator

    monkeypatch.setattr(simulator, "simulate_network", _sim_fault(kind))
    result = drive(checkout, capsys, "paper_lru_72core.tiny_closed",
                   seconds=0.2)
    assert not result["correct"] and result["failed"] == 1
    gap = result["checks"]["lane_gap"]
    assert not gap["value"] <= gap["limit"]


def test_replay_control_fails_the_cells_limits():
    """The reference with bfloat16 coins in the program's place, on one
    lane of the cells' own size, reads mismatches and an answer gap over
    the evict and fits limits."""
    cfg = bench.load_json(bench.HERE / "configs" / "ycsb_c_1m.json")
    mix = bench.load_json(bench.HERE / "mixes" / "evict.json")
    seq = gen.question_seq(SEED, 0)
    n = mix["requests"]
    a = cfg["assumed"]
    job = {"policy": "prob_lru", "capacity": 32768,
           "params": a["policies"]["prob_lru"],
           "keys": gen.zipf_trace(n, cfg["recordcount"],
                                  cfg["zipfian_constant"], seq),
           "us": gen.coin_stream(n, seq),
           "windows": gen.miss_window_stream(
               n, a["miss_window_mean_requests"], seq),
           "service": dict(a["services_us"]["prob_lru"], disk=a["disk_us"]),
           "mpl": a["mpl"], "warmup_frac": a["warmup_frac"], "coin": "bf16"}
    out = replay_ref.check_lane(job)
    assert out["mismatches"] > mix["limits"]["mismatches"]
    assert out["answer_gap"] > mix["limits"]["answer_gap"]


def _closed_engine(requests=4000, p_hit=(0.6, 0.9)):
    from chipbench.engines import sim

    files = {"config": bench.load_json(
        bench.HERE / "configs" / "paper_lru_72core.json"),
        "mix": dict(bench.load_json(bench.HERE / "mixes" / "closed.json"),
                    requests=requests, reference_seeds=2, p_hit=list(p_hit))}
    return sim.Engine(files, SEED)


@pytest.mark.parametrize("variant", [{"precision": "bf16"}, {"clock_ns": 100},
                                     {"clock_ns": 1000}])
def test_simulation_control_fails_the_cells_limits(variant):
    """The counter-stream reference with bfloat16 service draws, or on a
    coarser clock, in the program's place reads a lane gap over the closed
    cell's limit, at a size a test can hold."""
    eng = _closed_engine()
    eng.kept = (None, eng.pool[0])
    numbers = eng.control(InlinePool(), **variant)
    assert numbers["lane_gap"] > eng.mix["limits"]["lane_gap"]


def test_counter_stream_reference_follows_the_program_lane_for_lane():
    """One seed per call, so each hit ratio's answer is one lane: the
    program's lanes and the reference's agree to float32 rounding."""
    from repro.core.simulator import simulate_network

    eng = _closed_engine(requests=3000, p_hit=(0.5, 0.75, 0.99))
    seeds = eng.pool[0][:1]
    got = simulate_network(eng.net, eng.p_hits, n_requests=eng.n,
                           seeds=tuple(int(s) for s in seeds),
                           warmup_frac=eng.cfg["warmup_frac"],
                           backend="pallas")
    jobs = [sim_ref.run_job(j) for j in eng.counter_jobs(seeds)]
    want = eng.summarise_counter(jobs)
    assert np.allclose(got.throughput, want["throughput"], rtol=1e-6,
                       atol=0)
    assert all(lane["completed"] == eng.n for lanes in jobs
               for lane in lanes)


def test_reference_simulators_agree_with_themselves_across_clocks():
    """Sanity of the reference: a 1 ns clock and the control's 1 us clock
    differ only through the rounding of service times."""
    cfg = bench.load_json(bench.HERE / "configs" / "paper_lru_72core.json")
    lanes = [sim_ref.run_job({"config": cfg, "p_hit": 0.9,
                              "n_requests": 3000, "warmup_frac": 0.25,
                              "seeds": [7], "clock_ns": c})[0]
             for c in (1, 1000)]
    assert lanes[1]["throughput"] < 0.9 * lanes[0]["throughput"]

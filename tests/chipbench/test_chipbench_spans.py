"""The program's own spans (``repro.obs.spans``) in a profiler trace: where
the replay and simulation host paths open them, what they count, that
they change no output, and the numbers ``chipbench.program_spans`` reads
from them."""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

import fixture_cells
from chipbench import bench, gen, program_spans, tracing
from chipbench.program_spans import ProgramSpan
from chipbench.tracing import Event, TraceView

SEED = 2**33 + 7
P = "chipbench."
CAPS = [64, 256]
POLICY, PARAMS = "prob_lru", {"q": 0.5}
N = 2048


def replay_inputs():
    seq = gen.question_seq(SEED, 0)
    keys = gen.zipf_trace(N, 4096, 0.99, seq)
    return (np.stack([keys, keys[::-1]]),
            np.stack([gen.coin_stream(N, seq)] * 2),
            gen.miss_window_stream(N, 16, seq))


def replay_question():
    """One small replay through the program's host path, in the
    benchmark's spans: (hits, evicted, ops, cls, answers)."""
    from repro.core.harness import empirical_network
    from repro.kernels.replay import replay_grid_pallas, unpack_grid_ops

    keys, us, windows = replay_inputs()
    with bench.span("replay.dispatch"):
        res = replay_grid_pallas(POLICY, keys, us, CAPS, key_space=4096,
                                 window=windows, **PARAMS)
    with bench.span("replay.decode"):
        hits = np.asarray(res.hits)
        ops = unpack_grid_ops(res)
        answers = [empirical_network(POLICY, hits[c, 0], ops[c, 0])
                   for c in range(len(CAPS))]
    return (hits, np.asarray(res.evicted), ops, np.asarray(res.cls),
            [(m.hit_ratio, m.profiles, m.throughput_bound())
             for m in answers])


def sim_question():
    """One small closed-loop simulation on the event kernel's twin, in the
    benchmark's span: (throughput, ci95)."""
    from chipbench.engines.sim import program_network
    from repro.core.simulator import simulate_network

    net = program_network(bench.load_json(
        bench.HERE / "configs" / "paper_lru_72core.json"))
    with bench.span("sim.call"):
        res = simulate_network(net, (0.5, 0.9), n_requests=600, seeds=(3, 4),
                               backend="pallas")
    return res.throughput, res.ci95


def traced(fn):
    """(fn's result, harness view, program spans) of a traced window."""
    with tracing.capture() as trace:
        with bench.span("window"):
            out = fn()
    try:
        view, program = program_spans.load(trace["path"])
    finally:
        tracing.discard(trace)
    return out, view, program


def inside(span, outer):
    return outer.start <= span.start and span.end <= outer.end


def test_replay_spans_nest_in_the_benchmark_spans_and_count_the_shapes():
    (_, _, ops, _, _), view, program = traced(replay_question)
    (dispatch,) = view.spans_named("replay.dispatch")
    (decode,) = view.spans_named("replay.decode")
    from repro.cache import flat

    lanes = len(CAPS) * replay_inputs()[0].shape[0]
    for name in ("replay.lane_inputs", "replay.h2d", "replay.launch"):
        (s,) = program_spans.named(program, name)
        assert inside(s, dispatch), name
    (setup,) = program_spans.named(program, "replay.lane_inputs")
    assert setup.stats == {"lanes_count": lanes, "requests_count": N}
    # per lane: a parameter vector, its q, and a key, a coin and a window
    # per request
    pvec, _ = flat.flat_lane_params(POLICY, CAPS[0], **PARAMS)
    (h2d,) = program_spans.named(program, "replay.h2d")
    assert h2d.stats == {"h2d_bytes": lanes * (pvec.nbytes + 4 + N * 12)}
    (unpack,) = program_spans.named(program, "replay.unpack")
    assert inside(unpack, decode)
    assert unpack.stats == {"d2h_bytes": ops.size * 4}
    networks = program_spans.named(program, "harness.empirical_network")
    profiles = program_spans.named(program, "harness.profile")
    assert len(networks) == len(profiles) == len(CAPS)
    for net, prof in zip(networks, profiles):
        assert inside(net, decode) and inside(prof, net)
        assert net.stats == {"requests_count": N}
        assert prof.stats == {}


def test_simulation_spans_nest_in_the_call_and_count_the_events(monkeypatch):
    from repro.kernels import event_sim

    outs = []
    real = event_sim._twin_grid

    def keep(*a, **kw):
        outs.append(real(*a, **kw))
        return outs[-1]

    monkeypatch.setattr(event_sim, "_twin_grid", keep)
    _, view, program = traced(sim_question)
    (call,) = view.spans_named("sim.call")
    compiles = program_spans.named(program, "sim.compile_network")
    assert len(compiles) == 2
    for name in ("sim.tables", "sim.launch", "sim.collect"):
        (s,) = program_spans.named(program, name)
        assert all(c.end <= s.start for c in compiles), name
    assert all(inside(s, call) for s in program)
    (collect,) = program_spans.named(program, "sim.collect")
    (out,) = outs
    assert collect.stats == {"events_count": int(np.asarray(out[2]).sum())}
    assert collect.stats["events_count"] > 2 * 2 * 600


def test_outputs_are_bit_identical_traced_and_untraced_and_no_count_is_paid(
        monkeypatch):
    from repro.obs import spans

    counted = []
    real = spans.Span.count

    def count(self, **counts):
        counted.append(set(counts))
        real(self, **counts)

    monkeypatch.setattr(spans.Span, "count", count)
    plain = (replay_question(), sim_question())
    assert not any("events_count" in c for c in counted)
    (with_trace, _, _) = traced(lambda: (replay_question(), sim_question()))
    assert any("events_count" in c for c in counted)
    (replay_a, sim_a), (replay_b, sim_b) = plain, with_trace
    for a, b in zip(replay_a[:4], replay_b[:4]):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert replay_a[4] == replay_b[4]
    for a, b in zip(sim_a, sim_b):
        assert np.array_equal(a, b)


KERNEL = "%pallas_grid.1 tpu_custom_call"


def replay_window():
    """One replay dispatch and decode, with the program spans inside."""
    spans = [Event(P + "window", 0, 500),
             Event(P + "replay.dispatch", 10, 105),
             Event(P + "replay.decode", 210, 400)]
    program = [
        ProgramSpan("repro.replay.lane_inputs", 20, 50,
                    {"lanes_count": 8, "requests_count": 4}),
        ProgramSpan("repro.replay.h2d", 50, 70, {"h2d_bytes": 64}),
        ProgramSpan("repro.replay.launch", 70, 104, {}),
        ProgramSpan("repro.replay.unpack", 220, 260, {"d2h_bytes": 16}),
        ProgramSpan("repro.harness.empirical_network", 260, 300,
                    {"requests_count": 10}),
        ProgramSpan("repro.harness.profile", 270, 290, {}),
        ProgramSpan("repro.harness.empirical_network", 300, 330,
                    {"requests_count": 10}),
    ]
    return TraceView({"/device:TPU:0": [Event(KERNEL, 100, 200)]},
                     {"/device:TPU:0": [Event("jit_pallas_grid(1)", 100,
                                              200)]}, spans), program


def synthetic():
    """Two replay dispatches and decodes, then one simulation call."""
    view, program = replay_window()
    ops = view.ops() + [Event(KERNEL, 600, 800), Event("copy", 850, 860),
                        Event(KERNEL, 1200, 1400)]
    modules = view.modules["/device:TPU:0"] + [
        Event("jit_pallas_grid(1)", 600, 800),
        Event("jit_pallas_grid(1)", 1200, 1400)]
    spans = [Event(P + "window", 0, 2000)] + view.spans[1:] + [
        Event(P + "replay.dispatch", 500, 605),
        Event(P + "replay.decode", 810, 990),
        Event(P + "sim.call", 1100, 1500)]
    program += [
        ProgramSpan("repro.replay.lane_inputs", 510, 560,
                    {"lanes_count": 2, "requests_count": 5}),
        ProgramSpan("repro.replay.h2d", 560, 600, {"h2d_bytes": 16}),
        ProgramSpan("repro.replay.unpack", 820, 880, {"d2h_bytes": 104}),
        ProgramSpan("repro.harness.empirical_network", 880, 900,
                    {"requests_count": 5}),
        ProgramSpan("repro.sim.compile_network", 1110, 1120, {}),
        ProgramSpan("repro.sim.compile_network", 1120, 1140, {}),
        ProgramSpan("repro.sim.tables", 1140, 1180, {}),
        ProgramSpan("repro.sim.collect", 1400, 1450, {"events_count": 50}),
        ProgramSpan("repro.sim.collect", 1460, 1480, {"events_count": 25}),
    ]
    return TraceView({"/device:TPU:0": ops}, {"/device:TPU:0": modules},
                     spans), sorted(program, key=lambda s: s.start)


@pytest.mark.parametrize("name,want", [
    ("lane_setup_ms.replay", (30 + 50) / 2 * 1e-6),
    # set-up time over lanes x requests per lane
    ("lane_setup_ns.replay", (30 + 50) / (8 * 4 + 2 * 5)),
    ("h2d_ms.replay", (20 + 40) / 2 * 1e-6),
    # bytes per ns is GB/s
    ("h2d_gbps.replay", (64 + 16) / (20 + 40)),
    ("unpack_ms.replay", (40 + 60) / 2 * 1e-6),
    ("unpack_gbps.replay", (16 + 104) / (40 + 60)),
    # empirical_network time inside the decodes, per decode
    ("network_ms.replay", (40 + 30 + 20) / 2 * 1e-6),
    ("network_ns.replay", (40 + 30 + 20) / (10 + 10 + 5)),
    ("compile_network_ms.sim", (10 + 20) / 1 * 1e-6),
    ("tables_ms.sim", 40e-6),
    # kernel time (100 + 200 + 200 ns) over the events counted
    ("event_ns.sim", 500 / 75),
])
def test_readers_on_a_synthetic_trace(name, want):
    view, program = synthetic()
    assert program_spans.READERS[name](view, program) == pytest.approx(want)


def test_readers_find_nothing_where_the_program_opens_no_span():
    view, program = synthetic()
    assert {name: f(view, []) for name, f in
            program_spans.READERS.items()} == dict.fromkeys(
                program_spans.READERS)
    # spans without their counts: the numbers fed by counts are missing
    bare = [ProgramSpan(s.name, s.start, s.end, {}) for s in program]
    assert [name for name, f in program_spans.READERS.items()
            if f(view, bare) is None] == [
                "lane_setup_ns.replay", "h2d_gbps.replay",
                "unpack_gbps.replay", "network_ns.replay", "event_ns.sim"]


def test_idle_gaps_go_to_the_innermost_span_of_either_kind():
    view, program = replay_window()
    gaps = dict(program_spans.idle_gaps(view, program))
    # the device is busy 100-200; the rest of 0-500 is idle
    assert gaps == pytest.approx({
        "no span": (10 + 10 + 100) * 1e-9,
        "replay.dispatch": 10e-9,
        "repro.replay.lane_inputs": 30e-9,
        "repro.replay.h2d": 20e-9,
        "repro.replay.launch": 30e-9,
        "replay.decode": (10 + 70) * 1e-9,
        "repro.replay.unpack": 40e-9,
        "repro.harness.empirical_network": (10 + 10 + 30) * 1e-9,
        "repro.harness.profile": 20e-9,
    })
    # with no program span the harness's own attribution is unchanged
    assert program_spans.idle_gaps(view, []) == tracing.idle_gaps(
        view.ops(), view.spans, *view.window())


def test_dispatch_cover_is_program_span_time_before_the_programs_run():
    view, program = synthetic()
    # replay dispatches 10-100 and 500-600: covered 20-100 and 510-600
    assert program_spans.covered_pct(
        view, program, "replay.dispatch", r"^jit_pallas_grid\(") == \
        pytest.approx(100 * (80 + 90) / (90 + 100))
    # the simulation call 1100-1200: compile_network and tables 1110-1180
    assert program_spans.covered_pct(
        view, program, "sim.call", r"^jit_pallas_grid\(") == \
        pytest.approx(70.0)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = fixture_cells.make(tmp_path_factory.mktemp("checkout"))
    spec = importlib.util.spec_from_file_location(
        "fixture_run_spans", root / "chipbench" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return root, run


@pytest.mark.parametrize("cell,names", [
    ("tiny_cache.evict", ["lane_setup_ms.replay", "lane_setup_ns.replay",
                          "h2d_ms.replay", "h2d_gbps.replay",
                          "unpack_ms.replay", "unpack_gbps.replay",
                          "network_ms.replay", "network_ns.replay"]),
    ("paper_lru_72core.tiny_closed", ["compile_network_ms.sim",
                                      "tables_ms.sim"]),
])
def test_traced_fixture_run_keeps_the_program_spans(checkout, capsys,
                                                    tmp_path, cell, names):
    root, run = checkout
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   "0.5", "--trace", "1", "--keep-trace", str(tmp_path)],
                  root=root, require_tpu=False, pool=run.InlinePool())
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    assert json.loads(out.strip().splitlines()[-1])["correct"]
    (kept,) = tmp_path.glob("*.xplane.pb")
    got = program_spans.read(str(kept))
    assert all(got["metrics"][n] > 0 for n in names), got["metrics"]
    assert any(name.startswith("repro.") for name, _ in got["idle_gaps"])


DATA = Path(__file__).resolve().parent / "data"


def test_program_spans_from_a_v5e_replay_trace():
    """One small replay question traced on a TPU v5e with the program's
    spans (2 policies x 2 capacities x 2048 requests): the spans, their
    counts and the numbers read from them, on the trace's one clock."""
    view, program = program_spans.load(
        str(DATA / "v5e_replay_spans.xplane.pb"))
    got = {n: f(view, program) for n, f in program_spans.READERS.items()}
    assert got == pytest.approx({
        "lane_setup_ms.replay": (291620 + 213409) / 2 * 1e-6,
        "lane_setup_ns.replay": (291620 + 213409) / (2 * 2 * 2048),
        "h2d_ms.replay": (1418620 + 1357780) / 2 * 1e-6,
        "h2d_gbps.replay": 2 * 49208 / (1418620 + 1357780),
        "unpack_ms.replay": (5140950 + 5339940) / 2 * 1e-6,
        "unpack_gbps.replay": 2 * 2 * 2048 * 4 * 4 / (5140950 + 5339940),
        "network_ms.replay": (259050 + 142450 + 186240 + 153350) / 2 * 1e-6,
        "network_ns.replay":
            (259050 + 142450 + 186240 + 153350) / (4 * 2048),
        "compile_network_ms.sim": None, "tables_ms.sim": None,
        "event_ns.sim": None})
    (setup, _), (h2d, _), (unpack, _) = (
        program_spans.named(program, n) for n in
        ("replay.lane_inputs", "replay.h2d", "replay.unpack"))
    assert setup.stats == {"lanes_count": 2, "requests_count": 2048}
    assert h2d.stats == {"h2d_bytes": 49208}
    assert unpack.stats == {"d2h_bytes": 2 * 2048 * 4 * 4}
    assert [s.stats for s in program_spans.named(
        program, "harness.empirical_network")] == [
            {"requests_count": 2048}] * 4
    assert program_spans.covered_pct(
        view, program, "replay.dispatch",
        program_spans.DISPATCHES["replay.dispatch"]) == pytest.approx(
            96.10322439061446)
    gaps = program_spans.idle_gaps(view, program)
    assert gaps[0][0] == "repro.replay.unpack"
    assert gaps[0][1] == pytest.approx(0.01046874)


def test_program_spans_from_a_v5e_event_kernel_trace():
    """One small closed-loop question on the v5e's Pallas event kernel
    (2 hit ratios x 2 seeds x 3000 requests) with the program's spans."""
    view, program = program_spans.load(
        str(DATA / "v5e_closed_spans.xplane.pb"))
    got = {n: f(view, program) for n, f in program_spans.READERS.items()}
    assert got == pytest.approx({
        "lane_setup_ms.replay": None, "lane_setup_ns.replay": None,
        "h2d_ms.replay": None, "h2d_gbps.replay": None,
        "unpack_ms.replay": None, "unpack_gbps.replay": None,
        "network_ms.replay": None, "network_ns.replay": None,
        "compile_network_ms.sim": (2465500 + 2458990) * 1e-6,
        "tables_ms.sim": 15101949 * 1e-6,
        "event_ns.sim": 41976207 / 40761})
    (collect,) = program_spans.named(program, "sim.collect")
    assert collect.stats == {"events_count": 40761}
    # the simulation's host time before its kernel is all in program spans
    assert program_spans.covered_pct(
        view, program, "sim.call",
        program_spans.DISPATCHES["sim.call"]) == pytest.approx(
            99.48385059279094)
    assert [g[0] for g in program_spans.idle_gaps(view, program)[:2]] == [
        "repro.sim.tables", "repro.sim.compile_network"]

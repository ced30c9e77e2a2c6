"""The reduction from a profiler trace to the per-layer metrics, and the
peaks table."""

from pathlib import Path

import pytest

from chipbench import bench, tracing
from chipbench.tracing import Event, TraceView

P = "chipbench."


def view():
    ops = [Event("k", 100, 200), Event("k", 150, 260), Event("x", 400, 450),
           Event("k", 700, 900)]
    spans = [Event(P + "window", 50, 1000), Event(P + "replay.dispatch", 60, 90),
             Event(P + "replay.decode", 300, 380),
             Event(P + "replay.dispatch", 600, 650)]
    modules = [Event("jit_pallas_grid(1)", 95, 265), Event("jit_copy(2)", 400, 450),
               Event("jit_pallas_grid(1)", 690, 905)]
    return TraceView({"/device:TPU:0": ops}, {"/device:TPU:0": modules}, spans)


def test_busy_is_the_union_of_device_op_intervals():
    v = view()
    assert tracing.union(v.ops()) == [[100, 260], [400, 450], [700, 900]]
    assert tracing.busy_ns(tracing.in_window(v)) == 160 + 50 + 200
    assert tracing.idle_pct(v) == pytest.approx(100 * (1 - 410 / 950))


def test_idle_gaps_go_to_the_innermost_open_span():
    v = view()
    lo, hi = v.window()
    gaps = dict(tracing.idle_gaps(v.ops(), v.spans, lo, hi))
    # gaps 50-100 (the first dispatch from 60), 260-400 (the decode from
    # 300 to 380), 450-700 (the second dispatch from 600 to 650), 900-1000
    assert gaps == pytest.approx({
        "no span": (10 + 10 + 40 + 20 + 150 + 50 + 100) * 1e-9,
        "replay.dispatch": (30 + 50) * 1e-9, "replay.decode": 80e-9})


def test_dispatch_is_span_start_to_the_programs_first_run():
    v = view()
    # the kernel's program runs from 95 and from 690
    assert tracing.dispatch_ms(v, "replay.dispatch", r"^jit_pallas_grid\(") \
        == pytest.approx((35 + 90) / 2 * 1e-6)
    assert tracing.dispatch_ms(v, "sim.call", r"^jit_pallas_grid\(") is None


def test_kernel_time_per_unit_of_work():
    ops = tracing.in_window(view())
    assert tracing.device_ns(ops, r"^k$", 10) == pytest.approx((100 + 110
                                                                + 200) / 10)
    assert tracing.device_ns(ops, r"nothing", 10) is None
    assert tracing.top_ops(ops)[0][0] == "k"


def test_peaks_are_keyed_by_device_kind():
    assert tracing.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        tracing.peaks("cpu")


DATA = Path(__file__).resolve().parent / "data"


def read_metrics(trace, names, work):
    v = tracing.load(str(DATA / trace))
    ctx = {"view": v, "work": work, "questions": 1,
           "peaks": lambda: tracing.peaks("TPU v5 lite")}
    return v, {n: bench.metric_reader(n).read(ctx) for n in names}


def test_metrics_from_a_v5e_replay_trace():
    """A traced run of one small replay question on a TPU v5e (2 policies x
    2 capacities x 2048 requests): the kernel, its program, the host spans
    and the idle gaps are found and reduced on the trace's one clock."""
    v, m = read_metrics("v5e_replay.xplane.pb", [
        "dispatch_ms.replay", "replay_kernel_ns", "replay_kernel_roofline",
        "decode_ms.replay", "idle_pct.replay"], work=2 * 2 * 2048)
    lo, hi = v.window()
    assert (hi - lo) == pytest.approx(25063476)
    assert tracing.busy_ns(tracing.in_window(v)) == pytest.approx(4302208)
    assert m["replay_kernel_ns"] == pytest.approx(4276769 / 8192)
    assert m["replay_kernel_roofline"] == pytest.approx(
        100 * 22 / 819e9 * 1e9 / m["replay_kernel_ns"])
    assert m["dispatch_ms.replay"] == pytest.approx(1.494638)
    assert m["decode_ms.replay"] == pytest.approx(7.0491535)
    assert m["idle_pct.replay"] == pytest.approx(
        100 * (1 - 4302208 / 25063476))
    gaps = dict(tracing.idle_gaps(tracing.in_window(v), v.spans, lo, hi))
    assert max(gaps, key=gaps.get) == "replay.decode"


def test_metrics_from_a_v5e_event_kernel_trace():
    """One small closed-loop question on the v5e's Pallas event kernel
    (2 hit ratios x 2 seeds x 3000 requests)."""
    v, m = read_metrics("v5e_closed.xplane.pb", [
        "dispatch_ms.sim", "event_kernel_ns", "idle_pct.sim"],
        work=2 * 2 * 3000)
    assert m["event_kernel_ns"] == pytest.approx(41947596 / 12000)
    assert m["dispatch_ms.sim"] == pytest.approx(19.787749)
    assert 0 < m["idle_pct.sim"] < 100
    assert tracing.top_ops(tracing.in_window(v))[0][0].startswith(
        "%pallas_grid")

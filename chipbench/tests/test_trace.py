"""The trace reduction and the metric readers, on traces built by hand."""
from __future__ import annotations

import pytest

from chipbench import spec, trace
from chipbench.peaks import chip_peaks
from chipbench.run import Context
from chipbench.trace import Span, Trace

MS = 1_000_000  # ns


def dev(name, a, b, op="fusion"):
    return Span(name, a * MS, b * MS, op)


def built() -> Trace:
    """One device, a 100 ms window of two solves: a loop holding an
    ``scd`` kernel and fusions, an all-reduce overlapping a fusion, and
    idle gaps of 10, 5 and 5 ms."""
    ops = [
        dev("while.5", 0, 40, "while"),          # holds the next three
        dev("scd.9", 0, 20, "custom-call"),
        dev("fusion.1", 20, 30),
        dev("scd.10", 30, 40, "custom-call"),
        dev("fusion.2", 50, 70),                  # gap 40-50
        dev("all-reduce.1", 65, 75, "all-reduce"),  # overlaps fusion.2
        dev("copy.3", 80, 95, "copy"),            # gap 75-80, then 95-100
    ]
    host = [Span(trace.SOLVE_SPAN, 0, 60 * MS),
            Span("PjitFunction(jitted)", 38 * MS, 45 * MS),
            Span("np.asarray(jax.Array)", 42 * MS, 48 * MS),
            Span(trace.SOLVE_SPAN, 60 * MS, 100 * MS),
            Span("PjitFunction(_threefry_split)", 74 * MS, 82 * MS)]
    return Trace(devices={0: ops}, host=host)


def test_parse_op_reads_name_and_opcode_from_hlo_text():
    text = ('%scd.9 = (f32[250]{0:T(256)S(1)}, f32[1536,128]{1,0:T(8,128)}) '
            'custom-call(s32[256]{0:T(256)S(1)} %pad.46), '
            'custom_call_target="tpu_custom_call"')
    assert trace.parse_op(text) == ("scd.9", "custom-call")
    loop = ('%while.5 = (s32[]{:T(128)}, f32[8,196608]{1,0:T(8,128)}) '
            'while((s32[]{:T(128)}) %tuple.72), condition=%c')
    assert trace.parse_op(loop) == ("while.5", "while")
    assert trace.parse_op("jit_jitted(123)") == ("jit_jitted(123)", "")


def test_window_spans_the_solves():
    assert built().window() == (0, 100 * MS)
    assert Trace().window() is None


def test_busy_counts_overlapping_events_once():
    t = built()
    # [0,40] + [50,75] + [80,95] = 80 ms; the loop and its body, and the
    # all-reduce over the fusion, overlap
    assert trace.busy_ns(t.devices[0], 0, 100 * MS) == 80 * MS
    assert trace.busy_ns(t.devices[0], 10 * MS, 60 * MS) == 40 * MS


def test_idle_gaps_longest_first():
    gaps = trace.idle_gaps(built().devices[0], 0, 100 * MS)
    assert gaps == [(40 * MS, 50 * MS), (75 * MS, 80 * MS),
                    (95 * MS, 100 * MS)]
    assert trace.idle_gaps([], 0, 5) == [(0, 5)]


def test_kernel_time_by_name_and_collectives_by_opcode():
    ops = built().devices[0]
    assert trace.op_ns(ops, 0, 100 * MS, trace.is_scd) == 30 * MS
    assert trace.count(ops, 0, 100 * MS, trace.is_scd) == 2
    assert trace.op_ns(ops, 0, 100 * MS, trace.is_collective) == 10 * MS
    # clipped to the window
    assert trace.op_ns(ops, 10 * MS, 100 * MS, trace.is_scd) == 20 * MS


def test_breakdown_names_ops_and_gaps():
    bd = trace.breakdown(built(), 0, 0, 100 * MS)
    ops = dict(bd["device_ops"])
    assert "while" not in ops                    # a loop is not an op
    assert ops["scd"] == pytest.approx(0.030)
    assert ops["fusion"] == pytest.approx(0.030)
    assert [name for name, _ in bd["device_ops"]][:2] == ["scd", "fusion"]
    gaps = bd["idle_gaps"]
    assert gaps[0] == ["np.asarray(jax.Array)", pytest.approx(0.010)]
    assert gaps[1] == ["PjitFunction(_threefry_split)", pytest.approx(0.005)]
    assert gaps[2] == ["no host event", pytest.approx(0.005)]


def ctx(t: Trace, rounds=10, chips=1) -> Context:
    return Context(trace=t, window=t.window(), rounds=rounds, solves=2,
                   chips=chips, m=196_608, K=8, H=250, n_local=250,
                   peaks=chip_peaks("TPU v5 lite"))


def test_readers_on_a_built_trace():
    c = ctx(built())
    read = {m: spec.load_reader(m) for m in (
        "device_idle_frac", "rounds_per_s", "scd_ms_per_round",
        "scd_roofline", "round_mfu", "collective_ms_per_round")}
    assert read["device_idle_frac"](c) == pytest.approx(0.2)
    assert read["rounds_per_s"](c) == pytest.approx(100.0)
    assert read["scd_ms_per_round"](c) == pytest.approx(3.0)
    assert read["collective_ms_per_round"](c) == pytest.approx(1.0)
    # two scd calls: 2 x (4*250*196,608 + 16*250 + 8*250 + 8*196,608)
    # bytes at 819 GB/s, over 30 ms
    need = 2 * (4 * 250 * 196_608 + 16 * 250 + 8 * 250 + 8 * 196_608) / 819e9
    assert read["scd_roofline"](c) == pytest.approx(100 * need / 0.030)
    assert 0 < read["round_mfu"](c) < 100


def test_readers_find_nothing_and_return_nothing():
    empty = Trace(devices={0: [dev("fusion.1", 0, 5)]},
                  host=[Span(trace.SOLVE_SPAN, 0, 10 * MS)])
    c = ctx(empty)
    for m in ("scd_ms_per_round", "scd_roofline", "collective_ms_per_round"):
        assert spec.load_reader(m)(c) is None
    no_device = Trace(host=[Span(trace.SOLVE_SPAN, 0, 10 * MS)])
    assert spec.load_reader("device_idle_frac")(ctx(no_device)) is None

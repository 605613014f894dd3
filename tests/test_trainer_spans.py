"""The trainers' profiler spans and the round programs' named scopes,
and the chip benchmark's readers that charge device idle time to them.

A call of ``run`` / ``run_sharded`` (CoCoA) or ``run_workers`` /
``run_sharded`` (mini-batch SGD) traced with ``jax.profiler.trace``
shows ``repro.setup``, then per round a ``repro.round`` step holding
``repro.dispatch`` and ``repro.readback``, then ``repro.finish``. The
compiled rounds name their parts ``workers``, ``exchange``, ``apply``
and ``metric`` in their HLO ``op_name`` metadata.
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import spans, spec, trace  # noqa: E402
from chipbench.run import Context  # noqa: E402
from chipbench.trace import Span, Trace  # noqa: E402
from repro.core import (CoCoAConfig, CoCoATrainer, MinibatchSGD,  # noqa: E402
                        SGDConfig)
from repro.utils import compat  # noqa: E402

SCOPES = ("workers", "exchange", "apply", "metric")
READERS = ("readback_idle_ms_per_round", "dispatch_idle_ms_per_round",
           "call_idle_ms_per_solve")


def problem(m=256, n=16, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((m, n)).astype(np.float32),
            rng.standard_normal(m).astype(np.float32))


def cocoa(K):
    return CoCoATrainer(CoCoAConfig(K=K, H=4, solver="scd_kernel",
                                    partitioner="block"), *problem())


def sgd(K):
    return MinibatchSGD(SGDConfig(K=K, batch_frac=0.5), *problem())


def one_device_mesh():
    return compat.make_mesh((1,), ("workers",))


# (trainer, call) per entry point; the sharded ones on a 1-device mesh
ENTRIES = {
    "cocoa.run": lambda: (cocoa(2), lambda tr, **kw: tr.run(**kw)),
    "cocoa.run_sharded": lambda: (cocoa(1), lambda tr, **kw: tr.run_sharded(
        mesh=one_device_mesh(), **kw)),
    "sgd.run_workers": lambda: (sgd(2), lambda tr, **kw: tr.run_workers(
        p_star=0.0, **kw)),
    "sgd.run_sharded": lambda: (sgd(1), lambda tr, **kw: tr.run_sharded(
        mesh=one_device_mesh(), p_star=0.0, **kw)),
}


def traced(tmp_path, call):
    """Run ``call`` under the profiler inside a solve span, as a caller
    profiling a solve would; return the trace's host line and the
    ``step_num`` of each round span in order."""
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.SOLVE_SPAN):
            hist = call()
    host = trace.load(tmp_path).host
    data = jax.profiler.ProfileData.from_file(
        str(sorted(tmp_path.rglob("*.xplane.pb"))[-1]))
    steps = [dict(e.stats).get("step_num")
             for plane in data.planes for line in plane.lines
             for e in line.events if e.name == spans.ROUND]
    return hist, [s for s in host if s.name.startswith("repro.")], steps


def inside(outer, inner):
    return outer.start <= inner.start and inner.end <= outer.end


@pytest.mark.parametrize("entry", sorted(ENTRIES))
@pytest.mark.parametrize("record_every", [1, 2])
def test_call_records_setup_rounds_and_finish(tmp_path, entry,
                                              record_every):
    trainer, call = ENTRIES[entry]()
    call(trainer, rounds=1)                   # the programs are built
    hist, host, steps = traced(
        tmp_path, lambda: call(trainer, rounds=5, record_every=record_every))
    names = [s.name for s in host]
    assert names[0] == spans.SETUP and names[-1] == spans.FINISH
    assert names.count(spans.SETUP) == names.count(spans.FINISH) == 1
    rounds = [s for s in host if s.name == spans.ROUND]
    assert len(rounds) == hist.rounds[-1] == 5
    assert steps == list(range(1, 6))
    for r in rounds:
        parts = [s for s in host if s is not r and inside(r, s)]
        want = [spans.DISPATCH]
        # the round metric is read back on recorded rounds only
        if r is rounds[-1] or (rounds.index(r) + 1) % record_every == 0:
            want.append(spans.READBACK)
        assert [s.name for s in parts] == want
    assert names.count(spans.READBACK) == len(hist.rounds)
    setup, finish = host[0], host[-1]
    assert setup.end <= rounds[0].start and rounds[-1].end <= finish.start


def test_target_eps_stops_inside_the_round_span(tmp_path):
    trainer, call = ENTRIES["cocoa.run"]()
    p_star = trainer.p_star
    call(trainer, rounds=1, p_star=p_star)
    hist, host, _ = traced(tmp_path, lambda: call(
        trainer, rounds=200, target_eps=1e-2, p_star=p_star))
    assert hist.subopt[-1] <= 1e-2 and hist.rounds[-1] < 200
    assert sum(s.name == spans.ROUND for s in host) == hist.rounds[-1]
    assert host[-1].name == spans.FINISH


def test_spans_leave_the_trajectory_unchanged(tmp_path):
    """A traced call and an untraced one return the same iterate."""
    trainer, call = ENTRIES["cocoa.run"]()
    plain = call(trainer, rounds=4)
    alpha = trainer.alpha_final.copy()
    hist, _, _ = traced(tmp_path, lambda: call(trainer, rounds=4))
    assert hist.primal == plain.primal
    np.testing.assert_array_equal(trainer.alpha_final, alpha)


def op_names(round_fn, local, shared) -> set:
    text = round_fn.lower(local, shared, jax.random.key(0), 1) \
        .compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("layout", ["virtual", "sharded"])
@pytest.mark.parametrize("algorithm", ["cocoa", "sgd"])
def test_round_programs_carry_named_scopes(layout, algorithm):
    """Each part of a compiled round keeps its scope in the HLO
    metadata a device trace shows: the same names in both layouts. The
    SCD kernel fetches its own columns (CoCoA), so no ``gather`` scope
    is left around it."""
    K = 2 if layout == "virtual" else 1
    trainer = cocoa(K) if algorithm == "cocoa" else sgd(K)
    local, shared = trainer.init_state()
    if layout == "virtual":
        round_fn = trainer._round_fn
    else:
        round_fn = trainer.build_sharded_round(one_device_mesh())
    names = op_names(round_fn, local, shared)
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), (scope, sorted(names))
    assert not [n for n in names if "/gather/" in n]


# ---------------------------------------------------------------------------
# the readers, on traces built by hand (times in ms)
# ---------------------------------------------------------------------------
MS = 1_000_000


def solve_spans(t0, setup, rounds, finish, end):
    """One solve's host spans: ``setup`` and ``finish`` are (a, b);
    each round is (a, dispatch end, b), its readback from the dispatch
    end to b."""
    host = [Span(trace.SOLVE_SPAN, t0 * MS, end * MS),
            Span(spans.SETUP, setup[0] * MS, setup[1] * MS)]
    for a, d, b in rounds:
        host += [Span(spans.ROUND, a * MS, b * MS),
                 Span(spans.DISPATCH, a * MS, d * MS),
                 Span("PjitFunction(jitted)", a * MS, d * MS),
                 Span(spans.READBACK, d * MS, b * MS)]
    return host + [Span(spans.FINISH, finish[0] * MS, finish[1] * MS)]


def built(busy=((3, 7), (9, 18), (22, 33), (36, 40), (55, 68), (73, 88),
                (92, 93))) -> Trace:
    """Two solves of two rounds in a 100 ms window. Device 0's idle
    gaps (43 ms) fall 12 ms under readbacks, 2 ms under the second
    dispatches, 19 ms under set-up, first dispatches and finishes, and
    10 ms under no program span (45-50, 95-100)."""
    host = (solve_spans(0, (0, 5), [(5, 8, 20), (20, 21, 35)], (35, 45), 50)
            + solve_spans(50, (50, 52), [(52, 60, 70), (70, 71, 90)],
                          (90, 95), 100))
    ops = [Span(f"fusion.{i}", a * MS, b * MS, "fusion")
           for i, (a, b) in enumerate(busy)]
    return Trace(devices={0: ops}, host=sorted(host, key=lambda s: s.start))


def ctx(trc: Trace) -> Context:
    return Context(trace=trc, window=trc.window() or (0, 0), rounds=4,
                   solves=2, chips=1, m=1024, K=8, H=16, n_local=8,
                   peaks=None)


def read(name, c):
    return spec.load_reader(name, ROOT)(c)


def test_readers_split_idle_by_host_phase():
    c = ctx(built())
    assert read("readback_idle_ms_per_round", c) == pytest.approx(12 / 4)
    assert read("dispatch_idle_ms_per_round", c) == pytest.approx(2 / 4)
    assert read("call_idle_ms_per_solve", c) == pytest.approx(19 / 2)


def test_first_dispatch_of_each_solve_goes_to_the_call():
    """Idle only under each solve's first dispatch (where a new program
    is built) is the call's, not the rounds'."""
    c = ctx(built(busy=((0, 5), (8, 52), (60, 100))))
    assert read("dispatch_idle_ms_per_round", c) == 0.0
    assert read("readback_idle_ms_per_round", c) == 0.0
    assert read("call_idle_ms_per_solve", c) == pytest.approx((3 + 8) / 2)


def test_readers_sum_to_at_most_the_window_idle():
    c = ctx(built())
    idle_ms = read("device_idle_frac", c) * c.window_s * 1e3
    parts = (read("readback_idle_ms_per_round", c) * spans.rounds(c)
             + read("dispatch_idle_ms_per_round", c) * spans.rounds(c)
             + read("call_idle_ms_per_solve", c) * spans.solves(c))
    assert idle_ms == pytest.approx(43)
    assert parts == pytest.approx(33) and parts <= idle_ms


@pytest.mark.parametrize("name", READERS)
def test_readers_none_without_device_ops_or_program_spans(name):
    trc = built()
    assert read(name, ctx(Trace(devices={}, host=trc.host))) is None
    # a program without the spans: the solve spans alone
    bare = [s for s in trc.host if not s.name.startswith("repro.")]
    assert read(name, ctx(Trace(devices=trc.devices, host=bare))) is None


def test_overlap_of_interval_lists():
    assert spans.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.overlap_ns([(0, 10)], [(10, 20)]) == 0
    assert spans.overlap_ns([], [(0, 1)]) == 0

"""The bench harness itself: registry, schema round-trip, compare gate,
and a smoke-tier end-to-end run on a tiny problem."""
import json

import pytest

from repro.bench import compare as cmp_mod
from repro.bench import registry, schema
from repro.bench.run import run_benchmarks
from repro.bench.timing import TimingPolicy


# ---------------------------------------------------------------- registry
def test_registry_registration_and_lookup():
    @registry.benchmark("_test_dummy", figures="none")
    def dummy(ctx):
        """A dummy benchmark."""
        return {"timings_s": {"x": 1.0}}

    try:
        spec = registry.get("_test_dummy")
        assert spec.fn is dummy
        assert spec.description == "A dummy benchmark."
        assert "_test_dummy" in registry.names()
        # duplicate name with a different function is rejected
        with pytest.raises(ValueError, match="already registered"):
            @registry.benchmark("_test_dummy")
            def other(ctx):
                return {}
    finally:
        registry._REGISTRY.pop("_test_dummy", None)


def test_registry_loads_all_ported_benchmarks():
    names = registry.load_default_benchmarks()
    assert {"overheads", "h_sweep", "convergence", "kernels", "roofline",
            "scaling", "drivers"} <= set(names)


def test_unknown_benchmark_and_tier():
    with pytest.raises(KeyError, match="unknown benchmark"):
        registry.get("_no_such_bench")
    with pytest.raises(ValueError, match="unknown tier"):
        registry.BenchContext(tier="warp")


# ------------------------------------------------------------------ schema
def _result(**over):
    kw = dict(benchmark="demo", tier="smoke",
              env=schema.EnvFingerprint.capture(),
              params={"m": 8}, timings_s={"t": 0.5}, counters={"r": 3},
              rows=[{"a": 1}], notes=["n"])
    kw.update(over)
    return schema.BenchResult(**kw)


def test_schema_roundtrip(tmp_path):
    res = _result()
    path = res.write(str(tmp_path))
    assert path.endswith("BENCH_demo.json")
    back = schema.load(path)
    assert back.benchmark == "demo"
    assert back.timings_s == {"t": 0.5}
    assert back.env.jax == res.env.jax
    assert back.schema_version == schema.SCHEMA_VERSION


def test_schema_validation_rejects_junk(tmp_path):
    res = _result()
    d = res.to_dict()
    d["schema_version"] = 999
    d["timings_s"] = {"t": "fast"}
    p = tmp_path / "BENCH_bad.json"
    p.write_text(json.dumps(d))
    with pytest.raises(ValueError, match="schema_version"):
        schema.load(str(p))
    assert any("timings_s" in s for s in schema.validate(d))


# ----------------------------------------------------------------- compare
def test_compare_same_passes_slowdown_fails(tmp_path):
    old = _result(timings_s={"step": 0.1, "round": 0.02})
    same = _result(timings_s={"step": 0.1, "round": 0.02})
    deltas = cmp_mod.compare_results(old, same, max_regression=1.25)
    assert not any(d.regression for d in deltas)
    slow = _result(timings_s={"step": 0.15, "round": 0.02})  # +50%
    deltas = cmp_mod.compare_results(old, slow, max_regression=1.25)
    assert [d.metric for d in deltas if d.regression] == ["step"]
    fast = _result(timings_s={"step": 0.01, "round": 0.01})  # improvement
    assert not any(d.regression
                   for d in cmp_mod.compare_results(old, fast, 1.25))


def test_compare_cli_exit_codes(tmp_path):
    old_dir, new_dir = tmp_path / "old", tmp_path / "new"
    _result().write(str(old_dir))
    _result().write(str(new_dir))
    assert cmp_mod.main([str(old_dir), str(new_dir)]) == 0
    _result(timings_s={"t": 5.0}).write(str(new_dir))  # 10x slower
    assert cmp_mod.main([str(old_dir), str(new_dir),
                         "--max-regression", "1.25"]) == 1


def test_compare_min_time_floor():
    old = _result(timings_s={"tiny": 1e-6})
    new = _result(timings_s={"tiny": 3e-6})  # 3x, but below the floor
    deltas = cmp_mod.compare_results(old, new, max_regression=1.25,
                                     min_time_s=1e-4)
    assert not any(d.regression for d in deltas)


# ----------------------------------------------------- exact-counter gate
_BYTES = "comm_bytes_per_round_cocoa_persistent"


def test_exact_counter_passes_on_equal_counters(tmp_path):
    old_dir, new_dir = tmp_path / "old", tmp_path / "new"
    counters = {_BYTES: 3072, f"{_BYTES}_stale": 3072, "rounds_to_eps_x": 15}
    _result(counters=dict(counters)).write(str(old_dir))
    _result(counters=dict(counters)).write(str(new_dir))
    assert cmp_mod.main([str(old_dir), str(new_dir),
                         "--exact-counter", "comm_bytes_per_round_"]) == 0


def test_exact_counter_fails_on_one_byte_drift(tmp_path):
    old_dir, new_dir = tmp_path / "old", tmp_path / "new"
    _result(counters={_BYTES: 3072}).write(str(old_dir))
    _result(counters={_BYTES: 3073}).write(str(new_dir))  # one byte off
    assert cmp_mod.main([str(old_dir), str(new_dir),
                         "--exact-counter", "comm_bytes_per_round_"]) == 1
    # ...while without the flag the drifted counter is not gated at all
    assert cmp_mod.main([str(old_dir), str(new_dir)]) == 0
    # and the gate really is exact equality, not a tolerance: the delta
    # itself flags the 1-byte drift
    deltas = cmp_mod.compare_counters(
        _result(counters={_BYTES: 3072}), _result(counters={_BYTES: 3073}),
        ["comm_bytes_per_round_"])
    assert [d.regression for d in deltas] == [True]


def test_exact_counter_ignores_K_suffixed_on_full_mesh_baseline(tmp_path):
    """A device-starved candidate emits `_K<n>`-suffixed byte counters
    (its sharded worker count differs), which must NOT pair with — and
    spuriously fail against — a full-mesh baseline: counters present on
    only one side are skipped, both ways."""
    old_dir, new_dir = tmp_path / "old", tmp_path / "new"
    # full-mesh baseline: unsuffixed counters at K=4
    _result(counters={_BYTES: 3072}).write(str(old_dir))
    # device-starved candidate: same cell at K=2, suffixed (and with
    # genuinely different bytes — exactly why it must not be compared)
    _result(counters={f"{_BYTES}_K2": 1536}).write(str(new_dir))
    assert cmp_mod.main([str(old_dir), str(new_dir),
                         "--exact-counter", "comm_bytes_per_round_"]) == 0
    deltas = cmp_mod.compare_counters(
        _result(counters={_BYTES: 3072}),
        _result(counters={f"{_BYTES}_K2": 1536}),
        ["comm_bytes_per_round_"])
    assert deltas == []  # nothing paired, nothing gated


# ------------------------------------------------------------------ timing
def test_timing_policy_reduce():
    assert TimingPolicy(reduce="min").combine([3.0, 1.0, 2.0]) == 1.0
    assert TimingPolicy(reduce="median").combine([3.0, 1.0, 2.0]) == 2.0
    with pytest.raises(ValueError):
        TimingPolicy(reduce="max").combine([1.0])


# ------------------------------------------------------- end-to-end smoke
def test_smoke_tier_end_to_end(tmp_path):
    """One sweep-backed benchmark + the driver/comm-scheme coverage
    benchmark, smoke tier, in-process (1 device -> K=1 sharded mesh).
    Checks emitted files are schema-valid and carry gateable timings."""
    results = run_benchmarks(tier="smoke", only=["kernels", "drivers"],
                             out_dir=str(tmp_path), verbose=False)
    by = {r.benchmark: r for r in results}
    assert by["kernels"].status == "ok"
    assert by["drivers"].status == "ok"
    for name in ("kernels", "drivers"):
        loaded = schema.load(str(tmp_path / schema.result_filename(name)))
        assert loaded.tier == "smoke"
        assert loaded.timings_s, name
        assert loaded.env.device_count >= 1
    # drivers must cover the full matrix: 3 algorithms x both execution
    # drivers x every transport-x-codec scheme x both exchange modes
    # (72 rows — the 36 modelled-bytes cells each run on both drivers)
    # ... plus the regime cells (full ExchangeConfig specs: straggler,
    # bounded staleness, elastic membership) and the collective-backend
    # cells (ring fabric); a regime cell's sharded leg is skipped on a
    # device-starved mesh (membership events name absolute worker
    # indices the smaller mesh cannot host)
    from benchmarks.bench_drivers import (BACKEND_CELLS, CODEC_CELLS,
                                          REGIME_CELLS)
    from repro.core import ExchangeConfig

    got = {(r["algorithm"], r["driver"], r["scheme"], r["mode"])
           for r in by["drivers"].rows}
    k_sh = by["drivers"].params["K_sharded"]
    k_virt = by["drivers"].params["K_virtual"]
    expected = {(a, d, s, m)
                for a in ("cocoa", "minibatch_scd", "minibatch_sgd")
                for d in ("virtual", "sharded")
                for s in ("persistent", "spark_faithful",
                          "compressed:f32", "compressed:int8",
                          "compressed:int4", "reduce_scatter")
                for m in ("sync", "stale")}
    for algo, spec in REGIME_CELLS + BACKEND_CELLS + CODEC_CELLS:
        ex = ExchangeConfig.parse(spec)
        drivers = (("virtual", "sharded")
                   if ex.membership.empty or k_sh == k_virt
                   else ("virtual",))
        expected |= {(algo, d, spec, ex.mode.spec) for d in drivers}
    assert got == expected
    # every compressed row is labelled with its codec
    assert {r["codec"] for r in by["drivers"].rows
            if r["scheme"].startswith("compressed")} == {
        "f32", "int8", "int4", "int2", "topk(r=0.125)",
        "ef:int4", "ef:int2", "ef:topk(r=0.125)"}
    # every cell reports modelled bytes sized to the scheme's dtypes —
    # except reduce_scatter and the ring backend on a single-device
    # mesh, whose ring volumes are genuinely zero at K=1
    k_sh = by["drivers"].params["K_sharded"]
    for r in by["drivers"].rows:
        if k_sh == 1 and (r["scheme"] == "reduce_scatter"
                          or "/ring" in r["scheme"]):
            assert r["comm_bytes_per_round"] == 0
        else:
            assert r["comm_bytes_per_round"] > 0


# ------------------------------------------------------------ chip peaks
def test_chip_peaks_known_kind_and_unknown_raises():
    from repro.launch.mesh import V5E, chip_peaks, kernel_roofline

    peaks = chip_peaks(V5E)
    rl = kernel_roofline(197e9, 819e6, 1e-3, peaks)
    assert rl["flops_frac_of_peak"] == pytest.approx(1.0)
    assert rl["bw_frac_of_hbm"] == pytest.approx(1.0)
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            chip_peaks(kind)


@pytest.mark.parametrize("kind,measured", [("TPU v5 lite", True),
                                           ("cpu", False)])
def test_kernel_roofline_summary_only_on_known_chips(tmp_path, kind,
                                                     measured):
    """``repro.launch.roofline --kernels`` reports peak fractions only
    for a device with published peaks, and otherwise says they were
    not measured (in a subprocess: importing the module fakes 512
    host devices)."""
    import os
    import subprocess
    import sys

    bench = {"env": {"device_kind": kind},
             "counters": {"model_flops_k": 1000, "model_bytes_k": 4000},
             "timings_s": {"k": 1e-6}}
    src = tmp_path / "BENCH_kernels.json"
    src.write_text(json.dumps(bench))
    out = tmp_path / "ROOFLINE_kernels.json"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    r = subprocess.run([sys.executable, "-m", "repro.launch.roofline",
                        "--kernels", str(src), "--out", str(out)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    summary = json.loads(out.read_text())
    cell = summary["cells"]["k"]
    assert cell["model_flops"] == 1000
    assert ("flops_frac_of_peak" in cell) is measured
    assert ("notes" in summary) is not measured

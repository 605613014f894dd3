"""Smoke run of the CoCoA kernel path on TPU.

    python chip_smoke.py                # one chip: the virtual driver
    python chip_smoke.py --four-chips   # four chips: the sharded driver

One chip: CoCoA ridge on a seeded dense problem, K = 8 virtual workers,
H = n_local, the Pallas SCD solver, a few rounds through
``CoCoATrainer.run`` under the ``persistent`` and ``compressed:int8``
exchanges. Before any round runs, each round is compiled ahead of time
and its compiled text must hold the Pallas kernels as
``tpu_custom_call``s: the SCD solve, and under ``compressed:int8`` also
the quantize and the decode+reduce kernels. Nothing falls back to
interpret mode or to the jnp oracles. Each run is checked against the
same rounds with the jnp solver (``scd_ref``), round by round, and
against the ridge optimum solved on the host in float64.

Four chips: ``CoCoATrainer.run_sharded`` with K = 4 over a 4-chip mesh,
for both exchanges, against the virtual driver on the same problem,
round by round; the compiled sharded round must move its update over
all four devices.

Timings printed here are smoke timings, not a benchmark. Any failed
check raises and the script exits non-zero. The last line of standard
output is the JSON result.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the problem: webspam-like Zipf column skew, dense stand-in
M, N, DENSITY, LAM = 65_536, 8_192, 0.1, 1.0
ROUNDS = 10
EXCHANGES = ("persistent", "compressed:int8")
# per-round primal agreement: Pallas kernel vs jnp solver, and the
# sharded driver vs the virtual driver (f32 sums in different orders)
REF_RTOL = 1e-4
FOUR_CHIP_RTOL = 1e-4
KERNELS = {"persistent": {"scd"},
           "compressed:int8": {"scd", "quantize_pack_int8",
                               "decode_reduce_int8"}}
CUTS = (
    f"dense {M:,} x {N:,} at density {DENSITY} stands in for webspam's "
    f"350,000 x 16.6M sparse corpus: the data layer is dense",
    "p_star comes from a host float64 normal-equations solve, "
    "O(m n^2): there is no in-graph certificate yet",
    f"m = {M:,}: the SCD kernel's column stream needs more VMEM than "
    f"the scoped limit at m = 262,144",
)


def host_optimum(A: np.ndarray, b: np.ndarray) -> float:
    """Ridge optimum P* = P(alpha*) solved and evaluated in float64 on
    the host, independent of every device code path."""
    from repro.core.glm import ridge_exact

    A64, b64 = A.astype(np.float64), b.astype(np.float64)
    alpha = ridge_exact(A64, b64, LAM)
    r = A64 @ alpha - b64
    return float(0.5 * r @ r + 0.5 * LAM * alpha @ alpha)


def check(ok, what) -> None:
    """A failed check raises (``assert`` would vanish under ``-O``)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def check_trajectory(name: str, hist, p_star: float) -> None:
    p = np.asarray(hist.primal)
    s = np.asarray(hist.subopt)
    print(f"{name}: primal {p.tolist()}")
    print(f"{name}: subopt {s.tolist()}")
    check(len(p) == ROUNDS and np.isfinite(p).all(),
          f"{name}: {ROUNDS} finite primal values")
    check(p[-1] < p[0], f"{name}: primal decreases")
    check(s[-1] < s[0], f"{name}: suboptimality falls below round 1's")
    # the host optimum bounds every iterate's objective from below
    check(p.min() >= p_star * (1 - 1e-6),
          f"{name}: primal {p.min()!r} >= p_star {p_star!r}")


def agree(name: str, got, want, rtol: float) -> None:
    got, want = np.asarray(got.primal), np.asarray(want.primal)
    rel = np.abs(got - want) / np.abs(want)
    print(f"{name}: max per-round relative primal difference "
          f"{rel.max():.3e} (tolerance {rtol:g})")
    check(rel.max() <= rtol, f"{name}: {rel.tolist()} within {rtol:g}")


def compile_round(round_fn, trainer):
    import jax

    t0 = time.perf_counter()
    text = round_fn.lower(*trainer.init_state(), jax.random.key(0),
                          1).compile().as_text()
    return text, time.perf_counter() - t0


def run_timed(name, run, rounds):
    """One warm-up round (compiles), then the timed rounds."""
    t0 = time.perf_counter()
    run(1)
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    hist = run(rounds)
    per = (time.perf_counter() - t0) / rounds
    print(f"{name}: first round {warm:.3f} s incl. compile; "
          f"{per * 1e3:.3f} ms/round over {rounds} rounds "
          f"(smoke timing, not a benchmark)")
    return hist


def one_chip(A, b, p_star, K=8):
    from repro.analysis.graph import pallas_kernels
    from repro.core import CoCoAConfig, CoCoATrainer

    for ex in EXCHANGES:
        hists = {}
        for solver in ("scd_kernel", "scd_ref"):
            name = f"{ex} {solver}"
            tr = CoCoATrainer(CoCoAConfig(
                K=K, H=N // K, lam=LAM, solver=solver, exchange=ex,
                partitioner="block", seed=0), A, b)
            if solver == "scd_kernel":
                text, secs = compile_round(tr._round_fn, tr)
                found = pallas_kernels(text)
                print(f"{name}: round compiled in {secs:.1f} s; "
                      f"tpu_custom_call kernels {sorted(found)}")
                check(KERNELS[ex] <= found,
                      f"{name}: {sorted(KERNELS[ex])} compiled")
            hists[solver] = run_timed(
                name, lambda r: tr.run(r, p_star=p_star), ROUNDS)
            check_trajectory(name, hists[solver], p_star)
            del tr
            gc.collect()
        agree(f"{ex} kernel vs scd_ref", hists["scd_kernel"],
              hists["scd_ref"], REF_RTOL)


def four_chips(A, b, p_star, K=4):
    from repro.analysis.graph import lift_hlo, pallas_kernels
    from repro.core import CoCoAConfig, CoCoATrainer
    from repro.utils.compat import make_mesh

    mesh = make_mesh((K,), ("workers",))
    for ex in EXCHANGES:
        tr = CoCoATrainer(CoCoAConfig(
            K=K, H=N // K, lam=LAM, solver="scd_kernel", exchange=ex,
            partitioner="block", seed=0), A, b)
        text, secs = compile_round(tr.build_sharded_round(mesh), tr)
        found = pallas_kernels(text)
        graph = lift_hlo(text)
        kind, dtype = (("all-reduce", "f32") if ex == "persistent"
                       else ("all-gather", "s8"))
        wide = [op for op in graph.ops(kind)
                if dtype in op.operand_dtypes + op.result_dtypes
                and op.replica_groups
                and any(len(g) == K for g in op.replica_groups)]
        print(f"{ex} sharded: round compiled in {secs:.1f} s; "
              f"kernels {sorted(found)}; {len(wide)} {dtype} {kind} "
              f"over {K} devices; collectives "
              f"{' | '.join(graph.summary().splitlines())}")
        check(KERNELS[ex] <= found, f"{ex}: {sorted(KERNELS[ex])} compiled")
        check(wide, f"{ex}: a {dtype} {kind} over {K} devices")
        sharded = run_timed(
            f"{ex} sharded", lambda r: tr.run_sharded(r, mesh=mesh,
                                                      p_star=p_star),
            ROUNDS)
        check_trajectory(f"{ex} sharded", sharded, p_star)
        virtual = run_timed(f"{ex} virtual",
                            lambda r: tr.run(r, p_star=p_star), ROUNDS)
        check_trajectory(f"{ex} virtual", virtual, p_star)
        agree(f"{ex} sharded vs virtual", sharded, virtual, FOUR_CHIP_RTOL)
        del tr
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded driver and its "
                         "virtual-driver reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.utils.cache import enable_compilation_cache

    cache = enable_compilation_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"this script runs only on the chip", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    print(f"device {devices[0].device_kind} x{len(devices)}; "
          f"compilation cache {cache}")
    for cut in CUTS:
        print(f"cut: {cut}")

    from repro.data import make_glm_data

    t0 = time.perf_counter()
    A, b, _ = make_glm_data(m=M, n=N, density=DENSITY, seed=args.seed)
    print(f"data {M} x {N} made on the host in "
          f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    p_star = host_optimum(A, b)
    print(f"p_star {p_star!r} (host float64 solve, "
          f"{time.perf_counter() - t0:.1f} s)")

    (four_chips if args.four_chips else one_chip)(A, b, p_star)

    stats = devices[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak device memory {stats['peak_bytes_in_use'] / 2**30:.2f}"
              f" GiB on device 0")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

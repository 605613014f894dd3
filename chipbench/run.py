"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is resolved by name from ``BENCHMARK.json`` (see ``spec.py``).
Set-up makes the problem on the device from the seed, builds the
program's trainer from it, computes the reference optimum p*, and
calls the entry once for two rounds, which runs every program a solve
runs. The window then runs solves back to back for
``--seconds``: a solve is one call of the trainer's entry (``run`` or
``run_sharded``, as the configuration says) from alpha = 0 until its
certificate (P - p*) / (P(0) - p*) <= eps, called as users call it. A
solve that starts inside the window runs to its end.

``--trace 0`` reports the end-to-end metrics: ``time_to_eps_s`` (the
solves' total wall time over their count), ``time_to_eps_p95_s`` and
``setup_s``. ``--trace 1`` traces up to ``TRACE_SECONDS`` of solves with
the JAX profiler and reports the cell's per-layer metrics, each read by
its own ``chipbench/metrics/<name>.py``.

After the window every answer is compared with the reference
(``check.py``); each number compared is printed beside its limit as
the last lines of standard error and under ``checks`` in the result.
The last line of standard output is the result, one JSON object. With
no TPU, or fewer chips than the cell asks for, the run exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TRACE_SECONDS = 10.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


def check_devices(chips: int):
    """The devices the cell runs on: the first ``chips`` TPUs. Nothing
    falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


class Counters:
    """Programs JAX builds (``backend_compile_duration`` fires for each,
    whether compiled or loaded from the persistent cache) and the
    persistent cache's hits."""

    def __init__(self):
        from jax import monitoring

        self.built = self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.built, self.hits


@dataclass
class Solve:
    seconds: float
    rounds: int
    reported: float       # the primal the solve's last round reported
    subopt: float         # the solve's own certificate
    alpha: object         # the returned global alpha (host)


class Entry:
    """The system under test: the program's trainer on the cell's
    problem, and the call users make to solve it."""

    def __init__(self, cell, A, b, coord_seed: int):
        from repro.core import CoCoAConfig, CoCoATrainer

        t, mix = cell.config["trainer"], cell.mix
        self.K = t["K"]
        self.n_local = -(-cell.config["features"] // self.K)
        self.H = self.n_local if mix["H"] == "n_local" else int(mix["H"])
        self.eps, self.max_rounds = float(mix["eps"]), int(mix["max_rounds"])
        self.driver = t["driver"]
        if self.driver not in ("run", "run_sharded"):
            raise ValueError(f"unknown driver {self.driver!r}")
        self.trainer = CoCoATrainer(CoCoAConfig(
            K=self.K, H=self.H, lam=t["lam"], eta=1.0, solver=t["solver"],
            exchange=mix["exchange"], partitioner=t["partitioner"],
            seed=coord_seed), A, b)

    def solve(self, p_star: float, rounds: int | None = None) -> Solve:
        """One call of the entry; ``rounds`` caps it below the mix's
        ``max_rounds`` (the warm-up: the same programs, fewer rounds)."""
        call = getattr(self.trainer, self.driver)
        t0 = time.perf_counter()
        hist = call(rounds or self.max_rounds, record_every=1,
                    target_eps=self.eps, p_star=p_star)
        seconds = time.perf_counter() - t0
        return Solve(seconds, hist.rounds[-1], hist.primal[-1],
                     hist.subopt[-1], self.trainer.alpha_final)


@dataclass
class Context:
    """What a per-layer metric's reader sees."""
    trace: object          # chipbench.trace.Trace
    window: tuple          # (lo, hi) ns on the trace's clock
    rounds: int            # rounds of the traced solves
    solves: int
    chips: int
    m: int
    K: int
    H: int
    n_local: int
    peaks: object          # chipbench.peaks.ChipPeaks

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def device0(self) -> list:
        return self.trace.devices[min(self.trace.devices)] \
            if self.trace.devices else []


def _window(entry, p_star, seconds):
    """Solves back to back until ``seconds`` have passed; a solve that
    started runs to its end. A solve that raises ends the window. Each
    solve is a span in the profiler's trace when one is recorded."""
    import jax

    solves, failed, attempted = [], 0, 0
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
        attempted += 1
        try:
            with jax.profiler.TraceAnnotation("chipbench.solve"):
                s = entry.solve(p_star)
        except Exception as e:  # noqa: BLE001 - a failed answer, reported
            log(f"solve {attempted} raised {type(e).__name__}: {e}")
            failed += 1
            break
        if s.subopt <= entry.eps:
            solves.append(s)
        else:
            failed += 1
    return solves, failed, attempted


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: Path = ROOT, devices_for=check_devices) -> dict:
    import jax
    import numpy as np

    from chipbench import check, data, reference, spec
    from chipbench import trace as tr
    from chipbench.peaks import chip_peaks
    from repro.utils.cache import enable_compilation_cache

    cell = spec.resolve(workload, root)
    cache_dir = enable_compilation_cache()
    # every program the cell uses goes to the cache, however quick its
    # compile, so that only the first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = devices_for(cell.chips)[:cell.chips]
    kind = devices[0].device_kind
    peaks = chip_peaks(kind) if devices[0].platform == "tpu" else None
    log(f"cell {workload}: {kind} x{len(jax.devices())}, using "
        f"{cell.chips}; compilation cache {cache_dir}")
    counters = Counters()
    _, coord_word, sample_word = data.seed_words(seed, 3)
    cfg, lam = cell.config, float(cell.config["trainer"]["lam"])

    t = time.perf_counter()
    A_dev, b_dev = data.make_problem(cfg, seed)
    A, b = np.asarray(A_dev), np.asarray(b_dev)
    log(f"setup: problem {A.shape} made on the device and copied to the "
        f"host in {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    p_star, _ = reference.p_star(A_dev, A, b, lam)
    p_zero = 0.5 * float(np.dot(b.astype(np.float64), b))
    del A_dev, b_dev
    log(f"setup: reference p* {p_star!r}, P(0) {p_zero!r} in "
        f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    entry = Entry(cell, A, b, coord_word % 2**31)
    log(f"setup: trainer K={entry.K} H={entry.H} ({entry.driver}) built in "
        f"{time.perf_counter() - t:.3f} s")
    # two rounds run every program a solve runs: the round, the key
    # split, the certificate's read-back and the final copies
    warm = entry.solve(p_star, rounds=2)
    log(f"setup: warm-up call ({warm.rounds} rounds) {warm.seconds:.3f} s")
    setup_s = time.perf_counter() - T0
    log(f"setup: {setup_s:.3f} s from process start")

    built0 = counters.snapshot()
    trace_dir = Path(tempfile.mkdtemp(prefix="chipbench-trace-")) \
        if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            try:
                solves, failed, attempted = _window(
                    entry, p_star, min(seconds, TRACE_SECONDS))
            finally:
                jax.profiler.stop_trace()
        else:
            solves, failed, attempted = _window(entry, p_star, seconds)
        built1 = counters.snapshot()
        log(f"window: {attempted} solves, {failed} failed; programs built "
            f"in the window {built1[0] - built0[0]} "
            f"({built1[1] - built0[1]} from the persistent cache)")
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        K, H, n_local = entry.K, entry.H, entry.n_local
        del entry, warm
        gc.collect()

        numbers = check.compare(
            [(s.alpha, s.reported) for s in solves], failed, A=A, b=b,
            lam=lam, p_star=p_star, p_zero=p_zero, eps=float(cell.mix["eps"]),
            gap_limit=float(cfg["limits"]["primal_gap"]),
            seed_word=sample_word)
        correct = attempted > 0 and check.passed(numbers)

        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak}
        result = {"correct": correct, "attempted": attempted,
                  "failed": failed}
        times = [s.seconds for s in solves]
        if not trace:
            values = {"setup_s": setup_s}
            if times:
                values["time_to_eps_s"] = sum(times) / len(times)
                values["time_to_eps_p95_s"] = float(np.percentile(times, 95))
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end if m["name"] in values}
            log(f"solves: {len(times)}, rounds {[s.rounds for s in solves]}"
                f", seconds {times}")
        else:
            trc = tr.load(trace_dir)
            window = trc.window() or (0, 0)
            ctx = Context(trace=trc, window=window,
                          rounds=sum(s.rounds for s in solves),
                          solves=len(solves), chips=cell.chips, m=A.shape[0],
                          K=K, H=H, n_local=n_local, peaks=peaks)
            metrics = {}
            for m in cell.per_layer if ctx.window_s > 0 else []:
                value = spec.load_reader(m["name"], root)(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            used = sorted(trc.devices)[:cell.chips]
            device["busy_s"] = sum(
                tr.busy_ns(trc.devices[d], *window) for d in used
            ) * 1e-9 / max(len(used), 1)
            device["window_s"] = ctx.window_s
            result["breakdown"] = tr.breakdown(trc, used[0], *window) \
                if used else {"device_ops": [], "idle_gaps": []}
            log(f"trace: {len(solves)} solves, {ctx.rounds} rounds in "
                f"{ctx.window_s:.3f} s; devices {sorted(trc.devices)}")
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    result.update(metrics=metrics, device=device, checks={
        k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()})
    for line in check.lines(numbers):
        log(line)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        log(f"chipbench: {e}; this benchmark runs only on the chip")
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

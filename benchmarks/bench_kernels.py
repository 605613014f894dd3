"""Kernel microbenchmark: the Pallas kernels (interpret on CPU;
compiled on TPU) vs their pure-jnp oracles, timed under the harness's
warmup/repeat/min discipline — the tiled SCD local solver, the fused
quantize+pack wire encoders (int8 / packed int4 / packed int2), the
fused decode+mean gather-side reducers, and the fused top-k select.
Every fused kernel's interpret-mode output is asserted bit-identical
to its codec oracle, so cost AND correctness both show up in the
trajectory.

Each Pallas cell also reports its roofline position: ``model_flops_*``
and ``model_bytes_*`` are machine-independent operation/traffic models
(exact-gated in CI under the ``model_`` prefix — drift means the
kernel's work model changed, not that the host got slower), and
``roofline_flops_frac_*`` / ``roofline_bw_frac_*`` divide the achieved
rates by the chip's peaks in ``repro.launch.mesh.CHIP_PEAKS``. They are
emitted only on a device in that table; elsewhere (the CPU, where the
kernels run in interpret mode) the notes say they were not measured.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.bench.registry import BenchContext, benchmark
from repro.bench.timing import TimingPolicy, time_callable
from repro.comm.codec import get_codec
from repro.core.partition import block_partition, column_norms, pack_columns
from repro.kernels import (decode_mean_int2, decode_mean_int4,
                           decode_mean_int8, decode_stacked_ref,
                           quantize_pack_int2, quantize_pack_int2_ref,
                           quantize_pack_int4, quantize_pack_int4_ref,
                           quantize_pack_int8, quantize_pack_int8_ref,
                           scd_steps_kernel, scd_steps_ref, topk_select,
                           topk_select_ref)
from repro.launch.mesh import CHIP_PEAKS, kernel_roofline

# decoded-elements cost factor: unpack ops per element before the
# scale multiply (int8 converts only; int4/int2 mask+shift+bias)
_UNPACK_OPS = {"int8": 1, "int4": 3, "int2": 3}


def _roofline(counters: dict, cell: str, flops: int, nbytes: int,
              t: float, peaks) -> None:
    """Attach the exact work model of one Pallas cell to the counter
    dict, and its achieved roofline fractions when the device has
    published peaks (``peaks`` is None otherwise)."""
    counters[f"model_flops_{cell}"] = int(flops)
    counters[f"model_bytes_{cell}"] = int(nbytes)
    if peaks is not None:
        rl = kernel_roofline(float(flops), float(nbytes), t, peaks)
        counters[f"roofline_flops_frac_{cell}"] = rl["flops_frac_of_peak"]
        counters[f"roofline_bw_frac_{cell}"] = rl["bw_frac_of_hbm"]


@benchmark("kernels", figures="§kernels",
           description="Pallas SCD kernel vs jnp oracle microbench")
def run(ctx: BenchContext) -> dict:
    wl = common.workload(ctx.tier)
    reps = ctx.repeats or max(wl.reps, 2)
    policy = TimingPolicy(warmup=1, reps=reps)
    rng = np.random.default_rng(ctx.seed)
    rows, timings, counters = [], {}, {}
    kind = jax.devices()[0].device_kind
    peaks = CHIP_PEAKS.get(kind)
    # -- tiled SCD: lane-tiled Pallas kernel vs the jnp reference loop,
    # both on the (n, S, 128) column block. The kernel fetches each
    # visited (S, 128) column tile into VMEM itself, so it must hold its
    # own against the oracle even in interpret mode: the smoke tier pins
    # >= 0.9x ref GFLOP/s on the largest-m shape (the one where a
    # (1, m) row layout would waste 7/8 of every f32 sublane tile).
    big_m = max(wl.kernel_shapes, key=lambda s: s[0])
    for (m, n, H) in wl.kernel_shapes:
        packed, _ = pack_columns(
            rng.standard_normal((m, n)).astype(np.float32),
            block_partition(n, 1))
        A = jnp.asarray(packed[0])
        colsq = column_norms(A[None])[0]
        alpha = jnp.zeros(n, jnp.float32)
        w = jnp.asarray(rng.standard_normal(m), jnp.float32)
        idx = jnp.asarray(rng.integers(0, n, H), jnp.int32)
        kw = dict(sigma=8.0, lam=1.0, eta=1.0)
        # the asserted shape gets a deeper min-of-reps so the 0.9x gate
        # measures the kernel, not scheduler jitter on a busy CI host
        pol = (TimingPolicy(warmup=2, reps=max(reps, 5))
               if ctx.tier == "smoke" and (m, n, H) == big_m else policy)
        t_ref = time_callable(scd_steps_ref, A, colsq, alpha, w, idx,
                              policy=pol, **kw)
        t_ker = time_callable(scd_steps_kernel, A, colsq, alpha, w, idx,
                              policy=pol, **kw)
        flops = 4 * m * H  # dot + axpy per step
        # column stream + per-step scalars (csq, dinv, thr, idx) +
        # alpha read/write + w read / rho write
        scd_bytes = 4 * H * m + 16 * H + 8 * n + 8 * m
        for label, t in (("scd_ref", t_ref), ("scd_pallas_interp", t_ker)):
            cell = f"{label}_m{m}_H{H}"
            rows.append({"name": cell,
                         "us_per_call": round(t * 1e6, 1),
                         "derived": f"{flops / t / 1e9:.2f}GFLOP/s"})
            timings[cell] = t
            counters[f"gflops_{cell}"] = round(flops / t / 1e9, 3)
        _roofline(counters, f"scd_pallas_interp_m{m}_H{H}",
                  flops, scd_bytes, t_ker, peaks)
        ratio = t_ref / t_ker
        counters[f"scd_ratio_vs_ref_m{m}_H{H}"] = round(ratio, 3)
        if ctx.tier == "smoke" and (m, n, H) == big_m:
            assert ratio >= 0.9, (
                f"tiled SCD kernel at (m={m}, n={n}, H={H}) runs at "
                f"{ratio:.2f}x the reference GFLOP/s — below the 0.9x "
                f"floor the rework pins")
    # -- fused quantize+pack: oracle (jitted jnp) vs Pallas interpret,
    # with the interpret output asserted bit-identical to the oracle —
    # the same contract the comm codecs rely on for the compressed
    # exchange
    quant = {"quant_int8": (jax.jit(quantize_pack_int8_ref),
                            quantize_pack_int8, 8),
             "quant_int4": (jax.jit(quantize_pack_int4_ref),
                            quantize_pack_int4, 4),
             "quant_int2": (jax.jit(quantize_pack_int2_ref),
                            quantize_pack_int2, 2)}
    for L in wl.quant_lengths:
        dv = jnp.asarray(rng.standard_normal(L), jnp.float32)
        for name, (ref_fn, ker_fn, bits) in quant.items():
            p_ref, s_ref = ref_fn(dv)
            p_ker, s_ker = ker_fn(dv)
            assert (np.array_equal(np.asarray(p_ref), np.asarray(p_ker))
                    and float(s_ref) == float(s_ker)), (
                f"{name} L={L}: Pallas interpret output is not "
                f"bit-identical to the jnp oracle")
            t_ref = time_callable(ref_fn, dv, policy=policy)
            t_ker = time_callable(ker_fn, dv, policy=policy)
            wire = p_ref.size * p_ref.dtype.itemsize + 4
            for label, t in ((f"{name}_ref", t_ref),
                             (f"{name}_pallas_interp", t_ker)):
                rows.append({"name": f"{label}_L{L}",
                             "us_per_call": round(t * 1e6, 1),
                             "derived": f"{4 * L / wire:.2f}x smaller"})
                timings[f"{label}_L{L}"] = t
            counters[f"wire_bytes_{name}_L{L}"] = wire
            # absmax + scale + round/clip per element, then pack:
            # (spe - 1) shift+or per packed byte
            spe = 8 // bits
            q_flops = 6 * L + (spe - 1) * 2 * math.ceil(L / spe)
            _roofline(counters, f"{name}_pallas_interp_L{L}",
                      q_flops, 4 * L + wire, t_ker, peaks)
    # -- fused decode+mean: the gather-side kernels behind
    # decode_stacked_mean, against the sequential jnp oracle in
    # repro.kernels.ref — the contract that closed the f32-intermediate
    # findings. Bit-identity is asserted jitted-vs-jitted at every
    # (K, L) cell.
    dec = {"decode_mean_int8": ("int8", decode_mean_int8),
           "decode_mean_int4": ("int4", decode_mean_int4),
           "decode_mean_int2": ("int2", decode_mean_int2)}
    K = wl.K
    for L in wl.quant_lengths:
        for name, (codec_name, ker_fn) in dec.items():
            codec = get_codec(codec_name)
            parts = [codec.encode(
                jnp.asarray(rng.standard_normal(L), jnp.float32))
                for _ in range(K)]
            payload = jnp.stack([p for p, _ in parts])
            scales = jnp.stack([s for _, s in parts])
            ref_fn = jax.jit(lambda p, s, c=codec_name:
                             decode_stacked_ref(c, (p, s), L))
            out_ref = ref_fn(payload, scales)
            out_ker = ker_fn(payload, scales, L)
            assert np.array_equal(np.asarray(out_ref),
                                  np.asarray(out_ker)), (
                f"{name} K={K} L={L}: fused decode+mean is not "
                f"bit-identical to decode_stacked_ref")
            t_ref = time_callable(ref_fn, payload, scales, policy=policy)
            t_ker = time_callable(ker_fn, payload, scales, L,
                                  policy=policy)
            wire = payload.shape[1] * payload.dtype.itemsize + 4
            for label, t in ((f"{name}_ref", t_ref),
                             (f"{name}_pallas_interp", t_ker)):
                cell = f"{label}_K{K}_L{L}"
                rows.append({"name": cell,
                             "us_per_call": round(t * 1e6, 1),
                             "derived": f"{K * wire} wire bytes in"})
                timings[cell] = t
            # unpack + scale-multiply per decoded element, sequential
            # adds, one 1/K multiply; reads K wire payloads, writes the
            # (L,) f32 mean — never a (K, L) f32 stack
            d_flops = (K * L * _UNPACK_OPS[codec_name] + K * L
                       + (K - 1) * L + L)
            _roofline(counters, f"{name}_pallas_interp_K{K}_L{L}",
                      d_flops, K * wire + 4 * L, t_ker, peaks)
    # -- fused top-k select: k argmax+mask sweeps in VMEM vs the
    # lax.top_k oracle; values, indices and threshold all bit-identical
    topk_ref_fn = jax.jit(topk_select_ref)
    for L in wl.quant_lengths:
        k = get_codec("topk")._k(L)
        dv = jnp.asarray(rng.standard_normal(L), jnp.float32)
        v_ref, i_ref, th_ref = topk_ref_fn(dv)
        v_ker, i_ker, th_ker = topk_select(dv, k)
        assert (np.array_equal(np.asarray(v_ref), np.asarray(v_ker))
                and np.array_equal(np.asarray(i_ref), np.asarray(i_ker))
                and float(th_ref) == float(th_ker)), (
            f"topk L={L} k={k}: Pallas select is not bit-identical to "
            f"the lax.top_k oracle")
        t_ref = time_callable(topk_ref_fn, dv, policy=policy)
        t_ker = time_callable(topk_select, dv, k, policy=policy)
        for label, t in (("topk_ref", t_ref),
                         ("topk_pallas_interp", t_ker)):
            cell = f"{label}_L{L}"
            rows.append({"name": cell,
                         "us_per_call": round(t * 1e6, 1),
                         "derived": f"k={k} of {L}"})
            timings[cell] = t
        # |x| pass + k sweeps of (max, select, mask); ships 2 words
        # per kept entry + the threshold
        _roofline(counters, f"topk_pallas_interp_L{L}",
                  L + 3 * k * L, 4 * L + 8 * k + 4, t_ker, peaks)
    notes = ["pallas numbers are interpret-mode (CPU emulation) — "
             "correctness benchmark, not TPU speed",
             "quantize+pack, decode+mean and top-k interpret outputs "
             "asserted bit-identical to the codec oracles at every cell",
             "model_* counters are the machine-independent work models, "
             "exact-gated in CI",
             (f"roofline_*_frac counters divide achieved rates by the "
              f"{kind} peaks (repro.launch.mesh.CHIP_PEAKS)"
              if peaks is not None else
              f"roofline shares not measured: device kind {kind!r} has "
              f"no published peaks")]
    return {"params": {"shapes": [list(s) for s in wl.kernel_shapes],
                       "quant_lengths": list(wl.quant_lengths),
                       "K": K, "reps": reps},
            "timings_s": timings, "counters": counters,
            "rows": rows, "notes": notes}


def main() -> list[dict]:
    out = run(BenchContext(tier="full"))
    common.emit("kernels", out["rows"])
    for note in out["notes"]:
        print(f"# {note}")
    return out["rows"]


if __name__ == "__main__":
    main()

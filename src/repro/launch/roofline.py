import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Roofline analysis from the compiled dry-run (single-pod mesh).

Methodology (see DESIGN.md §5): `cost_analysis()` counts a `lax.scan`
body ONCE regardless of trip count, so the full-config scan lowering
cannot be read directly. Instead we lower the same model python-UNROLLED
at L1 = prologue + 1 cycle and L2 = prologue + 2 cycles and extrapolate

    cost(L) = cost(L1) + (n_cycles - 1) * (cost(L2) - cost(L1))

which is exact for layer-homogeneous stacks (per-cycle cost is constant;
embed/unembed/loss live in the L1 base term). Collective operand bytes
are parsed from the compiled HLO and extrapolated the same way.

Terms (TPU v5e peaks, ``chip_peaks`` in launch/mesh.py):
    compute    = FLOPs_per_device / 197e12
    memory     = bytes_per_device / 819e9
    collective = collective_bytes_per_device / 50e9  (one ICI link)
MODEL_FLOPS = 6*N*D (train, dense), 6*N_active*D (train, MoE), 2*N*D
(+cache reads in the memory term) for decode.
"""
import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402

import jax           # noqa: E402
import numpy as np   # noqa: E402

from repro.configs import ARCHS, SHAPES, get_config  # noqa: E402
from repro.configs.base import padded_vocab  # noqa: E402
from repro.launch import build  # noqa: E402
from repro.launch.mesh import (V5E, chip_peaks, kernel_roofline,  # noqa: E402
                               make_production_mesh)
from repro.analysis.graph import lift_hlo  # noqa: E402
from repro.models.transformer import _period, layer_plan  # noqa: E402


def _cost_of(built) -> dict:
    compiled = built.lowered.compile()
    ca = compiled.cost_analysis() or {}
    coll = lift_hlo(compiled.as_text())
    mem = compiled.memory_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll.total_operand_bytes),
        "peak_bytes": float(mem.argument_size_in_bytes
                            + mem.temp_size_in_bytes
                            - mem.alias_size_in_bytes),
    }


def _unrolled_cfg(cfg, n_cycles: int):
    k_dense = cfg.moe.first_k_dense if cfg.moe else 0
    period = _period(cfg)
    cfg2 = dataclasses.replace(cfg, num_layers=k_dense + n_cycles * period,
                               mtp_depth=0)
    return cfg2


def _lower_unrolled(cfg, shape, mesh, n_cycles):
    from repro.models.layers import set_force_dense_attention
    c = _unrolled_cfg(cfg, n_cycles)
    set_force_dense_attention(True)   # flash scans are cost-counted once
    try:
        if shape.kind == "train":
            return build.lower_train(c, shape, mesh, unroll=True, remat=True,
                                     donate=False, microbatch=1)
        if shape.kind == "prefill":
            return build.lower_prefill(c, shape, mesh, unroll=True)
        return build.lower_decode(c, shape, mesh, unroll=True, donate=False)
    finally:
        set_force_dense_attention(False)


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS for the full config + shape (global):
    6*N_active*D (train) / 2*N_active*D (inference) for the parametric
    part, plus the analytic attention-score term (which dominates at
    32k+): 4*S_kv*H*Dh per query token per attention layer (halved for
    causal prefill/train, windowed for SWA)."""
    v = padded_vocab(cfg)
    d = cfg.d_model
    n_embed = v * d * (1 if cfg.tie_embeddings else 2)
    plan = layer_plan(cfg)

    # ---- attention-score FLOPs ----
    B, S = shape.global_batch, shape.seq_len
    mult = 3.0 if shape.kind == "train" else 1.0
    attn_fl = 0.0
    for mixer, _ in plan:
        if mixer == "attn":
            hd, kv_len = cfg.num_heads * cfg.head_dim, S
            win = cfg.sliding_window
        elif mixer == "attn_local":
            hd = cfg.num_heads * cfg.head_dim
            win = (cfg.rglru.local_window if cfg.rglru
                   else cfg.sliding_window)
        elif mixer == "mla":
            m = cfg.mla
            hd = cfg.num_heads * (m.qk_nope_dim + m.qk_rope_dim
                                  + m.v_head_dim) / 2.0
            win = None
        else:
            continue
        if shape.kind == "decode":
            kv = min(win, S) if win else S
            attn_fl += mult * B * 1 * 4 * kv * hd
        else:
            kv_eff = (min(win, S) if win else S / 2.0)  # causal half
            attn_fl += mult * B * S * 4 * kv_eff * hd

    n_active = 0
    for mixer, channel in plan:
        if mixer in ("attn", "attn_local"):
            kvd = cfg.num_kv_heads * cfg.head_dim
            n_active += d * cfg.num_heads * cfg.head_dim * 2 + 2 * d * kvd
        elif mixer == "mla":
            m = cfg.mla
            n_active += (d * m.q_lora_rank
                         + m.q_lora_rank * cfg.num_heads
                         * (m.qk_nope_dim + m.qk_rope_dim)
                         + d * (m.kv_lora_rank + m.qk_rope_dim)
                         + m.kv_lora_rank * cfg.num_heads
                         * (m.qk_nope_dim + m.v_head_dim)
                         + cfg.num_heads * m.v_head_dim * d)
        elif mixer == "rglru":
            w = cfg.rglru.lru_width or d
            n_active += 2 * d * w + 2 * w * w + w * d
        elif mixer == "ssd":
            s = cfg.ssm
            din = s.d_inner(d)
            n_active += d * (2 * din + 2 * s.n_groups * s.d_state
                             + s.n_heads(d)) + din * d
        if channel == "mlp":
            n_active += d * cfg.d_ff * (3 if cfg.mlp_gated else 2)
        elif channel == "moe":
            mo = cfg.moe
            n_active += (mo.top_k + mo.num_shared) * d * mo.d_expert * 3
    if cfg.family == "audio":
        n_active *= 1.6  # cross-attention + encoder stack, rough
    n_total = n_active + n_embed
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    par_mult = 6 if shape.kind == "train" else 2
    return par_mult * n_total * tokens + attn_fl


def roofline_pair(arch: str, shape_name: str, *, chips: int = 256) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not build.supported(cfg, shape):
        return {"arch": arch, "shape": shape_name, "status": "skipped"}
    mesh = make_production_mesh(multi_pod=False)
    cfg_v = build.shape_variant(cfg, shape)
    if cfg_v.family == "audio":
        # whisper is python-unrolled already: lower the full config once
        built = build.lower_pair(arch, shape_name, mesh)
        cost = _cost_of(built)
    else:
        k_dense = cfg_v.moe.first_k_dense if cfg_v.moe else 0
        period = _period(cfg_v)
        n_cycles = (cfg_v.num_layers - k_dense) // period
        c1 = _cost_of(_lower_unrolled(cfg_v, shape, mesh, 1))
        c2 = _cost_of(_lower_unrolled(cfg_v, shape, mesh, 2))
        cost = {k: c1[k] + (n_cycles - 1) * (c2[k] - c1[k])
                for k in ("flops", "bytes", "coll_bytes")}
        cost["peak_bytes"] = c1["peak_bytes"]  # L1 peak, indicative only
        if shape.kind == "train":
            # roofline lowers microbatch=1; the production step uses the
            # same total tokens, so per-step cost is identical.
            pass
    peaks = chip_peaks(V5E)      # the production mesh's chip
    t_compute = cost["flops"] / peaks.flops_bf16
    t_memory = cost["bytes"] / peaks.hbm_bw
    t_coll = cost["coll_bytes"] / peaks.ici_link_bw
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg_v, shape)
    hlo_total = cost["flops"] * chips
    rec = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "kind": shape.kind, "chips": chips,
        "hlo_flops_per_dev": cost["flops"],
        "hlo_bytes_per_dev": cost["bytes"],
        "coll_bytes_per_dev": cost["coll_bytes"],
        **{k: round(v, 6) for k, v in terms.items()},
        "dominant": dominant.replace("_s", ""),
        "model_flops_global": mf,
        "useful_flops_ratio": mf / max(hlo_total, 1.0),
        "bound_step_time_s": round(max(terms.values()), 6),
    }
    return rec


def kernel_roofline_summary(bench: dict) -> dict:
    """Per-kernel roofline fractions from a BENCH_kernels.json dict:
    every ``model_flops_<cell>`` counter is paired with its
    ``model_bytes_<cell>`` twin and the cell's measured time. Rates and
    fractions of the chip peaks are reported only when the run's
    ``device_kind`` has published peaks (``repro.launch.mesh``);
    otherwise the summary says that they were not measured."""
    from repro.launch.mesh import CHIP_PEAKS

    kind = bench.get("env", {}).get("device_kind", "unknown")
    peaks = CHIP_PEAKS.get(kind)
    counters = bench.get("counters", {})
    timings = bench.get("timings_s", {})
    cells = {}
    for name, fl in sorted(counters.items()):
        if not name.startswith("model_flops_"):
            continue
        cell = name[len("model_flops_"):]
        nbytes = counters.get(f"model_bytes_{cell}")
        t = timings.get(cell)
        if nbytes is None or not t:
            continue
        cells[cell] = {"time_s": t, "model_flops": float(fl),
                       "model_bytes": float(nbytes)}
        if peaks is not None:
            cells[cell].update(kernel_roofline(float(fl), float(nbytes),
                                               float(t), peaks))
    out = {"device_kind": kind, "cells": cells}
    if peaks is None:
        out["notes"] = [f"roofline shares not measured: device kind "
                        f"{kind!r} has no published peaks"]
    else:
        out["peaks"] = {"flops_bf16_per_s": peaks.flops_bf16,
                        "hbm_bytes_per_s": peaks.hbm_bw}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/roofline")
    ap.add_argument("--kernels", metavar="BENCH_KERNELS_JSON",
                    help="write a per-kernel roofline-fraction summary "
                         "for a BENCH_kernels.json (to --out as a file) "
                         "instead of the transformer dry-run")
    args = ap.parse_args()
    if args.kernels:
        with open(args.kernels) as f:
            bench = json.load(f)
        summary = kernel_roofline_summary(bench)
        out = args.out
        if os.path.isdir(out) or out.endswith(os.sep):
            os.makedirs(out, exist_ok=True)
            out = os.path.join(out, "ROOFLINE_kernels.json")
        else:
            os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
        for note in summary.get("notes", ()):
            print(f"# {note}")
        for cell, rec in summary["cells"].items():
            if "flops_frac_of_peak" not in rec:
                print(f"{cell:44s} {rec['time_s'] * 1e6:10.1f} us")
                continue
            print(f"{cell:44s} {rec['achieved_gflops']:8.3f} GFLOP/s "
                  f"({rec['flops_frac_of_peak']:.2e} of peak)  "
                  f"{rec['achieved_gbps']:8.3f} GB/s "
                  f"({rec['bw_frac_of_hbm']:.2e} of HBM)")
        print(f"# -> {out}")
        return
    pairs = ([(a, s) for a in ARCHS for s in SHAPES] if args.all
             else [(args.arch, args.shape)])
    os.makedirs(args.out, exist_ok=True)
    for arch, shape in pairs:
        try:
            rec = roofline_pair(arch, shape)
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "status": "fail",
                   "error": f"{type(e).__name__}: {e}"}
        with open(os.path.join(args.out, f"{arch}_{shape}.json"), "w") as f:
            json.dump(rec, f, indent=1)
        if rec["status"] == "ok":
            print(f"{arch:28s} {shape:12s} comp {rec['compute_s']:9.4f}s "
                  f"mem {rec['memory_s']:9.4f}s coll {rec['collective_s']:9.4f}s"
                  f" -> {rec['dominant']:10s} useful={rec['useful_flops_ratio']:.2f}")
        else:
            print(f"{arch:28s} {shape:12s} {rec['status']}")


if __name__ == "__main__":
    main()

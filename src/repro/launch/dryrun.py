import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x shape x mesh) combination
lowers AND compiles on the production meshes, and record memory / cost /
collective-traffic analysis for §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]
"""
import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402

from repro.analysis.graph import lift_hlo  # noqa: E402
from repro.configs import ARCHS, SHAPES, get_config  # noqa: E402
from repro.launch import build  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402


def run_pair(arch: str, shape: str, *, multi_pod: bool, verbose: bool = True,
             **kw) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    built = build.lower_pair(arch, shape, mesh, **kw)
    if built is None:
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "status": "skipped",
                "reason": "long_context=skip (see DESIGN.md §6)"}
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = built.lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    coll = lift_hlo(compiled.as_text())
    n_dev = mesh.devices.size
    rec = {
        "arch": arch, "shape": shape, "multi_pod": multi_pod,
        "kind": built.kind, "status": "ok", "notes": built.notes,
        "devices": n_dev,
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "per_device_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "aliased": mem.alias_size_in_bytes,
            "temps": mem.temp_size_in_bytes,
            "peak_live": (mem.argument_size_in_bytes
                          + mem.temp_size_in_bytes
                          - mem.alias_size_in_bytes),
        },
        "hlo_flops": cost.get("flops", 0.0),
        "hlo_bytes": cost.get("bytes accessed", 0.0),
        "collectives": {k: {"count": v[0], "operand_bytes": v[1],
                            "result_bytes": v[2]}
                        for k, v in coll.by_kind().items()},
        "collective_operand_bytes": coll.total_operand_bytes,
    }
    if verbose:
        gb = 1 << 30
        print(f"[{arch} x {shape} | {'2x16x16' if multi_pod else '16x16'} "
              f"| {built.kind}] lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  per-device: args {mem.argument_size_in_bytes/gb:.2f} GiB, "
              f"temps {mem.temp_size_in_bytes/gb:.2f} GiB, "
              f"aliased {mem.alias_size_in_bytes/gb:.2f} GiB")
        print(f"  HLO flops/device {cost.get('flops', 0):.3e}  "
              f"bytes/device {cost.get('bytes accessed', 0):.3e}")
        print("  " + coll.summary().replace("\n", "\n  "))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    pairs = ([(a, s) for a in ARCHS for s in SHAPES] if args.all
             else [(args.arch, args.shape)])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in pairs:
        for mp in meshes:
            tag = f"{arch}_{shape}_{'mp' if mp else 'sp'}"
            try:
                rec = run_pair(arch, shape, multi_pod=mp)
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                       "status": "fail", "error": f"{type(e).__name__}: {e}"}
                failures.append(tag)
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(rec, f, indent=1)
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("all dry-runs OK")


if __name__ == "__main__":
    main()

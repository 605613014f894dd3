"""The control: the reference solve put in the program's place.

    python3 chipbench/control.py --workload <name> --seeds <n> [<n> ...] [--dtype bfloat16]

For each seed this makes the cell's problem, solves it with the plain
reference CoCoA (``reference.cocoa_solve``: the cell's K and H, the
mix's eps and max_rounds) at ``--dtype`` in place of the program, and
holds its answer to the same comparison as a benchmark run
(``check.compare``, with the configuration's limits). At bfloat16, the
precision below the float32 the configurations state, the comparison
has to refuse it; at float32 the reference is a second witness beside
the program and has to pass. One line of JSON per seed on standard
output. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def control(workload: str, seed: int, dtype: str, root: Path = ROOT) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from chipbench import check, data, reference, spec

    cell = spec.resolve(workload, root)
    cfg, mix = cell.config, cell.mix
    K, lam = cfg["trainer"]["K"], float(cfg["trainer"]["lam"])
    n_local = -(-cfg["features"] // K)
    H = n_local if mix["H"] == "n_local" else int(mix["H"])
    _, coord_word, sample_word = data.seed_words(seed, 3)
    A_dev, b_dev = data.make_problem(cfg, seed)
    A, b = np.asarray(A_dev), np.asarray(b_dev)
    p_star, _ = reference.p_star(A_dev, A, b, lam)
    p_zero = 0.5 * float(np.dot(b.astype(np.float64), b))
    alpha, primal, rounds, reached = reference.cocoa_solve(
        A_dev, b, K=K, H=H, lam=lam, eps=float(mix["eps"]), p_star=p_star,
        p_zero=p_zero, max_rounds=int(mix["max_rounds"]),
        seed=coord_word % 2**31, dtype=jnp.dtype(dtype))
    # the answer is compared whether or not it certified; a solve that
    # never certified is also counted as failed
    numbers = check.compare(
        [(alpha, primal)], 0 if reached else 1, A=A, b=b, lam=lam,
        p_star=p_star, p_zero=p_zero, eps=float(mix["eps"]), gap_limit=float(cfg["limits"]["primal_gap"]),
        seed_word=sample_word)
    return {"workload": workload, "seed": seed, "dtype": dtype,
            "rounds": rounds, "certified": reached,
            "correct": check.passed(numbers),
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in numbers.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    args = ap.parse_args(argv)
    for seed in args.seeds:
        print(json.dumps(control(args.workload, seed, args.dtype)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

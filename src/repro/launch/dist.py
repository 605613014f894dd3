"""Real multi-process execution of the sharded driver layer.

The whole repo so far runs one process (vmap virtual workers or
shard_map over local devices). This entry stands up the paper's actual
deployment shape — N communicating processes — via
``jax.distributed.initialize``, and runs the UNCHANGED sharded driver
(``repro.core.distributed.build_sharded_round``) across them: same
algorithms, same :class:`~repro.core.distributed.ExchangeConfig`
surface (including the collective-backend segment), same compiled
round. That makes two things real that were previously simulated:

  * ``calibrate_link`` (``--calibrate``) times the exchange's actual
    collective over a real inter-process transport instead of
    device-to-device copies inside one process, and
  * the paper's framework-gap experiment (same algorithm, different
    fabric) becomes rerunnable: ``--exchange persistent`` vs
    ``--exchange persistent/ring`` on real processes.

Every process runs this same script with the same arguments except
``--process-id``::

    # terminal 1                                      # terminal 2
    python -m repro.launch.dist \\                    ... same ... \\
        --coordinator 127.0.0.1:9876 \\
        --num-processes 2 --process-id 0 \\           --process-id 1 \\
        --algorithm cocoa --exchange persistent \\
        --rounds 5 --out /tmp/r0.json                 --out /tmp/r1.json

With ``--num-processes 1`` (the default) no coordinator is needed and
the run degrades to single-process shard_map over the visible devices
(fake extra CPU devices with ``XLA_FLAGS=--xla_force_host_platform_
device_count=K`` to match a K-process run) — the reference the CI
smoke test pins the 2-process trajectory bit-identical against.

The problem is rebuilt deterministically from ``--seed`` on every
process, so the only cross-process traffic is the driver's own
exchange. Worker count K = the GLOBAL device count (one device per
process on plain CPU hosts). The result JSON records the per-round
primal objectives plus SHA-256 hashes of the final shared/local state,
which is how runs are compared bit-for-bit.
"""
from __future__ import annotations

import argparse
import hashlib
import json


def _global_put(x, mesh, spec):
    """Place a host array on the (possibly multi-process) mesh: every
    process holds the full value, each materializes only its shards.
    (``device_put`` onto cross-process shardings is version-fragile;
    ``make_array_from_callback`` is the portable spelling.)"""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    x = np.asarray(x)
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(x.shape, sharding,
                                        lambda idx: x[idx])


def _replicate(x, mesh):
    """Re-replicate a sharded global array so every process can read
    (and hash) the full value — an all-gather via output sharding."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.jit(lambda a: a,
                   out_shardings=NamedSharding(mesh, P(None)))(x)


def _sha256(x) -> str:
    import numpy as np

    a = np.ascontiguousarray(np.asarray(x))
    return hashlib.sha256(a.tobytes()).hexdigest()


def build_trainer(args):
    from repro.core.baselines import SGDConfig
    from repro.core.cocoa import CoCoAConfig
    from repro.core.tradeoff import make_trainer
    from repro.data import make_glm_data

    A, b, _ = make_glm_data(m=args.m, n=args.n, density=args.density,
                            zipf_a=1.1, seed=args.seed)
    if args.algorithm == "minibatch_sgd":
        cfg = SGDConfig(K=args.workers, H=args.H, lam=args.lam,
                        step_size=0.1, exchange=args.exchange, seed=0)
    else:
        cfg = CoCoAConfig(K=args.workers, H=args.H, lam=args.lam,
                          solver=args.solver, exchange=args.exchange,
                          seed=0)
    return make_trainer(args.algorithm, cfg, A, b)


def run(args) -> dict:
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import distributed as dist
    from repro.utils import compat

    K = len(jax.devices())
    args.workers = K
    tr = build_trainer(args)
    mesh = compat.make_mesh((K,), ("workers",))
    # the trainer's OWN algorithm object on the generic sharded driver —
    # only the data placement differs from run_sharded: leaves are
    # placed as global arrays so the shards live where the processes are
    data = jax.tree_util.tree_map(
        lambda x: _global_put(x, mesh, P("workers")), tr._data)
    round_fn = dist.build_sharded_round(tr._algo, tr.exchange, data, mesh)
    local, shared = tr.init_state()
    # local may be the (local, codec_state) pair of a stateful codec —
    # every leaf is worker-partitioned, so tree_map the placement
    local = jax.tree_util.tree_map(
        lambda x: _global_put(x, mesh, P("workers")), local)
    shared = jax.tree_util.tree_map(
        lambda x: _global_put(x, mesh, P(None)), shared)

    key = jax.random.key(tr.cfg.seed)
    primals = []
    last_t = 0
    for t in range(args.rounds):
        last_t = t + 1
        key, sub = jax.random.split(key)
        keys = _global_put(round_fn.split_keys(sub), mesh, P("workers"))
        # drive the jitted inner with the keys placed as global arrays
        local, shared, primal = round_fn.jitted(data, keys, local, shared,
                                                t + 1)
        primals.append(float(primal))   # replicated -> readable anywhere
    shared = dist.finish_run(round_fn, shared, last_t)
    local = dist.unwrap_local_state(tr.exchange, local)

    result = {
        "workers": K,
        "num_processes": args.num_processes,
        "algorithm": args.algorithm,
        "exchange": tr.exchange.spec,
        "rounds": args.rounds,
        "primals": primals,
        "final_shared_sha256": _sha256(_replicate(shared, mesh)),
        "final_local_sha256": _sha256(_replicate(local, mesh)),
    }
    if args.calibrate:
        from repro.bench.timing import TimingPolicy, calibrate_link

        link = calibrate_link(tr.exchange, mesh=mesh,
                              policy=TimingPolicy(warmup=1, reps=3))
        result["link"] = {"bandwidth_Bps": link.bandwidth_Bps,
                          "latency_s": link.latency_s,
                          "source": link.source}
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="run the sharded driver across real processes")
    ap.add_argument("--coordinator", default="127.0.0.1:9876",
                    help="coordinator host:port (process 0 binds it)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--algorithm", default="cocoa",
                    choices=("cocoa", "minibatch_scd", "minibatch_sgd"))
    ap.add_argument("--exchange", default="persistent", metavar="SPEC",
                    help="full exchange spec incl. backend segment "
                         "(e.g. 'compressed:int4/ring')")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--H", type=int, default=16)
    ap.add_argument("--solver", default="scd_ref")
    ap.add_argument("--m", type=int, default=96)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--density", type=float, default=0.2)
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--calibrate", action="store_true",
                    help="also calibrate_link over the real transport")
    ap.add_argument("--out", default=None,
                    help="write the result JSON here (every process "
                         "writes — compare them bit-for-bit)")
    args = ap.parse_args(argv)

    import jax

    from repro.utils.cache import enable_compilation_cache

    enable_compilation_cache()

    if args.num_processes > 1:
        # the gloo CPU collectives client must be selected before
        # initialize(); it is what backs cross-process CPU collectives
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=args.coordinator,
                                   num_processes=args.num_processes,
                                   process_id=args.process_id)

    result = run(args)
    line = json.dumps(result, indent=2, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)


if __name__ == "__main__":
    main()

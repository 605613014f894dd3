"""Training launcher: real training on the available devices, or
--dry-run for the production-mesh lowering.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --steps 200 --batch 8 --seq 256 [--local-H 4] [--codec int8]

On this CPU container use --reduced; on a real TPU slice the full config
shards according to launch/sharding.py. --local-H enables the paper's
communication-avoiding local-update rounds (H optimizer steps per
parameter sync) with the roofline-driven default when set to 0.

--exchange takes a full driver-layer exchange spec (e.g.
``compressed:int4`` or ``compressed:int8/straggler:det(slow=4)``) and
uses its wire codec for the delta exchange; --codec remains as the
deprecated single-knob spelling (f32 exact pmean, int8/int4 the
compressed exchange — active when the round runs over a data-parallel
mesh axis).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.data.tokens import TokenStream
from repro.models import build_model
from repro.optim import AdamWConfig, adamw_init
from repro.optim.local_updates import LocalUpdatesConfig, local_updates_round, suggest_H
from repro.train import make_train_step
from repro.checkpoint import save_checkpoint
from repro.utils.cache import enable_compilation_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--local-H", type=int, default=None,
                    help="local steps per sync (paper's knob); 0=auto")
    ap.add_argument("--exchange", default=None, metavar="SPEC",
                    help="driver-layer exchange spec (e.g. "
                         "'compressed:int8' or 'compressed:int4/ring'); "
                         "its wire codec drives the delta exchange (the "
                         "backend segment matters on the sharded "
                         "driver / launch.dist)")
    ap.add_argument("--codec",
                    choices=("f32", "int8", "int4", "int2", "topk",
                             "ef:int8", "ef:int4", "ef:int2", "ef:topk"),
                    default=None,
                    help="DEPRECATED: wire codec alone — use "
                         "--exchange compressed:<codec>")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compilation_cache()

    # fold the deprecated --codec spelling into the --exchange spec
    from repro.core.distributed import ExchangeConfig
    from repro.utils.deprecation import warn_deprecated

    if args.exchange is not None:
        ex = ExchangeConfig.parse(args.exchange)
        codec = ex.scheme.codec.name
        if args.codec is not None and args.codec != codec:
            raise SystemExit(f"--codec {args.codec} conflicts with "
                             f"--exchange {args.exchange!r} (codec "
                             f"{codec}); drop the deprecated --codec")
    else:
        if args.codec is not None:
            warn_deprecated("--codec is deprecated; use "
                            "--exchange compressed:<codec>")
        codec = args.codec or "f32"
    args.codec = codec

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    opt_cfg = AdamWConfig(lr=args.lr)
    opt = adamw_init(params, opt_cfg)
    ts = TokenStream(cfg.vocab_size, args.seq, args.batch, seed=0)

    H = args.local_H
    if H == 0:
        H = suggest_H(t_compute_per_step=1.0, t_collective_per_sync=0.5)
        print(f"auto-selected local H = {H}")
    if H and H > 1:
        step_local = make_train_step(model, opt_cfg)
        lu_cfg = LocalUpdatesConfig(H=H, codec=args.codec)
        if args.codec != "f32":
            from repro.optim import delta_wire_bytes
            K = max(len(jax.devices()), 1)
            print(f"delta exchange codec={args.codec}: "
                  f"~{delta_wire_bytes(params, lu_cfg, K) / 1e6:.2f} MB "
                  f"modelled per sync across {K} shard(s) "
                  f"(vs {delta_wire_bytes(params, LocalUpdatesConfig(H=H), K) / 1e6:.2f} MB f32)")

        @jax.jit
        def round_fn(params, opt, batches):
            return local_updates_round(step_local, params, opt, batches,
                                       lu_cfg, None)

        n_rounds = args.steps // H
        t0 = time.time()
        for r in range(n_rounds):
            bs = [ts.next_batch() for _ in range(H)]
            batches = {k: jnp.stack([jnp.asarray(b[k]) for b in bs])
                       for k in bs[0]}
            params, opt, ms = round_fn(params, opt, batches)
            print(f"round {r} (H={H}) loss={float(ms['loss'][-1]):.4f} "
                  f"({time.time() - t0:.1f}s)")
    else:
        step = jax.jit(make_train_step(model, opt_cfg))
        t0 = time.time()
        for i in range(args.steps):
            batch = {k: jnp.asarray(v) for k, v in ts.next_batch().items()}
            params, opt, m = step(params, opt, batch)
            if i % args.log_every == 0 or i == args.steps - 1:
                print(f"step {i:5d} loss={float(m['loss']):.4f} "
                      f"acc={float(m['accuracy']):.3f} "
                      f"gnorm={float(m['grad_norm']):.2f} "
                      f"({time.time() - t0:.1f}s)")
    if args.ckpt:
        save_checkpoint(args.ckpt, {"params": params, "opt": opt},
                        step=args.steps)
        print("saved", args.ckpt)


if __name__ == "__main__":
    main()

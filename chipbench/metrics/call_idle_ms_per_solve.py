"""Device-0 idle time per call of the trainer's entry, in
milliseconds: inside ``repro.setup`` (state, and for ``run_sharded``
the mesh, the data's placement and the new program), the solve's first
``repro.dispatch`` (where a new program is traced, lowered and loaded)
and ``repro.finish`` (the final copies to the host), over the
``chipbench.solve`` spans in the window."""
from chipbench import spans


def read(ctx):
    ms = spans.idle_ms(ctx, "call")
    n = spans.solves(ctx)
    return None if ms is None or not n else ms / n

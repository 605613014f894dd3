"""Device-0 idle time inside the trainer's ``repro.readback`` spans
(the round metric's read-back and the stop test), per round, in
milliseconds: the per-round sync that chunking rounds on the device
would remove."""
from chipbench import spans


def read(ctx):
    ms = spans.idle_ms(ctx, "readback")
    return None if ms is None else ms / spans.rounds(ctx)

"""Rounds the traced solves executed (their histories' last round)
over the traced window."""


def read(ctx):
    if not ctx.rounds or ctx.window_s <= 0:
        return None
    return ctx.rounds / ctx.window_s

"""Ops the window completed over the window's seconds (host clock,
client side): the ops of every request the window sent and had
acknowledged, over the time from its opening until the last of them was
answered (the window sends nothing after its `--seconds`, then waits for
what it sent)."""


def read(ctx):
    return ctx['client']['ops_done'] / ctx['client']['window_s']

"""Training tokens per second per chip: every token the window's steps
consumed (steps x W x per-replica batch x sequence), over the window's wall
time from the host clock, over the cell's chips."""


def read(ctx):
    return ctx["tokens_per_s"]

"""Set-up seconds: from process start to the first step of the window —
imports, weights made from the seed, packing, placement, compiles or cache
loads, and the warm-up steps.  The host copies the correctness check keeps
of the first steps are left out."""


def read(ctx):
    return ctx["setup_s"]

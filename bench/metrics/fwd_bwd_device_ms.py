"""Device milliseconds per step of the forward and backward pass: the leaf
operations under the train step's named scope ``step.fwd_bwd`` (backward
operations carry it inside ``transpose(jvp(...))``); see
bench/metrics/_scopes.py."""
from bench.metrics._scopes import device_ms


def read(ctx):
    return device_ms(ctx, "step.fwd_bwd")

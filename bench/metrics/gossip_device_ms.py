"""Device milliseconds per step of the gossip round: the leaf operations
under the train step's named scope ``step.gossip`` (in the pipelined
engine the payload's slice, quantization and launch, both kernel passes
and the FIFO push); see bench/metrics/_scopes.py."""
from bench.metrics._scopes import device_ms


def read(ctx):
    return device_ms(ctx, "step.gossip")

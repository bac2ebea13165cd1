"""HBM roofline share of the gossip kernel's pass 1 (the gate's reduction
terms), in %; see bench/metrics/_blend.py."""
from bench.metrics._blend import share


def read(ctx):
    return share(ctx, "gossip_reduce_w_resident_pallas", "reduce")

"""HBM roofline share of the gossip kernel's pass 2 (the gated mean and
the eq.-1 step), in %; see bench/metrics/_blend.py."""
from bench.metrics._blend import share


def read(ctx):
    return share(ctx, "gossip_apply_w_resident_pallas", "apply")

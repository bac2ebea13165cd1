"""Roofline share of one pass of the resident gossip-blend kernel, in %.

The pass is found in the device trace by the name the program gives it:
its jitted wrapper, whose name the compiled kernel call carries.  The least
time of a call is the HBM bytes the round's mathematics needs on that chip
(bench.flops.blend_bytes) over the peak HBM bandwidth; both passes do a few
operations per byte, so HBM bounds them.  The share is the least time of
all calls over their summed device time, averaged over the chips."""
from bench import trace as tr
from bench.flops import blend_bytes


def share(ctx, prefix: str, which: str):
    t = ctx.get("trace")
    if t is None:
        return None
    st = ctx["settings"]
    local = ctx["workers"] // ctx["chips"]
    need = blend_bytes(ctx["n_params"], st.partial_blocks, local,
                       st.tile)[which]
    shares = []
    for ops in t.devices:
        calls, ns = tr.time_by_name(ops, prefix)
        if calls:
            least = calls * need / ctx["peaks"]["hbm_bytes_per_s"]
            shares.append(least / (ns / 1e9))
    return 100.0 * sum(shares) / len(shares) if shares else None

"""Model FLOP/s utilization of the whole train step, in %: the forward and
backward FLOPs a token needs (bench.flops, recomputation not counted) times
the traced window's tokens per second per chip, by the host's clock over
the whole window, over the chip's bf16 peak."""
from bench.flops import flops_per_token


def read(ctx):
    if ctx.get("trace") is None:
        return None
    f = flops_per_token(ctx["cell"]["cfg"], ctx["seq"])
    return 100.0 * f * ctx["tokens_per_s"] / ctx["peaks"]["bf16_flops_per_s"]

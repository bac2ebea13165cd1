"""Operations and bytes that the work needs, counted from the shapes.

Model FLOPs follow the usual convention: a matmul of an (m, k) by a (k, n)
operand is 2mkn; the backward pass costs twice the forward; recomputation
is not counted.  Kernel bytes are what the gossip round's mathematics must
move through HBM, not what today's kernel streams.
"""
from __future__ import annotations

from bench.reference import model_of


def matmul_params(cfg) -> int:
    """Weights that enter a matmul once per token, by the family's
    reference module."""
    return model_of(cfg).matmul_params(cfg)


def flops_per_token(cfg, seq: int) -> int:
    """Forward plus backward FLOPs per trained token, by the family's
    reference module."""
    return model_of(cfg).flops_per_token(cfg, seq)


def blend_bytes(n: int, p: int, workers: int, tile: int,
                wire_bytes: int = 1) -> dict:
    """HBM bytes one gossip round needs on a chip holding ``workers``
    replicas of ``n`` parameters, with a partition of n / p of them.

    reduce (pass 1): read the partition of w, dw (f32) and the external
    (``wire_bytes`` a value, plus one f32 scale per ``tile``);
    apply (pass 2): read all of w and dw, write all of w_next, and read the
    partition of the external again."""
    part = n / p
    ext = part * wire_bytes + 4 * part / tile
    return {"reduce": workers * (8 * part + ext),
            "apply": workers * (12 * n + ext)}

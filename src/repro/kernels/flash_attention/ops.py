"""Public wrapper for the causal flash-attention kernels: the model's
(B, S, H, Dh) layout, bf16 operands and block sizes by shape."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import resolve_interpret

from .kernel import BlockSizes, causal_flash_pallas


def _fit(block: int, seq: int) -> int:
    """The largest power-of-two divisor of ``seq`` up to ``block`` (a
    power of two)."""
    b = block
    while seq % b:
        b //= 2
    return b


def block_sizes(seq: int, head_dim: int) -> BlockSizes:
    """Tiles of the forward and both backward kernels for one shape.

    Chosen by timing each kernel on its own over tiles of 128-2048 (177
    tilings, all of which compile), at smollm-135m's shape on a TPU v5e (S 2048, Dh 64, 4 x 9
    heads): the forward 0.646 ms at these tiles against 2.81 at the
    library's 128s, dK/dV 1.19 against 3.96, dQ 1.10 against 3.19.  Other
    shapes take the same tiles, cut to divide ``seq``; ``head_dim`` was not
    varied."""
    del head_dim

    def b(block):
        return _fit(block, seq)
    return BlockSizes(block_q=b(512), block_k_major=b(512), block_k=b(512),
                      block_b=1,
                      block_q_major_dkv=b(1024), block_q_dkv=b(128),
                      block_k_major_dkv=b(1024), block_k_dkv=b(256),
                      block_q_dq=b(1024), block_k_major_dq=b(512),
                      block_k_dq=b(512))


def supports(seq: int, head_dim: int) -> bool:
    """Whether the kernels take this shape: the sequence splits into
    128-row tiles and a head fits the kernels' lane tiling."""
    return seq % 128 == 0 and (head_dim <= 128 or head_dim % 128 == 0)


def flash_attention(q, k, v, *, interpret=None):
    """Causal softmax(q k^T / sqrt(Dh)) v over the whole sequence.

    q, k, v: (B, S, H, Dh), k and v already expanded to H heads (GQA).
    Returns (B, S, H, Dh) in bf16.  Position i attends to positions <= i,
    by index.
    """
    _, S, _, Dh = q.shape
    if not supports(S, Dh):
        raise ValueError(f"flash_attention: no blocks for S={S}, Dh={Dh}")

    def heads_first(t):
        return t.astype(jnp.bfloat16).transpose(0, 2, 1, 3)

    def attend(q, k, v):
        out = causal_flash_pallas(heads_first(q), heads_first(k),
                                  heads_first(v), Dh ** -0.5,
                                  block_sizes(S, Dh),
                                  resolve_interpret(interpret))
        return out.transpose(0, 2, 1, 3)

    mesh = jax.sharding.get_abstract_mesh()
    auto = () if mesh.empty else tuple(
        a for a in mesh.axis_names if a not in mesh.manual_axes)
    if any(mesh.shape[a] > 1 for a in auto):
        # GSPMD cannot partition a Mosaic kernel: run it whole on each
        # device; a vmap with ``spmd_axis_name`` (the train step's worker
        # axis) still splits its mapped axis over the mesh
        attend = jax.shard_map(attend, mesh=mesh, in_specs=P(),
                               out_specs=P(), axis_names=set(auto),
                               check_vma=False)
    return attend(q, k, v)

"""Pallas TPU kernels: causal flash attention, forward and backward.

The kernels are JAX's own (``jax.experimental.pallas.ops.tpu.
flash_attention``).  The forward kernel keeps the online softmax's running
max, sum and accumulator in VMEM over a (batch, head, Q block, KV block)
grid and skips the KV blocks above the diagonal; it writes the output and
the row max and sum.  Two backward kernels recompute the probabilities
from those rows, tile by tile: ``flash_mha_bwd_dkv`` (dK, dV; the KV block
outer, Q blocks inner) and ``flash_mha_bwd_dq``.  No (S, S) score or
probability tile leaves VMEM.

Operands are bf16 and every matmul accumulates in f32; the softmax state
is f32, and the probabilities enter the PV matmul as bf16.

The library's entry point takes no ``interpret`` argument: its
``pallas_call``s read one from ``force_tpu_interpret_mode`` while they are
traced.  Given ``True`` (not TPU interpret-mode parameters, whose ordered
callbacks ``jax.checkpoint`` refuses), that is the plain Pallas
interpreter, as ``pallas_call(interpret=True)`` runs the repo's other
kernels.  The forward and the backward are traced at different times (the
backward when the gradient is transposed), so the custom VJP here enters
the mode in each of its rules.
"""
from __future__ import annotations

import contextlib
import functools

import jax
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu import flash_attention as fa

BlockSizes = fa.BlockSizes


@contextlib.contextmanager
def _forward(interpret: bool):
    # the scope names the forward kernel's instruction, as the library's
    # scopes name the backward ones
    with jax.named_scope("flash_mha_fwd"), _mode(interpret):
        yield


def _mode(interpret: bool):
    return (pltpu.force_tpu_interpret_mode(True) if interpret
            else contextlib.nullcontext())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def causal_flash_pallas(q, k, v, sm_scale: float, block_sizes: BlockSizes,
                        interpret: bool):
    """q, k, v: (B, H, S, Dh) -> (B, H, S, Dh), causal, in q's dtype."""
    with _forward(interpret):
        return fa._flash_attention(q, k, v, None, None, False, True,
                                   sm_scale, block_sizes, False)


def _fwd(q, k, v, sm_scale, block_sizes, interpret):
    with _forward(interpret):
        return fa._flash_attention_fwd(q, k, v, None, None, False, True,
                                       sm_scale, block_sizes, False)


def _bwd(sm_scale, block_sizes, interpret, residuals, do):
    with _mode(interpret):
        dq, dk, dv, _, _ = fa._flash_attention_bwd(
            False, True, sm_scale, block_sizes, False, residuals, do)
    return dq, dk, dv


causal_flash_pallas.defvjp(_fwd, _bwd)

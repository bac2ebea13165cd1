"""Pallas TPU kernel: batched fused ASGD gossip blend (paper eqs. 4-6).

Generalizes repro/kernels/parzen_blend from one external (P=1) to a stacked
``(P, R, LANE)`` array of P received states — the real shape of a gossip
round with N receive buffers.  HBM traffic per round, in full-state sweeps:

  naive (core.asgd.blend_externals, a Python loop over externals):
    per external ~4 state-sized traversals — empty_state_mask reads ext,
    parzen_gate re-materializes ``w - eps*dw`` and takes two tree_sq_dist
    passes, the accumulation re-reads acc and ext — so ≈ 4P sweeps total
    (≈ 11P counting every read+write), growing linearly in P.

  fused (this kernel): exactly TWO passes over the stacked externals,
    independent of P:
      pass 1 (gossip_reduce): one sweep accumulating all 3P reduction
        terms at once — per external p the gate inner products
        <dw, w-ext_p> and ||ext_p||^2, plus the shared ||dw||^2 — using
        the expanded eq.-(4) identity from core/parzen.py:
          d_before - d_after = 2*eps*<dw, w-ext> - eps^2*||dw||^2
      pass 2 (gossip_apply): the gated mean of eq. (6) applied
        elementwise with the P admission gates as scalars:
          w <- w - eps*((w - (sum_p g_p ext_p + w)/(sum_p g_p + 1)) + dw)
    Total bytes: (P+2) + (P+3) state-sizes vs ~11P+5 for the loop — the
    per-external cost approaches 2 sweeps, benchmarked in
    benchmarks/spmd_step.py:kernel_vs_ref.

Grid: 1-D over row blocks of the state viewed as (R, LANE) with LANE=512
f32 lanes; the P axis lives entirely inside each block (states are blended
P-at-a-time, P is small — the paper's N receive buffers, typically <= 8).
Pass 1 accumulates lane-dense partial sums: one ``(s, LANE)`` f32 tile per
reduction term (s = gcd(block_rows, 8) sublanes), added elementwise every
grid step, so the kernel never reduces across lanes and never stores a
1-D vector (Mosaic rejects both layouts); the jitted wrapper sums each
tile to its scalar.  Per-worker scalars of pass 2 (gates, 1/denominator,
lr, int8 scales) live in SMEM.

Worker-batched variants (``*_w_pallas``, DESIGN.md §6): the SPMD gossip path
(core/gossip.py) holds W_local worker replicas per shard, each with its own
P externals and its own gates.  The worker axis is a SECOND (leading) Pallas
grid dimension over ``(W, R, LANE)`` states and ``(W, P, R, LANE)``
externals — one kernel launch evaluates all W*P gates and all W gated means,
still in two HBM passes.  An optional ``(R, LANE)`` group mask (shared
across workers — the partial-update partition is drawn once per round)
restricts every gate reduction term and the attraction to the exchanged
partition, which is what 'leaves'-mode partial updates require (paper §4.4).

Packed-resident variants (``*_w_resident_pallas``): on the group-contiguous
layout (core/packing.py ``pack_spec_w(..., groups=)``) the exchanged
partition is a contiguous row range, so the mask degenerates to a
``row_start <= row < row_end`` comparison.  The ``(2,)`` int32 row range
enters through scalar prefetch (``pltpu.PrefetchScalarGridSpec``) and the
mask is an in-register iota compare — the ``(R, LANE)`` mask array and its
HBM read per pass disappear: pass 1 reads exactly w+dw+ext, pass 2 reads
the same and writes w_next (EXPERIMENTS.md §Perf byte table).

int8 wire payloads (``GossipConfig.wire_format="int8"``, DESIGN.md §6):
the resident variants optionally take the external as int8 plus
per-``block_rows`` f32 scales (``ext_scales``, one scalar per external per
grid block — the quantization tile equals the kernel row block by
construction).  Dequantization (``q.astype(f32) * scale``) is fused into
BOTH passes in-register, so the received block never materializes in
float in HBM and the ext read costs 1/4 of the f32 bytes.

Fused eq.-1 update (DESIGN.md §7): the resident apply pass takes the
eq.-1 step size ``lr`` as a RUNTIME f32 operand (one scalar for the whole
grid) and applies the local update ``w - lr*dw`` in-register in the same
sweep as the gated mean — the SGD update is never a separate full-state
traversal, and a traced lr schedule never forces a kernel recompile.  The
Parzen threshold keeps its own ``eps`` (evaluated on the tiny (W, P, 3)
accumulator in the wrapper, outside the kernel).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import LANE, resolve_interpret


def _acc_sublanes(block_rows: int) -> int:
    """Sublanes of one pass-1 partial-sum tile: the full 8-row f32 tile
    when block_rows allows, else the largest divisor of 8 that fits."""
    return math.gcd(block_rows, 8)


def _fold_rows(x):
    """(br, LANE) -> (s, LANE): sum row groups elementwise (VPU adds only)."""
    br = x.shape[0]
    s = _acc_sublanes(br)
    return jnp.sum(x.reshape(br // s, s, LANE), axis=0)


def _accumulate_terms(acc_ref, w, dw, exts):
    """Add one row block's reduction terms to the ``(1, 2P+1, s, LANE)``
    partial-sum block: rows [0, P) <dw, w - ext_p>, rows [P, 2P)
    ||ext_p||^2, row 2P ||dw||^2.  ``exts`` is a sequence of P (br, LANE)
    externals."""
    p = len(exts)
    for k, ext in enumerate(exts):
        acc_ref[0, k] += _fold_rows(dw * (w - ext))
        acc_ref[0, p + k] += _fold_rows(ext * ext)
    acc_ref[0, 2 * p] += _fold_rows(dw * dw)


def _partials_shape(lead: tuple, p: int, block_rows: int) -> tuple:
    return lead + (2 * p + 1, _acc_sublanes(block_rows), LANE)


def _terms_from_partials(partials):
    """(..., 2P+1, s, LANE) partial sums -> (..., P, 3) accumulator
    [<dw, w - ext_p>, ||ext_p||^2, ||dw||^2] (||dw||^2 repeated per p)."""
    sums = jnp.sum(partials, axis=(-2, -1))
    p = (sums.shape[-1] - 1) // 2
    dot, sq_ext = sums[..., :p], sums[..., p:2 * p]
    sq_dw = jnp.broadcast_to(sums[..., 2 * p:], dot.shape)
    return jnp.stack([dot, sq_ext, sq_dw], axis=-1)


def _reduce_kernel(w_ref, dw_ref, ext_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32)          # (br, LANE)
    dw = dw_ref[...].astype(jnp.float32)        # (br, LANE)
    ext = ext_ref[...].astype(jnp.float32)      # (P, br, LANE)
    _accumulate_terms(acc_ref, w, dw, [ext[k] for k in range(ext.shape[0])])


def _apply_kernel(w_ref, dw_ref, ext_ref, gates_ref, inv_denom_ref, out_ref,
                  *, eps, elastic, elastic_alpha):
    w = w_ref[...].astype(jnp.float32)
    dw = dw_ref[...].astype(jnp.float32)
    ext = ext_ref[...].astype(jnp.float32)      # (P, br, LANE)
    g = gates_ref[...]                          # (P, 1)
    inv_denom = inv_denom_ref[0, 0]
    # gated mean of {admitted externals} ∪ {w}: eq. (6) bracket
    mean = inv_denom * (w + jnp.sum(g[:, :, None] * ext, axis=0))
    attraction = w - mean
    if elastic:
        out = (w - eps * dw) - elastic_alpha * attraction
    else:
        out = w - eps * (attraction + dw)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gossip_reduce_pallas(w2d, dw2d, ext3d, *, block_rows=64, interpret=None):
    """w2d/dw2d: (R, LANE); ext3d: (P, R, LANE); R % block_rows == 0.

    Returns (P, 3) f32: per external p
      [:, 0] = <dw, w - ext_p>
      [:, 1] = ||ext_p||^2
      [:, 2] = ||dw||^2  (same value in every row)
    """
    r = w2d.shape[0]
    p = ext3d.shape[0]
    grid = (r // block_rows,)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    acc_shape = _partials_shape((1,), p, block_rows)
    partials = pl.pallas_call(
        _reduce_kernel,
        grid=grid,
        in_specs=[spec, spec,
                  pl.BlockSpec((p, block_rows, LANE), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec(acc_shape, lambda i: (0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(acc_shape, jnp.float32),
        interpret=resolve_interpret(interpret),
    )(w2d, dw2d, ext3d)
    return _terms_from_partials(partials[0])


@functools.partial(jax.jit, static_argnames=(
    "eps", "elastic", "elastic_alpha", "block_rows", "interpret"))
def gossip_apply_pallas(w2d, dw2d, ext3d, gates, inv_denom, *, eps,
                        elastic=False, elastic_alpha=0.5, block_rows=64,
                        interpret=None):
    """Pass 2: elementwise gated mean + step with P scalar gates.

    gates: (P,) f32 in {0., 1.}; inv_denom: scalar f32 = 1/(sum gates + 1).
    Returns the updated (R, LANE) state.
    """
    r = w2d.shape[0]
    p = ext3d.shape[0]
    grid = (r // block_rows,)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_apply_kernel, eps=eps, elastic=elastic,
                          elastic_alpha=elastic_alpha),
        grid=grid,
        in_specs=[spec, spec,
                  pl.BlockSpec((p, block_rows, LANE), lambda i: (0, i, 0)),
                  pl.BlockSpec((p, 1), lambda i: (0, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(w2d.shape, w2d.dtype),
        interpret=resolve_interpret(interpret),
    )(w2d, dw2d, ext3d, gates.reshape(p, 1),
      jnp.asarray(inv_denom, jnp.float32).reshape(1, 1))


# ---------------------------------------------------------------------------
# worker-batched variants: (W, R, LANE) states, (W, P, R, LANE) externals
# ---------------------------------------------------------------------------

def _reduce_w_kernel(*refs, has_mask):
    if has_mask:
        w_ref, dw_ref, ext_ref, mask_ref, acc_ref = refs
    else:
        w_ref, dw_ref, ext_ref, acc_ref = refs
    i = pl.program_id(1)        # row-block index (innermost grid dim)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[0].astype(jnp.float32)            # (br, LANE)
    dw = dw_ref[0].astype(jnp.float32)          # (br, LANE)
    exts = [ext_ref[0, k].astype(jnp.float32)   # P x (br, LANE)
            for k in range(ext_ref.shape[1])]
    if has_mask:
        # restrict every reduction term to the exchanged partition: masking
        # dw kills off-partition <dw, w-ext> and ||dw||^2 contributions,
        # masking ext kills off-partition ||ext||^2 (m in {0,1}, m^2 == m)
        m = mask_ref[...].astype(jnp.float32)   # (br, LANE), worker-shared
        dw = dw * m
        exts = [ext * m for ext in exts]
    _accumulate_terms(acc_ref, w, dw, exts)


def _gated_sum(w, exts, gate):
    """w + sum_p gate(p) * ext_p — the eq.-(6) numerator; ``gate(p)`` reads
    the p-th admission gate as a scalar."""
    acc = gate(0) * exts[0]
    for k in range(1, len(exts)):
        acc = acc + gate(k) * exts[k]
    return w + acc


# whole-array SMEM operand: the per-worker scalars of pass 2, read with the
# worker grid index (a (1, P) or (1, 1) VMEM block would break the TPU
# (8, 128) block rule)
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _apply_w_kernel(*refs, eps, elastic, elastic_alpha, has_mask):
    if has_mask:
        w_ref, dw_ref, ext_ref, gates_ref, inv_ref, mask_ref, out_ref = refs
    else:
        w_ref, dw_ref, ext_ref, gates_ref, inv_ref, out_ref = refs
    wi = pl.program_id(0)
    w = w_ref[0].astype(jnp.float32)            # (br, LANE)
    dw = dw_ref[0].astype(jnp.float32)
    exts = [ext_ref[0, k].astype(jnp.float32)   # P x (br, LANE)
            for k in range(ext_ref.shape[1])]
    mean = inv_ref[wi] * _gated_sum(w, exts, lambda k: gates_ref[wi, k])
    attraction = w - mean
    if has_mask:
        # off-partition positions take the plain SGD step (the attraction is
        # defined only on the exchanged partition in 'leaves' mode)
        attraction = attraction * mask_ref[...].astype(jnp.float32)
    if elastic:
        out = (w - eps * dw) - elastic_alpha * attraction
    else:
        out = w - eps * (attraction + dw)
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gossip_reduce_w_pallas(w3d, dw3d, ext4d, mask2d=None, *, block_rows=64,
                           interpret=None):
    """Worker-batched pass 1.  w3d/dw3d: (W, R, LANE); ext4d: (W, P, R, LANE);
    mask2d: optional (R, LANE) partition mask shared across workers.

    Returns (W, P, 3) f32: per worker w and external p
      [..., 0] = <dw_w, w_w - ext_wp>   (mask-restricted when given)
      [..., 1] = ||ext_wp||^2
      [..., 2] = ||dw_w||^2  (same value in every p row)
    """
    wn, r = w3d.shape[:2]
    p = ext4d.shape[1]
    grid = (wn, r // block_rows)
    spec_s = pl.BlockSpec((1, block_rows, LANE), lambda wi, i: (wi, i, 0))
    spec_e = pl.BlockSpec((1, p, block_rows, LANE),
                          lambda wi, i: (wi, 0, i, 0))
    in_specs = [spec_s, spec_s, spec_e]
    operands = [w3d, dw3d, ext4d]
    if mask2d is not None:
        in_specs.append(pl.BlockSpec((block_rows, LANE),
                                     lambda wi, i: (i, 0)))
        operands.append(mask2d)
    acc_shape = _partials_shape((wn,), p, block_rows)
    partials = pl.pallas_call(
        functools.partial(_reduce_w_kernel, has_mask=mask2d is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1,) + acc_shape[1:],
                               lambda wi, i: (wi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(acc_shape, jnp.float32),
        interpret=resolve_interpret(interpret),
    )(*operands)
    return _terms_from_partials(partials)


@functools.partial(jax.jit, static_argnames=(
    "eps", "elastic", "elastic_alpha", "block_rows", "interpret"))
def gossip_apply_w_pallas(w3d, dw3d, ext4d, gates, inv_denom, mask2d=None, *,
                          eps, elastic=False, elastic_alpha=0.5,
                          block_rows=64, interpret=None):
    """Worker-batched pass 2: per-worker gated mean + step.

    gates: (W, P) f32 in {0., 1.}; inv_denom: (W,) f32 = 1/(sum_p g + 1).
    mask2d: optional (R, LANE) partition mask — masked-out positions take the
    plain SGD step.  Returns the updated (W, R, LANE) states.
    """
    wn, r = w3d.shape[:2]
    p = ext4d.shape[1]
    grid = (wn, r // block_rows)
    spec_s = pl.BlockSpec((1, block_rows, LANE), lambda wi, i: (wi, i, 0))
    spec_e = pl.BlockSpec((1, p, block_rows, LANE),
                          lambda wi, i: (wi, 0, i, 0))
    in_specs = [spec_s, spec_s, spec_e, _SMEM, _SMEM]
    operands = [w3d, dw3d, ext4d, gates.astype(jnp.float32),
                jnp.asarray(inv_denom, jnp.float32).reshape(wn)]
    if mask2d is not None:
        in_specs.append(pl.BlockSpec((block_rows, LANE),
                                     lambda wi, i: (i, 0)))
        operands.append(mask2d)
    return pl.pallas_call(
        functools.partial(_apply_w_kernel, eps=eps, elastic=elastic,
                          elastic_alpha=elastic_alpha,
                          has_mask=mask2d is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=spec_s,
        out_shape=jax.ShapeDtypeStruct(w3d.shape, w3d.dtype),
        interpret=resolve_interpret(interpret),
    )(*operands)


# ---------------------------------------------------------------------------
# packed-resident variants: row-range partition mask from scalar prefetch
# (group-contiguous layout, core/packing.py pack_spec_w(groups=))
# ---------------------------------------------------------------------------

def _row_range_mask(rr_ref, block_idx, block_rows):
    """(block_rows, LANE) f32 in-register mask: 1.0 where the global row
    index falls inside the prefetched [row_start, row_end) partition."""
    rows = block_idx * block_rows + jax.lax.broadcasted_iota(
        jnp.int32, (block_rows, LANE), 0)
    return ((rows >= rr_ref[0]) & (rows < rr_ref[1])).astype(jnp.float32)


def _resident_exts(ext_ref, scales_ref):
    """The P externals of one row block as f32 (br, LANE) values.  With
    int8 wire scales (the whole (W, P, R // block_rows) array in SMEM: one
    f32 per external per row block — the quantization tile == the kernel
    grid block) the dequantization is fused here, so the external never
    materializes in float in HBM."""
    exts = []
    for k in range(ext_ref.shape[1]):
        ext = ext_ref[0, k].astype(jnp.float32)
        if scales_ref is not None:
            wi, i = pl.program_id(0), pl.program_id(1)
            ext = ext * scales_ref[wi, k, i]
        exts.append(ext)
    return exts


def _reduce_w_resident_kernel(*refs, block_rows, has_scales):
    if has_scales:
        rr_ref, w_ref, dw_ref, ext_ref, scales_ref, acc_ref = refs
    else:
        rr_ref, w_ref, dw_ref, ext_ref, acc_ref = refs
        scales_ref = None
    i = pl.program_id(1)        # row-block index (innermost grid dim)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m = _row_range_mask(rr_ref, i, block_rows)
    w = w_ref[0].astype(jnp.float32)                 # (br, LANE)
    dw = dw_ref[0].astype(jnp.float32) * m
    exts = [ext * m for ext in _resident_exts(ext_ref, scales_ref)]
    _accumulate_terms(acc_ref, w, dw, exts)


def _apply_w_resident_kernel(*refs, elastic, elastic_alpha, block_rows,
                             has_scales):
    if has_scales:
        (rr_ref, w_ref, dw_ref, ext_ref, scales_ref, gates_ref, inv_ref,
         lr_ref, out_ref) = refs
    else:
        (rr_ref, w_ref, dw_ref, ext_ref, gates_ref, inv_ref, lr_ref,
         out_ref) = refs
        scales_ref = None
    wi, i = pl.program_id(0), pl.program_id(1)
    m = _row_range_mask(rr_ref, i, block_rows)
    w = w_ref[0].astype(jnp.float32)                 # (br, LANE)
    dw = dw_ref[0].astype(jnp.float32)
    exts = _resident_exts(ext_ref, scales_ref)
    # lr is a RUNTIME operand (one f32 scalar shared by the whole grid):
    # the eq.-1 local update w - lr*dw is applied in-register in the same
    # sweep as the blend, and an lr schedule never forces a recompile
    lr = lr_ref[0]
    mean = inv_ref[wi] * _gated_sum(w, exts, lambda k: gates_ref[wi, k])
    # off-partition positions take the plain SGD step (the attraction is
    # defined only on the exchanged row range)
    attraction = (w - mean) * m
    if elastic:
        out = (w - lr * dw) - elastic_alpha * attraction
    else:
        out = w - lr * (attraction + dw)
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gossip_reduce_w_resident_pallas(row_range, w3d, dw3d, ext4d,
                                    ext_scales=None, *, block_rows=64,
                                    interpret=None):
    """Packed-resident pass 1.  row_range: (2,) int32 [row_start, row_end)
    of the exchanged partition (scalar prefetch); w3d/dw3d: (W, R, LANE);
    ext4d: (W, P, R, LANE) — float, or int8 when ext_scales
    (W, P, R // block_rows) f32 is given: dequantization is then fused
    into the pass (in-register q * scale per grid block).

    Returns (W, P, 3) f32 accumulators as gossip_reduce_w_pallas, with
    every term restricted to the row range — no mask operand, no mask HBM
    traffic.
    """
    wn, r = w3d.shape[:2]
    p = ext4d.shape[1]
    in_specs = [
        pl.BlockSpec((1, block_rows, LANE), lambda wi, i, rr: (wi, i, 0)),
        pl.BlockSpec((1, block_rows, LANE), lambda wi, i, rr: (wi, i, 0)),
        pl.BlockSpec((1, p, block_rows, LANE),
                     lambda wi, i, rr: (wi, 0, i, 0)),
    ]
    operands = [w3d, dw3d, ext4d]
    if ext_scales is not None:
        in_specs.append(_SMEM)
        operands.append(ext_scales.astype(jnp.float32))
    acc_shape = _partials_shape((wn,), p, block_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(wn, r // block_rows),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1,) + acc_shape[1:],
                               lambda wi, i, rr: (wi, 0, 0, 0)),
    )
    partials = pl.pallas_call(
        functools.partial(_reduce_w_resident_kernel, block_rows=block_rows,
                          has_scales=ext_scales is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(acc_shape, jnp.float32),
        interpret=resolve_interpret(interpret),
    )(row_range.astype(jnp.int32), *operands)
    return _terms_from_partials(partials)


@functools.partial(jax.jit, static_argnames=(
    "elastic", "elastic_alpha", "block_rows", "interpret"))
def gossip_apply_w_resident_pallas(row_range, w3d, dw3d, ext4d, gates,
                                   inv_denom, lr, ext_scales=None, *,
                                   elastic=False, elastic_alpha=0.5,
                                   block_rows=64, interpret=None):
    """Packed-resident pass 2: per-worker gated mean + fused eq.-1 step,
    attraction restricted to the prefetched [row_start, row_end) partition;
    positions outside take the plain SGD step.  ``lr`` is a RUNTIME f32
    scalar (the eq.-1 step size — traced, so lr schedules never recompile
    the kernel; the Parzen gate's eps lives in pass 1's wrapper).  ext4d
    may be int8 with ext_scales (W, P, R // block_rows) — the
    dequantization is fused, as in pass 1.
    Returns the updated (W, R, LANE) states."""
    wn, r = w3d.shape[:2]
    p = ext4d.shape[1]
    spec_s = pl.BlockSpec((1, block_rows, LANE), lambda wi, i, rr: (wi, i, 0))
    in_specs = [
        spec_s, spec_s,
        pl.BlockSpec((1, p, block_rows, LANE),
                     lambda wi, i, rr: (wi, 0, i, 0)),
    ]
    operands = [w3d, dw3d, ext4d]
    if ext_scales is not None:
        in_specs.append(_SMEM)
        operands.append(ext_scales.astype(jnp.float32))
    in_specs += [_SMEM, _SMEM, _SMEM]
    operands += [gates.astype(jnp.float32),
                 jnp.asarray(inv_denom, jnp.float32).reshape(wn),
                 jnp.asarray(lr, jnp.float32).reshape(1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(wn, r // block_rows),
        in_specs=in_specs,
        out_specs=spec_s,
    )
    return pl.pallas_call(
        functools.partial(_apply_w_resident_kernel, elastic=elastic,
                          elastic_alpha=elastic_alpha, block_rows=block_rows,
                          has_scales=ext_scales is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(w3d.shape, w3d.dtype),
        interpret=resolve_interpret(interpret),
    )(row_range.astype(jnp.int32), *operands)

"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel ships three modules:
  <name>/kernel.py — pl.pallas_call with explicit BlockSpec VMEM tiling
  <name>/ops.py    — the jit'd public wrapper (auto shape padding, dtype)
  <name>/ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels (DESIGN.md §6):
  kmeans_assign — E-step distances + argmin + M-step partial sums (the
                  paper's K-Means inner loop), MXU-tiled.
  parzen_blend  — fused ASGD update eq. (4)+(6), single external (P=1).
  gossip_blend  — batched fused ASGD update: P externals per gossip round,
                  all gates + the gated mean in two HBM passes.
  ssd_scan      — mamba-2 chunked SSD inner scan.
  flash_attention — causal attention, forward and backward, with the
                  score tiles in VMEM (JAX's Pallas TPU kernels, bound
                  to this convention).

``interpret`` convention: every public kernel entry point takes
``interpret=None`` meaning "auto" — run the Pallas interpreter only when no
TPU backend is present (CPU CI / tests), compile for real hardware
otherwise.  Resolution happens once, in :func:`resolve_interpret`.
"""
from __future__ import annotations

import functools

import jax

# f32 lane width of the flat-state (R, LANE) kernel layout, shared by
# parzen_blend / gossip_blend and the pack-once layer (core/packing.py)
LANE = 512


@functools.lru_cache(maxsize=None)
def _has_tpu_backend() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret) -> bool:
    """Resolve the tri-state ``interpret`` kernel argument.

    None  -> auto: interpret unless a TPU backend is available.
    bool  -> explicit override, returned unchanged.

    Must be called before ``pl.pallas_call`` / before the value is used as a
    jit-static argument (None is not a valid pallas interpret value).
    """
    if interpret is None:
        return not _has_tpu_backend()
    return bool(interpret)

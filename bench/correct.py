"""The comparison that decides a training cell's ``correct``.

Both sides are put in the terms of :class:`bench.reference.asgd.Outputs`:
the per-step losses, per-leaf norms of the first gradient and of the change
over the first steps, the payloads in flight, the gates and the tokens fed.
The program's side is read from what its timed path produced: the losses
its loop returned, the gates its step returned, its weights after the first
step and after the last (host copies, split into leaves by the program's
own packing table), its staleness FIFO, and the batches it was fed.

The payloads in flight are compared value by value, so a payload from the
wrong peer, or from the wrong rows, shows.  The steps compared are the
first few, before rounding has grown into the trajectory: every gate of
those steps is shut in both (see PERF.md on why no later step is
compared).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.asgd import Outputs

# a leaf whose reference gradient is below this share of the median leaf's
# is left out of the change comparison: it moves by round-off alone
STILL_LEAF = 1e-3


def _gaps(prog, ref, keep=None):
    """Worst leaf: |prog - ref| over max(ref, the median leaf's ref)."""
    base = np.maximum(ref, np.median(ref, axis=1, keepdims=True))
    gap = np.abs(prog - ref) / base
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    return float(np.max(gap))


def compare(prog: Outputs, ref: Outputs) -> dict:
    """The numbers compared, each a worst case over steps, replicas and
    leaves (0 is perfect agreement)."""
    keep = ref.grad_norms >= STILL_LEAF * np.median(ref.grad_norms, axis=1,
                                                    keepdims=True)
    fifo = fifo_gap(prog.fifo, ref.fifo)
    if not np.array_equal(prog.fifo_part, ref.fifo_part):
        fifo = max(fifo, 1.0)
    return {
        "loss_gap": float(np.max(np.abs(prog.losses - ref.losses)
                                 / np.abs(ref.losses))),
        "grad_gap": _gaps(prog.grad_norms, ref.grad_norms),
        "change_gap": _gaps(prog.change_norms, ref.change_norms, keep),
        "fifo_gap": float(fifo),
        "gate_mismatches": int(np.sum(prog.gates != ref.gates)),
        "token_mismatches": int(np.sum(prog.tokens != ref.tokens)),
    }


def fifo_gap(prog: list, ref: list) -> float:
    """Worst payload in flight: ||prog - ref|| / ||ref||, per slot and
    receiver; where the program's payload runs on past the reference's
    (padding to whole rows), the rest counts against zero."""
    worst = 0.0
    for p, r in zip(prog, ref):
        n = r.shape[1]
        d2 = (np.sum(np.square(p[:, :n] - r), axis=1)
              + np.sum(np.square(p[:, n:]), axis=1))
        gap = np.sqrt(d2) / np.maximum(np.linalg.norm(r, axis=1), 1e-30)
        worst = max(worst, float(np.max(gap)))
    return worst


def worst_leaves(prog: Outputs, ref: Outputs, names) -> str:
    """Which replica and leaf set grad_gap and change_gap, for the log."""
    out = []
    for what in ("grad_norms", "change_norms"):
        p, r = getattr(prog, what), getattr(ref, what)
        gap = np.abs(p - r) / np.maximum(r, np.median(r, axis=1,
                                                      keepdims=True))
        w, leaf = np.unravel_index(np.argmax(gap), gap.shape)
        out.append(f"{what}: worst replica {w} leaf {names[leaf]} gap "
                   f"{gap[w, leaf]:.4g} (program {p[w, leaf]:.6g}, "
                   f"reference {r[w, leaf]:.6g})")
    return "; ".join(out)


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) — every number within its
    limit, and no number without one."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return all(v <= limits[k] for k, v in numbers.items()), checks


def leaf_offsets(spec, lane: int) -> list[tuple[int, int]]:
    """(offset, size) of each leaf, in flatten order, in one replica's
    packed rows: the program's group-contiguous packing table."""
    out = [None] * len(spec.sizes)
    for idxs, (r0, _) in zip(spec.group_leaves, spec.group_row_ranges):
        off = r0 * lane
        for i in idxs:
            out[i] = (off, spec.sizes[i])
            off += spec.sizes[i]
    return out


@partial(jax.jit, static_argnums=2)
def _leaf_norms(packed, w0, offsets):
    flat = packed.reshape(packed.shape[0], -1)
    return jnp.stack([jnp.linalg.norm(flat[:, o:o + n] - w.reshape(1, -1),
                                      axis=1)
                      for (o, n), w in zip(offsets, w0)], axis=1)


def payloads(buf, scales, part, group_rows, block_rows: int) -> list:
    """The payloads of an int8 FIFO (depth, W, R, lane) with scales
    (depth, W, R / block_rows), as depth float32 arrays (W, R * lane):
    each slot holds partition ``part[k]`` at its rows ``group_rows`` of
    the packed layout and zeros elsewhere, and is returned with those rows
    first and the rest after them."""
    d, w, r, lane = buf.shape
    q = buf.reshape(d, w, r // block_rows, block_rows * lane)
    out = []
    for k in range(d):
        x = (q[k].astype(np.float32) * scales[k][..., None]).reshape(w, -1)
        r0, r1 = group_rows[int(part[k])]
        out.append(np.concatenate([x[:, r0 * lane:r1 * lane],
                                   x[:, :r0 * lane], x[:, r1 * lane:]],
                                  axis=1))
    return out


def program_outputs(cap: dict, w0, offsets, eps: float,
                    block_rows: int) -> Outputs:
    """The program's side, from the capture of its first steps (see
    bench.harness) and the reference's initial weights ``w0``."""
    offsets = tuple(offsets)
    w1 = jax.device_put(cap["w1"])
    g = np.asarray(_leaf_norms(w1, w0, offsets)) / eps
    del w1
    w3 = jax.device_put(cap["w_last"])
    d = np.asarray(_leaf_norms(w3, w0, offsets))
    del w3
    return Outputs(losses=np.asarray(cap["losses"]), grad_norms=g,
                   change_norms=d,
                   fifo=payloads(cap["fifo"], cap["fifo_scales"],
                                 cap["fifo_part"], cap["group_rows"],
                                 block_rows),
                   fifo_part=cap["fifo_part"], gates=cap["gates"],
                   tokens=cap["tokens"])

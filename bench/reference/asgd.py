"""Reference of the first steps of pipelined ASGD training, written from
the paper (eqs. 1, 3, 4 and 6) and the trainer's documented schedule.

W replicas start from the same weights.  At step t, with key
``fold_in(key(seed), t)`` split into (k_shift, k_part), every replica

1. launches partition ``part_t`` of its current weights, quantized to int8
   with one absmax/127 scale per tile of ``block_rows * lane`` values, to
   the replica ``shift_t`` places ahead on the ring;
2. computes its loss and gradient g on its own batch;
3. consumes the payload launched ``delay + 1`` steps earlier (none yet in
   the first ``delay + 1`` steps): the Parzen gate admits the external e
   when a plain step would bring the replica closer to it,
   ||(w - eps g) - e||^2 < ||w - e||^2 over that partition, and e != 0;
4. steps w <- w - eps (g + gate (w - e) / 2) on the partition and
   w <- w - eps g elsewhere (the gated mean of {w, e} is (w + gate e) /
   (1 + gate)).

Partitions are the trainer's leaf groups: leaves in flatten order, taken
largest first, each to the group with the least load; a group's leaves lie
back to back, padded to whole tiles, which is what the wire quantizes.

The reference imports nothing of the system under test.  ``fault`` plants
one of the faults a run can have, for the checks of the comparison:
``unchanged`` (the step returns its state), ``half_batch`` (the loss is
the mean over the first half of each row only), ``no_exchange`` (nothing
arrives) and ``wrong_peer`` (each replica receives from the replica one
place beyond its peer).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import data


@dataclasses.dataclass(frozen=True)
class Settings:
    """The run the reference follows, read from a cell's trainer argv."""
    workers: int
    batch: int
    seq: int
    eps: float
    partial_blocks: int
    delay: int
    block_rows: int
    lane: int

    @property
    def shifts(self) -> tuple:
        return tuple(s for s in (1, 2, 4, 8) if s < max(self.workers, 2))

    @property
    def tile(self) -> int:
        return self.block_rows * self.lane


@dataclasses.dataclass
class Outputs:
    """What one run produced in its first steps, in the comparison's terms.

    losses (steps,): mean loss over the replicas at each step;
    grad_norms (W, L): per-leaf norms of the step-0 gradient;
    change_norms (W, L): per-leaf norms of w_steps - w_0;
    fifo (depth arrays (W, n)) and fifo_part (depth,): the dequantized
      payloads in flight after the last step, oldest first, each as its
      receiver holds it (the program's may run on past n with padding);
    gates (steps, W); tokens (steps, W, B, S);
    margins (steps, W): the reference's gate margin
      (d_before - d_after) / d_before, NaN where nothing was consumed (the
      program's side has none)."""
    losses: np.ndarray
    grad_norms: np.ndarray
    change_norms: np.ndarray
    fifo: list
    fifo_part: np.ndarray
    gates: np.ndarray
    tokens: np.ndarray
    margins: np.ndarray | None = None


def leaf_groups(sizes, p: int) -> list[int]:
    loads, gid = [0] * p, [0] * len(sizes)
    for i in sorted(range(len(sizes)), key=lambda i: -sizes[i]):
        g = min(range(p), key=lambda j: loads[j])
        gid[i] = g
        loads[g] += sizes[i]
    return gid


def quantize(flat, tile: int):
    """int8 wire round trip of a flat vector, one scale per ``tile``."""
    n = flat.shape[0]
    t = jnp.pad(flat, (0, -n % tile)).reshape(-1, tile)
    scale = jnp.max(jnp.abs(t), axis=1, keepdims=True) / 127.0
    q = jnp.where(scale > 0, jnp.round(t / jnp.where(scale > 0, scale, 1)),
                  0.0)
    return (jnp.clip(q, -127, 127) * scale).reshape(-1)[:n]


def run(model, cfg, st: Settings, seed: int, *, steps: int = 3,
        dtype=jnp.float32, fault: str | None = None):
    """Run the reference for ``steps`` steps from the seed.

    The forward and backward passes are computed in ``dtype`` (float32 at
    the highest matmul precision, or the lower precision of the control);
    the weights and the update stay float32.  Returns (Outputs, w0 leaves).
    """
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    key = jax.random.key(seed)
    w0 = jax.jit(partial(model.init, cfg))(key)
    leaves0, treedef = jax.tree.flatten(w0)
    sizes = [int(l.size) for l in leaves0]
    gid = leaf_groups(sizes, st.partial_blocks)
    members = [[i for i in range(len(sizes)) if gid[i] == g]
               for g in range(st.partial_blocks)]
    tokens = data.worker_batches(seed, st.workers, st.batch, st.seq,
                                 cfg["vocab_size"], steps)
    used = tokens[..., :st.seq // 2] if fault == "half_batch" else tokens

    @jax.jit
    def value_and_grad(leaves, toks):
        def f(leaves):
            p = jax.tree.unflatten(treedef, [l.astype(dtype) for l in leaves])
            return model.loss(cfg, p, toks, prec=prec)
        return jax.value_and_grad(f)(leaves)

    @partial(jax.jit, static_argnums=1)
    def payload(leaves, g):
        flat = jnp.concatenate([leaves[i].reshape(-1) for i in members[g]])
        return quantize(flat, st.tile)

    @jax.jit
    def plain(leaves, grads):
        return [l - st.eps * d for l, d in zip(leaves, grads)]

    @partial(jax.jit, static_argnums=3)
    def update(leaves, grads, ext, g):
        w = jnp.concatenate([leaves[i].reshape(-1) for i in members[g]])
        dw = jnp.concatenate([grads[i].reshape(-1) for i in members[g]])
        d_after = jnp.sum(jnp.square(w - st.eps * dw - ext))
        d_before = jnp.sum(jnp.square(w - ext))
        gate = ((d_after < d_before)
                & (jnp.sum(jnp.square(ext)) > 0)).astype(jnp.float32)
        pull = gate * (w - ext) / 2
        new = plain(leaves, grads)
        off = 0
        for i in members[g]:
            n = sizes[i]
            new[i] = new[i] - st.eps * pull[off:off + n].reshape(
                leaves[i].shape)
            off += n
        return new, gate, (d_before - d_after) / d_before

    @jax.jit
    def leaf_norms(a, b=None):
        b = [0.0] * len(a) if b is None else b
        return jnp.stack([jnp.linalg.norm((x - y).reshape(-1))
                          for x, y in zip(a, b)])

    w = [leaves0] * st.workers
    fifo, losses, gates, margins = {}, [], [], []
    grad_norms = None
    lag = st.delay + 1
    sender = 1 if fault == "wrong_peer" else 0
    for t in range(steps):
        ks, kp = jax.random.split(jax.random.fold_in(key, t))
        shift = st.shifts[int(jax.random.randint(ks, (), 0, len(st.shifts)))]
        part = int(jax.random.randint(kp, (), 0, st.partial_blocks))
        sent = [payload(w[j], part) for j in range(st.workers)]
        arrived = [jnp.zeros_like(sent[0]) if fault == "no_exchange"
                   else sent[(i - shift - sender) % st.workers]
                   for i in range(st.workers)]
        fifo[t] = (part, arrived)
        ext_part, ext = fifo.pop(t - lag) if t >= lag else (None, None)
        step_loss, step_gate, step_margin, new_w = [], [], [], []
        for i in range(st.workers):
            lv, g = value_and_grad(w[i], jnp.asarray(used[t, i]))
            if t == 0:
                n = np.asarray(leaf_norms(g))
                grad_norms = n[None] if grad_norms is None else np.concatenate(
                    [grad_norms, n[None]])
            if ext is None:
                nw, gate, margin = plain(w[i], g), 0.0, np.nan
            else:
                nw, gate, margin = update(w[i], g, ext[i], ext_part)
            step_loss.append(float(lv))
            step_gate.append(float(gate))
            step_margin.append(float(margin))
            new_w.append(w[i] if fault == "unchanged" else nw)
        w = new_w
        losses.append(np.mean(step_loss))
        gates.append(step_gate)
        margins.append(step_margin)
    change_norms = np.stack([np.asarray(leaf_norms(w[i], leaves0))
                             for i in range(st.workers)])
    slots = [fifo[t] for t in sorted(fifo)]
    out = Outputs(losses=np.array(losses), grad_norms=grad_norms,
                  change_norms=change_norms,
                  fifo=[np.stack([np.asarray(e) for e in arrived])
                        for _, arrived in slots],
                  fifo_part=np.array([p for p, _ in slots]),
                  gates=np.array(gates), tokens=tokens,
                  margins=np.array(margins))
    return out, leaves0

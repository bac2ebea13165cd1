"""SPMD (TPU-native) ASGD gossip — DESIGN.md §2.2.

Execution model: every param leaf carries a leading worker axis ``W`` that is
sharded over the mesh's data-parallel axes (``data`` or ``(pod, data)``);
each of the W worker groups holds its own model replica, tensor-parallel over
``model``. One ASGD round per train step (the paper communicates once per
mini-batch):

  1. pick a random 1/p partition of the state                (partial updates §4.4)
  2. exchange it with a ring/exponential peer:
       jnp.roll along the worker axis with a static shift s drawn from a
       small set via lax.switch -> XLA lowers each branch to ONE
       collective-permute per exchanged leaf (point-to-point; the cheapest
       collective — the moral equivalent of the paper's single-sided
       'send to one random peer', see DESIGN.md table)
  3. blend the *previous* round's received block (staleness delay >= 1, the
     asynchrony analogue) through the Parzen gate, eq. (4)-(6) — with
     ASGDConfig.use_fused the whole gate + blend runs through the
     worker-batched gossip_blend Pallas kernel on the pack-once
     (W_local, R, LANE) layout (core/packing.py pack_w): all W gates and
     gated means in exactly two guaranteed kernel passes (the per-round
     pack/unpack boundary adds copy sweeps — honest byte accounting in
     EXPERIMENTS.md §Perf).  use_fused=False keeps the jnp tree reduction
     as the reference path (_per_worker_reduce3, the single-traversal jnp
     mirror of kernel pass 1; _gossip_gate's single_sweep=False selects
     the original four-traversal ablation form)
  4. store the newly received block in the staleness buffer

Packed-resident rounds (asgd_gossip_apply_packed, DESIGN.md §6): with the
group-contiguous layout (core/packing.py pack_spec_w(groups=)) the packed
(W, R, LANE) ensemble is carried ACROSS rounds — the exchange is a static
slice of packed rows (wire bytes stay |w|/p), the staleness buffer is
packed rows (PackedGossipState), and the blend is the row-range resident
kernel (no materialized mask, no pack/unpack inside the round).  The
per-round pack/unpack boundary of the pytree fused path disappears: 18 ->
~9 sweep-byte units per round (EXPERIMENTS.md §Perf).

Partial-update partitioning (paper §4.4 leaves "the choice of the
partitioning to the application"):
  * 'leaves' — p static leaf groups (≈ layer blocks), selected by lax.switch;
    non-selected leaves are NOT communicated at all (they enter the exchange
    as locally-generated zeros). This is the LM mode: every collective moves
    |w|/p bytes and no traced offset ever touches a model-sharded dim (traced
    dynamic-slice on a sharded axis would force XLA to all-gather the leaf —
    measured, see EXPERIMENTS.md §Perf).
  * 'rows' — traced dynamic-slice of 1/p of each leaf along its first
    non-worker dim. Matches the paper's K-Means partitioning "along the
    individual cluster centers"; only safe when that dim is unsharded.

Collective bytes per step = |w| / p per worker group, vs 2|w| (ring
all-reduce) for the synchronous BATCH baseline — the roofline tables in
EXPERIMENTS.md quantify this on all 10 assigned architectures.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .asgd import ASGDConfig
from .parzen import gate_from_terms


@dataclasses.dataclass(frozen=True)
class GossipConfig:
    """SPMD gossip parameters.

    shifts: static ring shifts; one is drawn per round (exponential gossip —
      information reaches all W workers in O(log W) rounds, the scheduled
      counterpart of 'random recipient').
    partial_blocks: p — each round exchanges ~1/p of the state.
    partial_mode: 'leaves' (static leaf groups) or 'rows' (traced row slice).
    delay: staleness in rounds. delay=1 blends states received last round
      (faithful: a receiver only ever sees a past sender state);
      delay=0 blends immediately (synchronous gossip, beyond-paper ablation).
    wire_format: what travels on the wire (DESIGN.md §6 wire formats):
      * None     — the carrier dtype, no transformation (default; if
                   payload_dtype is set it resolves to "dtype" for
                   backward compatibility);
      * "dtype"  — cast to payload_dtype for the collective, cast back on
                   receipt (a fake-quant round-trip: the staleness buffer
                   always stores carrier-dtype values);
      * "int8"   — int8 quantization with per-block_rows f32 scales
                   (core/packing.py quantize_rows).  On the packed-resident
                   engine this is a REAL wire format: the collective moves
                   int8 payload + tiny scales, the staleness buffer stays
                   quantized, and the resident kernel dequantizes
                   in-register.  On the pytree engines it is the
                   per-worker-per-leaf fake-quant stand-in.
    payload_dtype: wire dtype for wire_format="dtype".
    """

    shifts: tuple = (1, 2, 4, 8)
    partial_blocks: int = 4
    partial_mode: str = "leaves"
    delay: int = 1
    wire_format: Any = None
    payload_dtype: Any = None
    # communication interval: gossip every k-th step (paper's frequency
    # 1/b generalized — on TPU the mini-batch is the step, so the interval
    # is expressed in steps). 1 == every step (paper default).
    gossip_every: int = 1
    # fused-path (ASGDConfig.use_fused) knobs: row-block size of the
    # pack-once (W_local, R, LANE) kernel layout, and the mesh axis name(s)
    # to psum the (W_local, P, 3) gate accumulator over when the blend runs
    # under shard_map with the non-worker state dims also manually sharded
    # (see launch/mesh.py shard_map_workers + DESIGN.md §2.2). () == no psum
    # (single-shard states: the in-jit GSPMD path and all tests).
    fused_block_rows: int = 64
    gate_psum_axes: tuple = ()


# ---------------------------------------------------------------------------
# wire format: the ONE place the exchanged block's on-wire representation is
# decided (unifies the historical _roll_group / _apply_rows /
# _roll_packed_rows / mesh-region cast sites, which disagreed on whether the
# staleness buffer stored wire-dtype or carrier-dtype values)
# ---------------------------------------------------------------------------

def resolved_wire_format(cfg: GossipConfig):
    """Resolve GossipConfig.wire_format to None | "dtype" | "int8".

    wire_format=None with payload_dtype set keeps the pre-wire_format
    behaviour (a payload_dtype cast) as "dtype"."""
    wf = cfg.wire_format
    if wf is None:
        return "dtype" if cfg.payload_dtype is not None else None
    if wf == "dtype":
        if cfg.payload_dtype is None:
            raise ValueError(
                'wire_format="dtype" requires payload_dtype')
        return "dtype"
    if wf == "int8":
        if cfg.payload_dtype is not None:
            raise ValueError(
                'wire_format="int8" ignores payload_dtype — remove '
                "payload_dtype or use wire_format=\"dtype\"")
        return "int8"
    raise ValueError(f"unknown wire_format {wf!r} "
                     '(expected None, "dtype" or "int8")')


def _fake_quant_leaf(x):
    """Per-worker int8 fake-quant round-trip of one (W, ...) leaf — the
    pytree-engine stand-in for the packed int8 wire (one absmax scale per
    worker row per leaf; zeros stay exactly zero, eq. 3)."""
    x32 = x.astype(jnp.float32)
    axes = tuple(range(1, x.ndim))
    absmax = jnp.max(jnp.abs(x32), axis=axes, keepdims=True)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0.0,
                    1.0 / jnp.where(scale > 0.0, scale, 1.0), 0.0)
    q = jnp.clip(jnp.round(x32 * inv), -127.0, 127.0)
    return (q * scale).astype(x.dtype)


def wire_roundtrip(tree, cfg: GossipConfig):
    """Apply the wire round-trip to every leaf of a (sub)tree or array.

    The sender-side transformation of the exchanged block, as VALUES: the
    receiver always stores carrier-dtype numbers that have made the wire
    round-trip ("dtype": cast down and back; "int8": fake-quant; None:
    identity).  Commutes with the worker roll (both are elementwise /
    worker-permutation maps), so GSPMD stand-ins may apply it on either
    side of the collective.  The packed-resident engine does NOT use this
    for "int8" — there the wire is genuinely quantized
    (exchange_packed / quantize_rows) and dequantization happens inside
    the kernel."""
    wf = resolved_wire_format(cfg)
    if wf is None:
        return tree
    if wf == "dtype":
        return jax.tree.map(
            lambda x: x.astype(cfg.payload_dtype).astype(x.dtype), tree)
    return jax.tree.map(_fake_quant_leaf, tree)


# ---------------------------------------------------------------------------
# per-peer liveness (elastic fault tolerance — DESIGN.md §8): a (W,) f32
# 0/1 vector saying which worker groups are alive THIS round.  A dead (or
# just-joined) peer's payload is dropped on the wire (masked to the eq.-3
# all-zero 'no message') and its gate is closed at the blend — the existing
# gate_scale operand composes the scalar staleness guard with the per-peer
# vector, so no kernel or gate math changes.  live=None everywhere keeps
# the exact legacy computation.
# ---------------------------------------------------------------------------

def roll_live(live, shift_idx, cfg: GossipConfig):
    """Receiver-side validity of this round's payload: worker w's slot is
    real iff the SENDER (w - shift) was alive at launch AND w itself is
    alive to receive it.  Same lax.switch-over-static-shifts structure as
    the payload exchange, so the two travel the identical permutation."""
    branches = [(lambda l, s=s: jnp.roll(l, s, axis=0))
                for s in cfg.shifts]
    return jax.lax.switch(shift_idx, branches, live) * live


def mask_live_rows(x, live):
    """Zero the worker rows whose liveness is 0 (eq. 3: an all-zero block
    IS 'no message').  jnp.where, not multiplication — live rows pass
    through bitwise (the live=ones parity guarantee) and int8 payloads
    stay int8."""
    if live is None:
        return x
    cond = live.reshape((-1,) + (1,) * (x.ndim - 1)) > 0.0
    return jnp.where(cond, x, jnp.zeros_like(x))


def mask_live_tree(tree, live):
    """mask_live_rows over every (W, ...) leaf of a pytree."""
    if live is None:
        return tree
    return jax.tree.map(lambda x: mask_live_rows(x, live), tree)


def combine_gate_scale(valid, *lives):
    """Fold the scalar staleness guard and any per-peer liveness vectors
    into the ONE gate_scale operand the blend paths already accept
    (kernels/gossip_blend ops.py _scale_gates handles scalar and (W,)).
    None entries are skipped; all-None returns None (no gating)."""
    out = valid
    for lv in lives:
        if lv is None:
            continue
        out = lv if out is None else out * lv
    return out


def _resolve_live(state_is_elastic: bool, live, n_workers: int,
                  engine: str):
    """Normalize the per-round ``live`` argument against the state.

    Elastic-initialized states (init_*_gossip_state(elastic=True) carry a
    buf_live mask) default to all-alive when the caller passes nothing;
    passing ``live`` into a NON-elastic state raises — lazily growing
    buf_live mid-run would change the carried pytree structure between
    jitted steps."""
    if state_is_elastic:
        if live is None:
            return jnp.ones((n_workers,), jnp.float32)
        return jnp.asarray(live, jnp.float32)
    if live is not None:
        raise ValueError(
            f"{engine}: live= requires a state initialized with "
            "elastic=True (the carried buf_live mask cannot appear "
            "mid-run without changing the state pytree structure)")
    return None


# ---------------------------------------------------------------------------
# leaf partitioning ('leaves' mode)
# ---------------------------------------------------------------------------

def leaf_groups(params, p: int):
    """Assign each leaf a static group id in [0, p) — greedy size balancing.

    Returns a pytree of python ints (static metadata, not traced).
    """
    leaves, treedef = jax.tree.flatten(params)
    order = sorted(range(len(leaves)), key=lambda i: -leaves[i].size)
    loads = [0] * p
    gid = [0] * len(leaves)
    for i in order:
        g = min(range(p), key=lambda j: loads[j])
        gid[i] = g
        loads[g] += leaves[i].size
    return jax.tree.unflatten(treedef, gid)


def _roll_group(params, groups, g: int, shift: int, cfg: GossipConfig):
    """Branch body: roll group-``g`` leaves by ``shift`` along the worker
    axis (-> collective-permute); other leaves are local zeros (no comms).

    The wire round-trip (wire_roundtrip) happens HERE, on the rolled
    group's leaves only — transforming the whole tree up front would cost a
    full-state sweep per round for leaves that are never sent.  The buffer
    stores carrier-dtype values either way."""
    def f(x, gi):
        if gi != g:
            return jnp.zeros_like(x)
        return jnp.roll(wire_roundtrip(x, cfg), shift, axis=0)
    return jax.tree.map(f, params, groups)


def exchange_leaves(params, groups, shift_idx, block_idx, cfg: GossipConfig):
    """lax.switch over (shift, group) static pairs. Returns the peer block
    (full-tree shape; non-group leaves are zero and were never sent)."""
    branches = []
    for s in cfg.shifts:
        for g in range(cfg.partial_blocks):
            branches.append(
                lambda t, s=s, g=g: _roll_group(t, groups, g, s, cfg))
    idx = shift_idx * cfg.partial_blocks + block_idx
    return jax.lax.switch(idx, branches, params)


# ---------------------------------------------------------------------------
# row slicing ('rows' mode — K-Means-style, unsharded feature dims only)
# ---------------------------------------------------------------------------

def _block_size(dim0: int, p: int) -> int:
    return max(1, -(-dim0 // p))  # ceil


def slice_rows(tree, block_idx, p):
    """Dynamic-slice a 1/p block of every leaf along axis 1 (first non-worker
    dim). block_idx is traced; dynamic_slice clamps trailing blocks."""
    def f(x):
        if x.ndim < 2:
            return x
        blk = _block_size(x.shape[1], p)
        start = jnp.minimum(block_idx * blk, x.shape[1] - blk)
        starts = (0, start) + (0,) * (x.ndim - 2)
        return jax.lax.dynamic_slice(
            x, starts, (x.shape[0], blk) + x.shape[2:])
    return jax.tree.map(f, tree)


def update_rows(tree, block_tree, block_idx, p):
    """Inverse of slice_rows: write blended blocks back into full leaves."""
    def f(x, b):
        if x.ndim < 2:
            return b.astype(x.dtype)
        blk = _block_size(x.shape[1], p)
        start = jnp.minimum(block_idx * blk, x.shape[1] - blk)
        starts = (0, start) + (0,) * (x.ndim - 2)
        return jax.lax.dynamic_update_slice(x, b.astype(x.dtype), starts)
    return jax.tree.map(f, tree, block_tree)


def exchange_rows(tree, shift_idx, cfg: GossipConfig):
    """Ring exchange of a row-block tree: switch over static shifts, each
    branch one jnp.roll along the worker axis -> collective-permute."""
    branches = [
        (lambda t, s=s: jax.tree.map(lambda x: jnp.roll(x, s, axis=0), t))
        for s in cfg.shifts
    ]
    return jax.lax.switch(shift_idx, branches, tree)


# ---------------------------------------------------------------------------
# shared numeric pieces
# ---------------------------------------------------------------------------

def _per_worker_sq_dist(a, b, mask_tree=None, block_idx=None):
    """sum_{leaves, axes>0} (a-b)^2 -> (W,). In 'leaves' mode, only leaves
    whose static group id equals the traced block_idx contribute."""
    def leaf_d(x, y):
        return jnp.sum(
            (x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2,
            axis=tuple(range(1, x.ndim)))
    dists = jax.tree.map(leaf_d, a, b)
    if mask_tree is not None:
        dists = jax.tree.map(
            lambda d, gi: jnp.where(gi == block_idx, d, 0.0),
            dists, mask_tree)
    return sum(jax.tree.leaves(dists))


def _per_worker_reduce3(params, grads, ext, mask_tree=None, block_idx=None):
    """Fused gate reduction: all three eq.-(4) terms in ONE state traversal.

    Returns (dot, sq_dw, sq_ext), each (W,):
      dot    = <dw, w - ext>        sq_dw = ||dw||^2      sq_ext = ||ext||^2
    summed over non-worker axes.  Replaces the naive four traversals
    (stepped materialization + d_after + d_before + nonempty) with one,
    via the expanded identity d_before - d_after
      = 2*eps*<dw, w-ext> - eps^2*||dw||^2 — the SPMD analogue of pass 1 of
    the gossip_blend Pallas kernel.  In 'leaves' mode only leaves whose
    static group id equals the traced block_idx contribute (to every term,
    so the identity stays exact under the restriction).
    """
    wl = jax.tree.leaves(params)
    gl = jax.tree.leaves(grads)
    el = jax.tree.leaves(ext)
    ml = jax.tree.leaves(mask_tree) if mask_tree is not None \
        else [None] * len(wl)
    dot = sq_dw = sq_ext = 0.0
    for x, d, e, gi in zip(wl, gl, el, ml):
        axes = tuple(range(1, x.ndim))
        x32, d32, e32 = (t.astype(jnp.float32) for t in (x, d, e))
        t_dot = jnp.sum(d32 * (x32 - e32), axis=axes)
        t_dw = jnp.sum(d32 * d32, axis=axes)
        t_ext = jnp.sum(e32 * e32, axis=axes)
        if gi is not None:
            sel = (gi == block_idx)
            t_dot = jnp.where(sel, t_dot, 0.0)
            t_dw = jnp.where(sel, t_dw, 0.0)
            t_ext = jnp.where(sel, t_ext, 0.0)
        dot = dot + t_dot
        sq_dw = sq_dw + t_dw
        sq_ext = sq_ext + t_ext
    return dot, sq_dw, sq_ext


def _gossip_gate(params, grads, ext, acfg: ASGDConfig, mask_tree=None,
                 block_idx=None, *, single_sweep: bool = True):
    """Per-worker admission gate (eq. 3 x eq. 4) -> (W,) f32.

    The jnp reference path (ASGDConfig.use_fused=False — the kernel route
    never calls this).  single_sweep=True (default) uses the fused
    single-traversal reduction (_per_worker_reduce3); single_sweep=False
    keeps the original four-traversal form (ablation / bitwise reference,
    exercised in tests/test_gossip_blend.py).
    """
    if single_sweep:
        dot, sq_dw, sq_ext = _per_worker_reduce3(
            params, grads, ext, mask_tree, block_idx)
        return gate_from_terms(dot, sq_dw, sq_ext, acfg.eps,
                               use_parzen=acfg.use_parzen)

    stepped = jax.tree.map(
        lambda w, g: w.astype(jnp.float32) - acfg.eps * g.astype(jnp.float32),
        params, grads)
    d_after = _per_worker_sq_dist(stepped, ext, mask_tree, block_idx)
    d_before = _per_worker_sq_dist(params, ext, mask_tree, block_idx)
    zeros = jax.tree.map(jnp.zeros_like, ext)
    nonempty = (_per_worker_sq_dist(ext, zeros, mask_tree, block_idx) > 0.0)
    if acfg.use_parzen:
        return jnp.where((d_after < d_before) & nonempty, 1.0, 0.0)
    return nonempty.astype(jnp.float32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GossipState:
    """Carried between rounds (part of the train state pytree).

    buf: staleness buffer — the block received last round ('leaves' mode:
      full-tree shape, zeros outside the group; 'rows' mode: block tree).
    buf_idx: which partition index buf holds.
    step: round counter.
    buf_live: per-peer liveness of buf's worker rows, (W,) f32 0/1
      (DESIGN.md §8) — None unless the state was initialized with
      elastic=True.  Transient like buf_scales: checkpoints canonicalize
      it away (a restored state re-enters the join window at zeros).
    """

    buf: Any
    buf_idx: jnp.ndarray
    step: jnp.ndarray
    buf_live: Any = None

    def tree_flatten(self):
        return (self.buf, self.buf_idx, self.step, self.buf_live), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_gossip_state(params, cfg: GossipConfig,
                      elastic: bool = False) -> GossipState:
    """Zero staleness buffer in the CARRIER dtype.

    Paper eq. 3 reads an all-zero buffer as 'no message yet' — but the
    engines no longer rely on the gate's zero-detection for correctness on
    round 1: the explicit ``step == 0`` staleness guard
    (_apply_leaves/_apply_rows/asgd_gossip_apply_packed) closes every gate
    on the first delayed round regardless of the buffer's content.  The
    buffer stores carrier-dtype values post wire round-trip in every mode
    (wire_roundtrip), so delayed-buffer dtypes no longer differ between
    'leaves'/'rows'/packed engines.

    elastic=True additionally carries a buf_live peer-liveness mask
    (DESIGN.md §8), initialized to ZEROS: for a fresh start the step-based
    staleness guard closes the same first rounds anyway, and for an
    elastic restore the zeros ARE the join window — every peer's buffered
    payload reads as dropped until one full exchange completes on the new
    worker set."""
    if cfg.partial_mode == "rows":
        blk = slice_rows(params, jnp.int32(0), cfg.partial_blocks)
        buf = jax.tree.map(jnp.zeros_like, blk)
    else:
        buf = jax.tree.map(jnp.zeros_like, params)
    live = None
    if elastic:
        W = jax.tree.leaves(params)[0].shape[0]
        live = jnp.zeros((W,), jnp.float32)
    return GossipState(buf=buf, buf_idx=jnp.int32(0), step=jnp.int32(0),
                       buf_live=live)


def _blend(w_blk, ext_blk, g_blk, gate, acfg: ASGDConfig):
    """eq. (5)/(6) with N=1 applied to one (block) leaf.

    attraction = gate * (w - (w+ext)/2) = gate * (w-ext)/2
    paper:   w <- w - eps*(attraction + Delta_M)
    elastic: w <- (w - eps*Delta_M) - alpha*attraction
    """
    gexp = gate.reshape((-1,) + (1,) * (w_blk.ndim - 1))
    w32 = w_blk.astype(jnp.float32)
    attraction = gexp * 0.5 * (w32 - ext_blk.astype(jnp.float32))
    if acfg.elastic:
        out = (w32 - acfg.eps * g_blk.astype(jnp.float32)
               - acfg.elastic_alpha * attraction)
    else:
        out = w32 - acfg.eps * (attraction + g_blk.astype(jnp.float32))
    return out.astype(w_blk.dtype)


# ---------------------------------------------------------------------------
# the full SPMD ASGD round
# ---------------------------------------------------------------------------

def asgd_gossip_apply(params, grads, state: GossipState, key,
                      cfg: GossipConfig, acfg: ASGDConfig, live=None):
    """One SPMD ASGD round: local SGD step + gossip blend (paper eqs. 4-7).

    Args:
      params: pytree, every leaf (W, ...) with W sharded over data axes.
      grads:  matching pytree — local mini-batch steps Delta_M per group.
      state:  GossipState staleness buffer.
      key:    per-step PRNG key (shift + partition randomness).
      live:   optional (W,) f32 0/1 per-peer liveness (DESIGN.md §8);
        needs an elastic-initialized state.  Dead workers freeze (their
        Delta_M is masked), their payloads are dropped on the wire, and
        every gate touching a dead sender or receiver is closed.

    Returns (new_params, new_state, metrics); metrics carries the paper's
    'good messages' gate stats (Fig. 12).
    """
    W = jax.tree.leaves(params)[0].shape[0]
    live = _resolve_live(state.buf_live is not None, live, W,
                         "asgd_gossip_apply")
    if acfg.silent:
        new_params = jax.tree.map(
            lambda w, g: w - acfg.eps * g.astype(w.dtype), params,
            mask_live_tree(grads, live))
        state = GossipState(state.buf, state.buf_idx, state.step + 1,
                            state.buf_live)
        return new_params, state, {
            "gate": jnp.zeros((W,), jnp.float32), "n_good": jnp.float32(0.0)}

    p = cfg.partial_blocks
    k_shift, k_blk = jax.random.split(key)
    shift_idx = jax.random.randint(k_shift, (), 0, len(cfg.shifts))
    block_idx = jax.random.randint(k_blk, (), 0, p)

    apply = _apply_rows if cfg.partial_mode == "rows" else _apply_leaves

    if cfg.gossip_every <= 1:
        return apply(params, grads, state, shift_idx, block_idx, cfg, acfg,
                     live=live)

    # interval mode: skip communication entirely on off-steps (lax.cond —
    # XLA compiles the collective branch with static channel ids; only the
    # taken branch executes)
    def gossip_branch(args):
        params, grads, state = args
        return apply(params, grads, state, shift_idx, block_idx, cfg, acfg,
                     live=live)

    def silent_branch(args):
        params, grads, state = args
        new_params = jax.tree.map(
            lambda w, g: w - acfg.eps * g.astype(w.dtype), params,
            mask_live_tree(grads, live))
        new_state = GossipState(state.buf, state.buf_idx, state.step + 1,
                                state.buf_live)
        zero = jnp.zeros((W,), jnp.float32)
        return new_params, new_state, {"gate": zero,
                                       "n_good": jnp.float32(0.0)}

    return jax.lax.cond(
        state.step % cfg.gossip_every == 0,
        gossip_branch, silent_branch, (params, grads, state))


def staleness_valid(step, cfg: GossipConfig, *, extra: int = 0,
                    depth: int | None = None):
    """Warm-up staleness guard: with staleness depth D, the external
    blended on the first D rounds (step < D) is a zero-initialized
    placeholder slot, not a received block — gate it out explicitly
    (f32 0/1 multiplier on the admission gates) instead of relying on the
    Parzen gate's eq.-3 zero-detection, which conflates 'no message yet'
    with a legitimately all-zero (or garbage-restored) state.

    D defaults to ``cfg.delay + extra``; ``extra`` is the pipelined
    engines' mandatory in-flight round (DESIGN.md §7: the consumed
    payload was launched delay+1 rounds ago), and deeper unpipelined
    FIFOs (delay >= 2) are covered by the same ``step >= D`` condition.
    ``depth`` overrides D outright: engines whose buffer is SHALLOWER
    than cfg.delay claims must pass their real buffered depth — the
    single-slot pytree engines (and the single-slot reference/manual
    rounds) clamp to 1, else rounds that DID receive a real payload
    would be gated out.

    Interval gossip: buffer pushes happen only on gossip rounds (every
    ``gossip_every``-th step), so the D-th PUSH completes at step
    ``D * gossip_every`` — the guard threshold scales accordingly (a
    plain ``step >= D`` would declare the FIFO head real while it still
    holds an init placeholder).  Returns None when every external is
    valid (D == 0: the just-received block is always real).  The single
    source of the guard condition — shared by the pytree engines, the
    packed GSPMD engines, and the shard_map manual-region rounds
    (launch/mesh.py)."""
    if depth is None:
        depth = cfg.delay + extra
    if depth == 0:
        return None
    return (step >= depth * max(1, cfg.gossip_every)).astype(jnp.float32)


def _fused_blend(params, grads, ext, cfg, acfg, groups=None, ext_idx=None,
                 gate_scale=None):
    """Gate + blend through the worker-batched Pallas kernel (both modes).

    Pack-once dataflow (core/packing.py): the state trees are each
    ravelled to the (W_local, R, LANE) layout once per round and both
    kernel passes run on the packed arrays (the pack/unpack boundary adds
    copy sweeps until the packed ensemble is carried across rounds — the
    honest accounting is in EXPERIMENTS.md §Perf).  With groups/ext_idx
    given ('leaves' mode, partial_blocks > 1) the partial-update
    restriction enters as a single worker-shared (R, LANE) mask
    (pack_group_mask) instead of per-leaf jnp.where sweeps; 'rows' mode
    passes block trees and no mask (every position participates).  Under
    shard_map each shard sees its local worker slice — cfg.gate_psum_axes
    globalizes the gate accumulator when the non-worker dims are manually
    sharded too.

    Returns (blended_tree, gate (W_local,)).
    """
    from ..kernels.gossip_blend import gossip_blend_worker_batched
    from .packing import pack_group_mask, pack_spec_w, pack_w, unpack_w

    spec = pack_spec_w(params, block_rows=cfg.fused_block_rows)
    mask2 = (pack_group_mask(groups, ext_idx, spec)
             if groups is not None and cfg.partial_blocks > 1 else None)
    out3, gates = gossip_blend_worker_batched(
        pack_w(params, spec), pack_w(grads, spec),
        pack_w(ext, spec)[:, None],          # (W_local, P=1, R, LANE)
        acfg.eps, mask2d=mask2, use_parzen=acfg.use_parzen,
        elastic=acfg.elastic, elastic_alpha=acfg.elastic_alpha,
        block_rows=spec.block_rows, psum_axes=cfg.gate_psum_axes or None,
        gate_scale=gate_scale)
    return unpack_w(out3, spec), gates[:, 0]


def _apply_leaves(params, grads, state, shift_idx, block_idx, cfg, acfg,
                  live=None):
    groups = leaf_groups(params, cfg.partial_blocks)
    sent = exchange_leaves(params, groups, shift_idx, block_idx, cfg)
    sent_live = None
    if live is not None:
        # drop dead payloads on the wire (eq. 3: all-zero == no message)
        # and freeze dead workers' local steps
        sent_live = roll_live(live, shift_idx, cfg)
        sent = mask_live_tree(sent, sent_live)
        grads = mask_live_tree(grads, live)

    if cfg.delay == 0:
        ext, ext_idx, valid = sent, block_idx, None
        ext_live = sent_live
    else:
        # single-slot buffer: the effective staleness is 1 round whatever
        # cfg.delay claims, so the guard clamps to depth 1 (delay >= 2
        # FIFOs exist only on the packed engines)
        ext, ext_idx = state.buf, state.buf_idx
        valid = staleness_valid(state.step, cfg, depth=1)
        ext_live = state.buf_live
    gate_scale = combine_gate_scale(valid, ext_live, live)

    if acfg.use_fused:
        new_params, gate = _fused_blend(
            params, grads, ext, cfg, acfg, groups, ext_idx,
            gate_scale=gate_scale)
    else:
        # Parzen gate (eq. 4) restricted to the buffered partition's leaves
        gate = _gossip_gate(params, grads, ext, acfg, groups, ext_idx)
        if gate_scale is not None:
            gate = gate * gate_scale

        def upd(w, g, e, gi):
            in_group = (gi == ext_idx)  # traced bool scalar, static group id
            blended = _blend(w, e, g, gate, acfg)
            plain = (w.astype(jnp.float32)
                     - acfg.eps * g.astype(jnp.float32)).astype(w.dtype)
            return jnp.where(in_group, blended, plain)

        new_params = jax.tree.map(upd, params, grads, ext, groups)
    new_state = GossipState(buf=sent, buf_idx=block_idx,
                            step=state.step + 1, buf_live=sent_live)
    return new_params, new_state, {"gate": gate, "n_good": jnp.sum(gate)}


def _apply_rows(params, grads, state, shift_idx, block_idx, cfg, acfg,
                live=None):
    p = cfg.partial_blocks
    my_block = slice_rows(params, block_idx, p)
    # sender-side wire round-trip BEFORE the roll — same site semantics as
    # 'leaves' mode (_roll_group), so the staleness buffer stores
    # carrier-dtype round-tripped values in both modes
    sent = exchange_rows(wire_roundtrip(my_block, cfg), shift_idx, cfg)
    sent_live = None
    if live is not None:
        sent_live = roll_live(live, shift_idx, cfg)
        sent = mask_live_tree(sent, sent_live)
        grads = mask_live_tree(grads, live)

    if cfg.delay == 0:
        ext, ext_idx, valid = sent, block_idx, None
        ext_live = sent_live
    else:
        # single-slot buffer -> guard depth 1 (see _apply_leaves)
        ext, ext_idx = state.buf, state.buf_idx
        valid = staleness_valid(state.step, cfg, depth=1)
        ext_live = state.buf_live
    gate_scale = combine_gate_scale(valid, ext_live, live)

    local_blk = slice_rows(params, ext_idx, p)
    grads_blk = slice_rows(grads, ext_idx, p)
    if acfg.use_fused:
        blended, gate = _fused_blend(local_blk, grads_blk, ext, cfg, acfg,
                                     gate_scale=gate_scale)
    else:
        gate = _gossip_gate(local_blk, grads_blk, ext, acfg)
        if gate_scale is not None:
            gate = gate * gate_scale
        blended = jax.tree.map(
            lambda w, e, g: _blend(w, e, g, gate, acfg),
            local_blk, ext, grads_blk)
    new_params = jax.tree.map(
        lambda w, g: w - acfg.eps * g.astype(w.dtype), params, grads)
    new_params = update_rows(new_params, blended, ext_idx, p)
    new_state = GossipState(buf=sent, buf_idx=block_idx,
                            step=state.step + 1, buf_live=sent_live)
    return new_params, new_state, {"gate": gate, "n_good": jnp.sum(gate)}


# ---------------------------------------------------------------------------
# packed-resident rounds: the (W, R, LANE) ensemble is the carried training
# representation (DESIGN.md §6) — exchange AND blend run on packed rows,
# unpack_w happens only at eval/checkpoint boundaries
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedGossipState:
    """Carried between packed-resident rounds.

    buf: staleness buffer as packed rows — the (W, R, LANE) array received
      last round, zeros outside the exchanged partition's row range (the
      packed analogue of GossipState.buf in 'leaves' mode).  Carrier f32
      normally; int8 under wire_format="int8" (the received block stays
      QUANTIZED until the kernel dequantizes it in-register — it never
      materializes in float in HBM).  With a staleness FIFO deeper than
      one slot (delay >= 2, or any pipelined engine state — DESIGN.md §7)
      buf is stacked (D, W, R, LANE), oldest payload first.
    buf_scales: per-block_rows f32 dequantization scales
      (W, R // block_rows) matching buf when wire_format="int8"
      ((D, W, R // block_rows) stacked); None otherwise.  Transient —
      never checkpointed (checkpoint/ canonicalizes buf to the
      dequantized pytree layout).
    buf_idx: which partition index buf holds ((D,) stacked).
    step: round counter.
    buf_live: per-peer liveness of each buffered payload's worker rows,
      (W,) f32 0/1 ((D, W) stacked, aligned with buf) — None unless the
      state was initialized with elastic=True (DESIGN.md §8).  Transient
      like buf_scales: a restored state re-enters the join window at
      zeros.
    """

    buf: Any
    buf_idx: jnp.ndarray
    step: jnp.ndarray
    buf_scales: Any = None
    buf_live: Any = None

    def tree_flatten(self):
        return (self.buf, self.buf_idx, self.step, self.buf_scales,
                self.buf_live), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def fifo_depth(cfg: GossipConfig, *, pipelined: bool = False) -> int:
    """Staleness-FIFO depth of the packed engines (static).

    Unpipelined: the engine carries the last ``delay`` launched payloads
    (one unstacked slot historically; delay >= 2 stacks them).  Pipelined
    (DESIGN.md §7): one extra slot for the mandatory in-flight round —
    the consumed payload was launched ``delay + 1`` rounds ago.  Depth 1
    keeps the exact single-slot PackedGossipState layout of PR 3/4;
    deeper FIFOs stack a leading depth axis on buf/buf_scales/buf_idx."""
    return max(1, cfg.delay + (1 if pipelined else 0))


def init_packed_gossip_state(packed, cfg: GossipConfig | None = None,
                             block_rows: int | None = None,
                             depth: int | None = None,
                             elastic: bool = False
                             ) -> PackedGossipState:
    """Zero packed staleness buffer (paper eq. 3: all-zero == 'no message
    yet' — exact on packed rows: padding is zero too; the first ``depth``
    rounds are additionally gated by the explicit step-based staleness
    guard in the engines).  With cfg resolving to wire_format="int8"
    (pass the spec's block_rows too) the buffer is int8 zeros plus zero
    scales — the quantized form of 'no message'.

    depth: staleness-FIFO slots (default ``fifo_depth(cfg)``): 1 keeps
    the single-slot layout; >= 2 stacks buf (D, W, R, LANE),
    buf_idx (D,), buf_scales (D, W, nb) — oldest payload first.

    elastic=True carries the buf_live peer-liveness mask (DESIGN.md §8),
    zero-initialized: every buffered slot reads as dropped until real
    exchanges refill the FIFO — the join window of a fresh start or an
    elastic restore onto a new worker count."""
    if depth is None:
        depth = fifo_depth(cfg) if cfg is not None else 1
    lead = () if depth == 1 else (depth,)
    idx = jnp.zeros(lead, jnp.int32) if lead else jnp.int32(0)
    live = (jnp.zeros(lead + (packed.shape[0],), jnp.float32)
            if elastic else None)
    if cfg is not None and resolved_wire_format(cfg) == "int8":
        if block_rows is None:
            raise ValueError(
                'init_packed_gossip_state: wire_format="int8" needs '
                "block_rows (spec.block_rows)")
        from .packing import scale_blocks
        nb = scale_blocks(packed.shape[1], block_rows)
        return PackedGossipState(
            buf=jnp.zeros(lead + packed.shape, jnp.int8),
            buf_scales=jnp.zeros(lead + (packed.shape[0], nb), jnp.float32),
            buf_idx=idx, step=jnp.int32(0), buf_live=live)
    return PackedGossipState(buf=jnp.zeros(lead + packed.shape,
                                           packed.dtype),
                             buf_idx=idx, step=jnp.int32(0), buf_live=live)


def init_pipelined_gossip_state(packed, cfg: GossipConfig,
                                block_rows: int | None = None,
                                elastic: bool = False
                                ) -> PackedGossipState:
    """Staleness FIFO for the pipelined engine (DESIGN.md §7): depth
    ``cfg.delay + 1`` — the in-flight payload plus ``delay`` buffered
    rounds."""
    return init_packed_gossip_state(
        packed, cfg, block_rows=block_rows,
        depth=fifo_depth(cfg, pipelined=True), elastic=elastic)


def _fifo_head(state: PackedGossipState, stacked: bool):
    """(ext, ext_scales, ext_idx, ext_live) — the OLDEST buffered
    payload."""
    if not stacked:
        return state.buf, state.buf_scales, state.buf_idx, state.buf_live
    scales = None if state.buf_scales is None else state.buf_scales[0]
    live = None if state.buf_live is None else state.buf_live[0]
    return state.buf[0], scales, state.buf_idx[0], live


def _silent_round(packed, pgrads, state: PackedGossipState, step_lr,
                  live=None):
    """Shared silent-round body of the packed engines (ASGDConfig.silent
    and the gossip_every off-rounds): plain local SGD step, buffers
    untouched, step bumped, zero gate metrics — ONE implementation so the
    engines the parity tests compare cannot drift.  ``live`` masks the
    local steps of dead workers (they freeze through silent rounds too)."""
    new_state = PackedGossipState(buf=state.buf, buf_scales=state.buf_scales,
                                  buf_idx=state.buf_idx, step=state.step + 1,
                                  buf_live=state.buf_live)
    zero = jnp.zeros((packed.shape[0],), jnp.float32)
    return packed - step_lr * mask_live_rows(pgrads, live), new_state, {
        "gate": zero, "n_good": jnp.float32(0.0)}


def _fifo_push(state: PackedGossipState, sent, sent_scales, block_idx,
               stacked: bool, sent_live=None) -> PackedGossipState:
    """Drop the oldest payload, append the just-launched one, bump step."""
    if not stacked:
        return PackedGossipState(buf=sent, buf_scales=sent_scales,
                                 buf_idx=block_idx, step=state.step + 1,
                                 buf_live=sent_live)
    buf = jnp.concatenate([state.buf[1:], sent[None]], axis=0)
    idx = jnp.concatenate(
        [state.buf_idx[1:], jnp.asarray(block_idx, jnp.int32)[None]])
    scales = None
    if sent_scales is not None:
        scales = jnp.concatenate([state.buf_scales[1:], sent_scales[None]],
                                 axis=0)
    live = None
    if sent_live is not None:
        live = jnp.concatenate([state.buf_live[1:], sent_live[None]],
                               axis=0)
    return PackedGossipState(buf=buf, buf_scales=scales, buf_idx=idx,
                             step=state.step + 1, buf_live=live)


def packed_row_ranges(spec, cfg: GossipConfig) -> tuple:
    """Static (row_start, row_end) per partition index on the packed layout.

    'leaves' mode reads the group-contiguous ``group_row_ranges`` table the
    spec was built with (pack_spec_w(groups=leaf_groups(...))); 'rows' mode
    partitions the packed rows themselves into p contiguous chunks — the
    packed-space analogue of slicing "along the individual cluster centers"
    (any contiguous 1/p of the flat state is a valid paper §4.4 partition).

    Under wire_format="int8" ONLY, 'rows'-mode chunks are rounded up to a
    block_rows multiple so the per-block_rows quantization scales never
    straddle a partition boundary (the float kernel's row mask handles
    unaligned ranges fine, so other formats keep the exact 1/p split).
    A config whose alignment would leave empty partitions — rows <
    p * block_rows, i.e. 1/p of a round's exchanges silently shipping the
    whole state and the rest nothing — raises instead.
    """
    p = cfg.partial_blocks
    if cfg.partial_mode == "leaves":
        if spec.group_row_ranges is None:
            raise ValueError(
                "packed 'leaves' mode needs a group-contiguous spec: "
                "pack_spec_w(tree, groups=leaf_groups(tree, p), n_groups=p)")
        if len(spec.group_row_ranges) != p:
            raise ValueError(
                f"spec has {len(spec.group_row_ranges)} group ranges, "
                f"cfg.partial_blocks={p}")
        return spec.group_row_ranges
    if resolved_wire_format(cfg) == "int8":
        br = spec.block_rows
        if spec.rows < p * br:
            raise ValueError(
                f"wire_format='int8' 'rows' partitioning is unsatisfiable: "
                f"rows={spec.rows} < partial_blocks={p} * block_rows={br} "
                f"cannot give every partition a non-empty block-aligned "
                f"range — lower block_rows (pack_spec_w) or partial_blocks")

        def bound(g):  # proportional boundary, snapped to block_rows
            return min(int(round(g * spec.rows / p / br)) * br, spec.rows)

        # rows >= p*br guarantees every rounded range is non-empty
        return tuple((bound(g), bound(g + 1)) for g in range(p))
    chunk = -(-spec.rows // p)
    return tuple((min(g * chunk, spec.rows), min((g + 1) * chunk, spec.rows))
                 for g in range(p))


def _roll_packed_rows(packed, r0: int, r1: int, shift: int,
                      cfg: GossipConfig):
    """Branch body: roll rows [r0, r1) of the packed ensemble by ``shift``
    along the worker axis (-> ONE collective-permute of |w|/p bytes); all
    other rows are local zeros — they were never sent.  The wire round-trip
    (wire_roundtrip — None or "dtype" formats; "int8" takes the genuinely
    quantized _roll_packed_rows_q path) applies to the sliced block only."""
    blk = wire_roundtrip(packed[:, r0:r1], cfg)
    rolled = jnp.roll(blk, shift, axis=0)
    return jnp.zeros_like(packed).at[:, r0:r1].set(rolled)


def quantized_exchange_body(packed, r0: int, r1: int, block_rows: int,
                            roll):
    """int8-wire branch body, shared by the GSPMD roll and the
    manual-region ppermute (launch/mesh.py): quantize rows [r0, r1), roll
    the int8 payload and its per-block_rows scales along the worker axis
    with ``roll`` (wire bytes (r1-r0)·LANE·1 + 4·(r1-r0)/block_rows ≈
    |w|/(4p)), scatter both into full-size zero buffers.  Returns
    (q (W, R, LANE) int8, scales (W, R // block_rows) f32) — the quantized
    staleness buffer.  One body for both transports so the scale tiling /
    scatter indexing can never drift between them."""
    from .packing import quantize_rows, scale_blocks
    wn, rows = packed.shape[0], packed.shape[1]
    nb = scale_blocks(rows, block_rows)
    q, s = quantize_rows(packed[:, r0:r1], block_rows)
    q, s = roll(q), roll(s)
    full_q = jnp.zeros(packed.shape, jnp.int8).at[:, r0:r1].set(q)
    full_s = jnp.zeros((wn, nb), jnp.float32) \
        .at[:, r0 // block_rows:r1 // block_rows].set(s)
    return full_q, full_s


def _roll_packed_rows_q(packed, r0: int, r1: int, shift: int,
                        block_rows: int):
    return quantized_exchange_body(
        packed, r0, r1, block_rows, lambda x: jnp.roll(x, shift, axis=0))


def exchange_packed(packed, ranges, shift_idx, block_idx, cfg: GossipConfig,
                    block_rows: int | None = None):
    """lax.switch over (shift, partition) static pairs on packed rows.

    Every branch slices a STATIC row range (the partition index is static
    inside its branch), so the exchange moves exactly (r1-r0)·LANE·4 ≈
    |w|/p bytes — or |w|/(4p) + scales under wire_format="int8", where the
    return value is the (q, scales) pair instead of a float block (pass the
    spec's block_rows) — and never re-lays-out the resident ensemble."""
    wire = resolved_wire_format(cfg)
    if wire == "int8" and block_rows is None:
        raise ValueError(
            'exchange_packed: wire_format="int8" needs block_rows '
            "(spec.block_rows)")
    branches = []
    for s in cfg.shifts:
        for g in range(cfg.partial_blocks):
            r0, r1 = ranges[g]
            if wire == "int8":
                branches.append(
                    lambda t, s=s, r0=r0, r1=r1: _roll_packed_rows_q(
                        t, r0, r1, s, block_rows))
            else:
                branches.append(
                    lambda t, s=s, r0=r0, r1=r1: _roll_packed_rows(
                        t, r0, r1, s, cfg))
    idx = shift_idx * cfg.partial_blocks + block_idx
    return jax.lax.switch(idx, branches, packed)


def _resident_blend(packed, pgrads, ext, ext_scales, ext_idx, ranges, lr,
                    gate_scale, cfg: GossipConfig, acfg: ASGDConfig, spec):
    """The resident-kernel blend of both packed engines: one received slot
    ``ext`` (W, R, LANE) — int8 with ``ext_scales`` (W, nb) on the int8
    wire — blended over partition ``ext_idx``'s row range.  Returns
    (new_packed, gate (W,)).

    Under an ambient mesh whose data axes shard the worker axis
    (launch/mesh.py ambient_worker_mesh) the kernel runs per shard inside
    shard_map_workers: GSPMD cannot partition a Mosaic kernel, and the
    blend is communication-free per worker.  ``gate_scale`` is then
    broadcast to (W,) so it splits with the workers (the same product
    per gate)."""
    from ..kernels.gossip_blend import gossip_blend_w_resident
    from ..launch.mesh import ambient_worker_mesh, shard_map_workers

    row_range = jnp.asarray(ranges, jnp.int32)[ext_idx]

    def blend(packed, pgrads, ext, row_range, lr, gate_scale, *scales):
        new_packed, gates = gossip_blend_w_resident(
            packed, pgrads, ext[:, None], row_range, acfg.eps, lr=lr,
            ext_scales=scales[0][:, None] if scales else None,
            use_parzen=acfg.use_parzen, elastic=acfg.elastic,
            elastic_alpha=acfg.elastic_alpha, block_rows=spec.block_rows,
            psum_axes=cfg.gate_psum_axes or None, gate_scale=gate_scale)
        return new_packed, gates[:, 0]

    scales = () if ext_scales is None else (ext_scales,)
    mesh = ambient_worker_mesh()
    if mesh is None:
        return blend(packed, pgrads, ext, row_range, lr, gate_scale, *scales)
    lr = jnp.asarray(acfg.eps if lr is None else lr, jnp.float32)
    gate_scale = jnp.broadcast_to(
        jnp.float32(1.0) if gate_scale is None else gate_scale,
        (packed.shape[0],))
    return shard_map_workers(blend, mesh, replicated_argnums=(3, 4))(
        packed, pgrads, ext, row_range, lr, gate_scale, *scales)


def asgd_gossip_apply_packed(packed, pgrads, state: PackedGossipState, key,
                             cfg: GossipConfig, acfg: ASGDConfig, spec,
                             live=None):
    """One packed-resident SPMD ASGD round (paper eqs. 4-7).

    The packed ``(W, R, LANE)`` ensemble (core/packing.py pack_w on a
    group-contiguous spec) is the carried representation: the partial
    exchange is a static slice of packed rows -> jnp.roll ->
    collective-permute, the staleness buffer is packed rows, and the blend
    runs the row-range resident kernel (gossip_blend_w_resident) — no
    pack/unpack inside the round, no materialized partition mask.  Sweep
    accounting: 2 kernel passes reading w+dw+ext (7 byte units) vs 18 for
    the per-round pack/unpack wiring (EXPERIMENTS.md §Perf).

    With wire_format="int8" the exchanged slice travels (and is buffered)
    as int8 + per-block_rows f32 scales; both kernel passes dequantize
    in-register, so the external never exists in float in HBM and the
    collective moves |w|/(4p) bytes.  The first ``delay`` rounds are
    closed by the explicit step-based staleness guard (the init buffer
    slots are placeholders, not received blocks).  delay >= 2 carries a
    stacked payload FIFO (init_packed_gossip_state depth) and blends the
    payload launched ``delay`` rounds ago — deeper paper-tolerated
    staleness, and the parity oracle for the pipelined engine run at
    ``delay - 1`` (DESIGN.md §7).

    Args:
      packed: (W, R, LANE) f32 resident ensemble.
      pgrads: (W, R, LANE) packed local steps Delta_M (pack_w of grads —
        the one remaining pack per round; grads are born as a pytree).
      state: PackedGossipState staleness buffer.
      key:   per-step PRNG key — same draw structure as asgd_gossip_apply,
        so a packed run follows the identical gossip schedule.
      spec:  the WPackSpec the ensemble was packed with (static).
      live:  optional (W,) f32 0/1 per-peer liveness (DESIGN.md §8);
        needs an elastic-initialized state.

    Returns (new_packed, new_state, metrics) with the same metrics contract
    as asgd_gossip_apply.
    """
    live = _resolve_live(state.buf_live is not None, live, packed.shape[0],
                         "asgd_gossip_apply_packed")
    if acfg.silent:
        return _silent_round(packed, pgrads, state, acfg.eps, live=live)

    p = cfg.partial_blocks
    wire = resolved_wire_format(cfg)
    stacked = fifo_depth(cfg) >= 2
    k_shift, k_blk = jax.random.split(key)
    shift_idx = jax.random.randint(k_shift, (), 0, len(cfg.shifts))
    block_idx = jax.random.randint(k_blk, (), 0, p)
    ranges = packed_row_ranges(spec, cfg)

    def gossip_branch(args):
        packed, pgrads, state = args
        if wire == "int8":
            sent, sent_scales = exchange_packed(
                packed, ranges, shift_idx, block_idx, cfg,
                block_rows=spec.block_rows)
        else:
            sent = exchange_packed(packed, ranges, shift_idx, block_idx,
                                   cfg)
            sent_scales = None
        sent_live = None
        if live is not None:
            sent_live = roll_live(live, shift_idx, cfg)
            sent = mask_live_rows(sent, sent_live)
            if sent_scales is not None:
                sent_scales = mask_live_rows(sent_scales, sent_live)
            pgrads = mask_live_rows(pgrads, live)
        if cfg.delay == 0:
            ext, ext_scales, ext_idx = sent, sent_scales, block_idx
            valid, ext_live = None, sent_live
        else:
            # delay >= 2 pops the FIFO head (the payload launched ``delay``
            # rounds ago); delay == 1 keeps the historical single slot
            ext, ext_scales, ext_idx, ext_live = _fifo_head(state, stacked)
            valid = staleness_valid(state.step, cfg)
        new_packed, gate = _resident_blend(
            packed, pgrads, ext, ext_scales, ext_idx, ranges, None,
            combine_gate_scale(valid, ext_live, live), cfg, acfg, spec)
        new_state = _fifo_push(state, sent, sent_scales, block_idx,
                               stacked, sent_live=sent_live)
        return new_packed, new_state, {"gate": gate,
                                       "n_good": jnp.sum(gate)}

    if cfg.gossip_every <= 1:
        return gossip_branch((packed, pgrads, state))

    def silent_branch(args):
        packed, pgrads, state = args
        return _silent_round(packed, pgrads, state, acfg.eps, live=live)

    return jax.lax.cond(
        state.step % cfg.gossip_every == 0,
        gossip_branch, silent_branch, (packed, pgrads, state))


# ---------------------------------------------------------------------------
# pipelined rounds (DESIGN.md §7): the exchange is split off the blend —
# round t LAUNCHES its payload from the pre-blend ensemble (the collective
# overlaps the forward/backward) and BLENDS the payload launched delay+1
# rounds ago (the FIFO head).  Bit-identical to the unpipelined engine run
# at delay+1: same key schedule, same exchange, same kernel.
# ---------------------------------------------------------------------------

def initiate_exchange_packed(packed, key, cfg: GossipConfig, spec,
                             live=None):
    """The INITIATE half of the pipelined round: draw this round's
    (shift, partition) pair and launch the payload from the CURRENT
    (pre-blend) ensemble.

    ``packed`` is the train-step program's input, so the ppermute this
    lowers to depends on nothing computed this round — issued before the
    forward/backward (launch/steps.py pipelined step), the collective runs
    concurrently with the compute and its product is consumed only by the
    NEXT round's blend.  Returns (sent, sent_scales, block_idx);
    sent_scales is None except under wire_format="int8".  With ``live``
    given (elastic mode, DESIGN.md §8) the payload rows of dead senders/
    receivers are dropped on the wire and a fourth element ``sent_live``
    (W,) records the launch-time validity for the consume half."""
    k_shift, k_blk = jax.random.split(key)
    shift_idx = jax.random.randint(k_shift, (), 0, len(cfg.shifts))
    block_idx = jax.random.randint(k_blk, (), 0, cfg.partial_blocks)
    ranges = packed_row_ranges(spec, cfg)
    if resolved_wire_format(cfg) == "int8":
        sent, sent_scales = exchange_packed(
            packed, ranges, shift_idx, block_idx, cfg,
            block_rows=spec.block_rows)
    else:
        sent = exchange_packed(packed, ranges, shift_idx, block_idx, cfg)
        sent_scales = None
    if live is None:
        return sent, sent_scales, block_idx
    sent_live = roll_live(jnp.asarray(live, jnp.float32), shift_idx, cfg)
    sent = mask_live_rows(sent, sent_live)
    if sent_scales is not None:
        sent_scales = mask_live_rows(sent_scales, sent_live)
    return sent, sent_scales, block_idx, sent_live


def consume_exchange_packed(packed, pgrads, state: PackedGossipState, sent,
                            sent_scales, block_idx, cfg: GossipConfig,
                            acfg: ASGDConfig, spec, lr=None,
                            sent_live=None, live=None):
    """The CONSUME half of the pipelined round: blend the FIFO head — the
    payload launched ``cfg.delay + 1`` rounds ago — with the eq.-1 local
    update fused in-register (the resident kernel's runtime ``lr``
    operand, default acfg.eps), then push the just-launched payload.

    The blend never touches ``sent`` (this round's launch), so the
    collective that produced it sits entirely off the blend's critical
    path.  The first delay+1 rounds blend placeholder slots and are closed
    by the staleness guard (staleness_valid extra=1).  In elastic mode
    ``sent_live`` is the launch-time validity from initiate_exchange_packed
    (defaults to all-alive on an elastic state) and ``live`` this round's
    liveness; the FIFO head's recorded validity and the current mask both
    close the gates.  Returns (new_packed, new_state, metrics) with the
    engine metrics contract."""
    live = _resolve_live(state.buf_live is not None, live, packed.shape[0],
                         "consume_exchange_packed")
    if live is not None:
        if sent_live is None:
            sent_live = jnp.ones((packed.shape[0],), jnp.float32)
        pgrads = mask_live_rows(pgrads, live)
    stacked = fifo_depth(cfg, pipelined=True) >= 2
    ext, ext_scales, ext_idx, ext_live = _fifo_head(state, stacked)
    valid = staleness_valid(state.step, cfg, extra=1)
    new_packed, gate = _resident_blend(
        packed, pgrads, ext, ext_scales, ext_idx,
        packed_row_ranges(spec, cfg), lr,
        combine_gate_scale(valid, ext_live, live), cfg, acfg, spec)
    new_state = _fifo_push(state, sent, sent_scales, block_idx, stacked,
                           sent_live=sent_live)
    return new_packed, new_state, {"gate": gate, "n_good": jnp.sum(gate)}


def asgd_gossip_apply_pipelined(packed, pgrads, state: PackedGossipState,
                                key, cfg: GossipConfig, acfg: ASGDConfig,
                                spec, lr=None, live=None):
    """One PIPELINED packed-resident ASGD round (DESIGN.md §7).

    initiate_exchange_packed + consume_exchange_packed composed — the
    in-jit GSPMD formulation of the pipelined round, for callers without
    a model in the loop (tests, benchmarks, the manual-region parity
    suite).  The train step (launch/steps.py make_train_step(
    pipelined=True)) calls the two halves around the forward/backward
    instead, so the payload collective overlaps the compute.

    Effective staleness is ``cfg.delay + 1`` (the mandatory in-flight
    round plus cfg.delay buffered rounds): bit-identical to
    asgd_gossip_apply_packed run at ``delay + 1`` on the same key
    schedule (the acceptance driver is
    kernels/gossip_blend/ref.py run_pipelined_parity).  ``state`` comes
    from init_pipelined_gossip_state.  ``lr`` optionally overrides the
    fused eq.-1 step size (a traced schedule value; the Parzen gate keeps
    acfg.eps).  ``live`` is the per-peer liveness mask (DESIGN.md §8;
    needs an elastic-initialized state).
    """
    step_lr = acfg.eps if lr is None else lr
    live = _resolve_live(state.buf_live is not None, live, packed.shape[0],
                         "asgd_gossip_apply_pipelined")
    if acfg.silent:
        return _silent_round(packed, pgrads, state, step_lr, live=live)

    def gossip_branch(args):
        packed, pgrads, state = args
        if live is None:
            sent, sent_scales, block_idx = initiate_exchange_packed(
                packed, key, cfg, spec)
            sent_live = None
        else:
            sent, sent_scales, block_idx, sent_live = \
                initiate_exchange_packed(packed, key, cfg, spec, live=live)
        return consume_exchange_packed(packed, pgrads, state, sent,
                                       sent_scales, block_idx, cfg, acfg,
                                       spec, lr=lr, sent_live=sent_live,
                                       live=live)

    if cfg.gossip_every <= 1:
        return gossip_branch((packed, pgrads, state))

    def silent_branch(args):
        packed, pgrads, state = args
        return _silent_round(packed, pgrads, state, step_lr, live=live)

    return jax.lax.cond(
        state.step % cfg.gossip_every == 0,
        gossip_branch, silent_branch, (packed, pgrads, state))


# ---------------------------------------------------------------------------
# baseline steps in the same W-leading-axis formulation (for the roofline
# comparison: BATCH all-reduces |w| bytes, SimuParallel communicates zero)
# ---------------------------------------------------------------------------

def sync_dp_apply(params, grads, eps):
    """Synchronous data-parallel SGD (the BATCH/MapReduce analogue):
    grads are averaged over the worker axis -> XLA all-reduce."""
    gmean = jax.tree.map(
        lambda g: jnp.broadcast_to(jnp.mean(g, axis=0, keepdims=True),
                                   g.shape),
        grads)
    return jax.tree.map(lambda w, g: w - eps * g.astype(w.dtype),
                        params, gmean)


def local_sgd_apply(params, grads, eps):
    """SimuParallelSGD inner step: purely local, zero communication."""
    return jax.tree.map(lambda w, g: w - eps * g.astype(w.dtype),
                        params, grads)


def final_average(params):
    """SimuParallelSGD final aggregation (alg. 3 line 9) / ASGD optional
    MapReduce aggregate (paper §4.3, Figs. 16/17)."""
    return jax.tree.map(
        lambda w: jnp.broadcast_to(jnp.mean(w, axis=0, keepdims=True),
                                   w.shape),
        params)

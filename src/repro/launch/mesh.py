"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model) — 16 ASGD worker
groups, each 16-way tensor-parallel. Multi-pod: (2, 16, 16) = 512 chips,
axes (pod, data, model) — the pod axis extends the ASGD worker set to 32
groups; gossip ppermutes run over the combined (pod, data) super-axis so a
shift can cross the DCI (see core/gossip.py + DESIGN.md §5).

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run forces 512 host devices before first init;
tests and benches see the single real device).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 2, *, devices=None):
    """(data, model) mesh over the host's devices (``devices``, default
    ``jax.devices()``): the trainer's (device count, 1) worker mesh, and
    the small meshes of the tests and the smoke dry-run."""
    devices = jax.devices() if devices is None else list(devices)
    data = min(data, max(1, len(devices) // model))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=devices[:data * model])


def data_axes(mesh) -> tuple:
    """The axes the ASGD worker dimension is sharded over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_worker_groups(mesh) -> int:
    return math.prod(mesh.shape[a] for a in data_axes(mesh))


def ambient_worker_mesh():
    """The ambient mesh (``jax.set_mesh``) when its data axes split the
    worker axis over more than one device, else None — also None inside a
    shard_map region whose data axes are already manual.  A Mosaic kernel
    cannot be partitioned by GSPMD, so the packed engines run their kernel
    through :func:`shard_map_workers` on this mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    wa = data_axes(mesh)
    if not wa or any(a in mesh.manual_axes for a in wa):
        return None
    return mesh if n_worker_groups(mesh) > 1 else None


def local_worker_count(mesh, n_workers: int | None = None) -> int:
    """Worker replicas resident on ONE shard of the data axes.

    The fused gossip blend (core/gossip.py, ASGDConfig.use_fused) batches
    the Pallas kernel over exactly this many replicas per shard: the
    leading worker axis W divided by the number of data shards.  W defaults
    to n_worker_groups(mesh) (the production configuration, W_local == 1);
    oversubscribed runs (W a multiple of the group count) get W_local > 1.
    """
    groups = n_worker_groups(mesh)
    n = groups if n_workers is None else n_workers
    if n % groups:
        raise ValueError(
            f"worker count {n} does not divide over {groups} data shards")
    return n // groups


def shard_map_workers(fn, mesh, *, replicated_argnums=()):
    """shard_map ``fn`` over the mesh's data axes, worker-axis split only.

    The production wiring for the worker-batched fused gossip blend
    (DESIGN.md §2.2): every argument and output is split along its leading
    worker axis across (pod+)data and replicated over `model`, so inside
    ``fn`` each shard sees its local (W_local, ...) worker slice and the
    Pallas kernel (kernels/gossip_blend ``*_w_pallas``) runs per shard with
    no re-layout.  Arguments that are worker-SHARED rather than
    worker-leading — e.g. the (R, LANE) 'leaves'-mode partition mask, whose
    axis 0 is the packed row dim, not workers — must be named in
    ``replicated_argnums`` so every shard receives the full array instead
    of a wrong-axis split.

    The peer exchange stays OUTSIDE this wrapper (the GSPMD jnp.roll ->
    collective-permute of core/gossip.py) — ``fn`` must be
    communication-free per worker, which the blend is: the only cross-shard
    term is the (W_local, P, 3) gate accumulator, and that psum is needed
    only when the non-worker dims are ALSO manually sharded
    (GossipConfig.gate_psum_axes).

    check_vma is disabled: pallas_call inside shard_map defeats jax's
    replication inference.
    """
    wa = data_axes(mesh)
    if not wa:
        raise ValueError(
            f"mesh has no data axes (axis_names={mesh.axis_names}); the "
            "ASGD worker dimension shards over 'pod'/'data'")
    split = jax.sharding.PartitionSpec(wa if len(wa) > 1 else wa[0])
    rep = jax.sharding.PartitionSpec()
    repl = frozenset(replicated_argnums)

    def wrapped(*args):
        in_specs = tuple(rep if i in repl else split
                         for i in range(len(args)))
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=split, check_vma=False)(*args)
    return wrapped


# ---------------------------------------------------------------------------
# manual-axis gossip: exchange (ppermute) + blend (kernel) in ONE region
# ---------------------------------------------------------------------------

def _ppermute_shift(x, axis_name, n_shards: int, shift: int):
    """ppermute ``x`` forward by ``shift`` shards (jnp.roll semantics:
    shard i's data lands on shard (i + shift) % n)."""
    import jax.lax as lax
    perm = [(i, (i + shift) % n_shards) for i in range(n_shards)]
    return lax.ppermute(x, axis_name, perm)


def _roll_workers_manual(x, shift: int, axis_name, n_shards: int,
                         w_local: int):
    """Global jnp.roll(·, shift, axis=0) over the worker axis, expressed
    inside the manual region: each shard holds ``w_local`` contiguous
    workers of the (n_shards * w_local)-ring.

    Decompose shift = q * w_local + r: output local row j takes shard
    (d - q) local row (j - r) for j >= r and shard (d - q - 1) local row
    (w_local + j - r) for j < r — one ppermute when r == 0 (the production
    W_local == 1 case), two otherwise.
    """
    shift = shift % (n_shards * w_local)
    q, r = divmod(shift, w_local)

    def from_shard(d):  # this shard's block, fetched from d shards back
        d = d % n_shards
        return x if d == 0 else _ppermute_shift(x, axis_name, n_shards, d)

    a = from_shard(q)
    if r == 0:
        return a
    b = from_shard(q + 1)
    import jax.numpy as jnp
    return jnp.concatenate([b[w_local - r:], a[:w_local - r]], axis=0)


def _region_ctx(mesh, spec, cfg, n_workers):
    """Shared setup of the manual-region gossip functions: worker-axis
    name(s), shard count, local worker count, static row ranges, resolved
    wire format, and the split/replicated PartitionSpecs."""
    from ..core.gossip import packed_row_ranges, resolved_wire_format

    wa = data_axes(mesh)
    if not wa:
        raise ValueError(
            f"mesh has no data axes (axis_names={mesh.axis_names})")
    axis_name = wa if len(wa) > 1 else wa[0]
    n_shards = math.prod(mesh.shape[a] for a in wa)
    w_local = local_worker_count(mesh, n_workers)
    ranges = packed_row_ranges(spec, cfg)
    wire = resolved_wire_format(cfg)
    split = jax.sharding.PartitionSpec(wa if len(wa) > 1 else wa[0])
    rep = jax.sharding.PartitionSpec()
    return axis_name, n_shards, w_local, ranges, wire, split, rep


def _exchange_switch(packed, shift_idx, block_idx, *, cfg, spec, ranges,
                     wire, roll):
    """The partial-exchange ``lax.switch`` inside a manual region: every
    (shift, partition) branch slices a STATIC row range, applies the wire
    transform, and rolls it along the worker ring with ``roll`` (the
    ppermute-based manual-region transport).  Returns ``sent`` (float
    wires) or ``(sent, sent_scales)`` (int8 wire)."""
    import jax.numpy as jnp

    from ..core.gossip import quantized_exchange_body, wire_roundtrip

    p = cfg.partial_blocks
    if wire == "int8":
        def branch(s, r0, r1):
            def body(x):
                # shared quantize/scatter body; only the roll transport
                # (ppermute here, jnp.roll in the GSPMD engine) differs
                return quantized_exchange_body(
                    x, r0, r1, spec.block_rows, lambda t: roll(t, s))
            return body
    else:
        def branch(s, r0, r1):
            def body(x):
                blk = wire_roundtrip(x[:, r0:r1], cfg)
                return jnp.zeros_like(x).at[:, r0:r1].set(roll(blk, s))
            return body

    branches = [branch(s, r0, r1)
                for s in cfg.shifts for (r0, r1) in ranges]
    return jax.lax.switch(shift_idx * p + block_idx, branches, packed)


def _region_blend(packed, pgrads, ext, ext_scales, ext_idx, step, *, cfg,
                  acfg, spec, ranges_arr, extra=0, depth=None, lr=None,
                  lives=()):
    """The resident-kernel blend inside a manual region, with the
    step-based staleness guard (``extra=1`` selects the pipelined
    delay+1 threshold; ``depth`` overrides for single-slot callers) and
    the fused eq.-1 ``lr`` operand.  ``lives`` are per-peer liveness
    vectors (DESIGN.md §8 — the buffered payload's recorded validity and
    this round's mask, each the local (W_local,) slice) folded into the
    same gate_scale operand as the scalar guard."""
    from ..core.gossip import combine_gate_scale, staleness_valid
    from ..kernels.gossip_blend import gossip_blend_w_resident

    valid = staleness_valid(step, cfg, extra=extra, depth=depth)
    new_packed, gates = gossip_blend_w_resident(
        packed, pgrads, ext[:, None], ranges_arr[ext_idx], acfg.eps, lr=lr,
        ext_scales=None if ext_scales is None else ext_scales[:, None],
        use_parzen=acfg.use_parzen, elastic=acfg.elastic,
        elastic_alpha=acfg.elastic_alpha, block_rows=spec.block_rows,
        psum_axes=cfg.gate_psum_axes or None,
        gate_scale=combine_gate_scale(valid, *lives))
    return new_packed, gates[:, 0]


def _roll_live_manual(live, shift_idx, cfg, roll):
    """sent_live inside a manual region: the (W_local,) liveness slice
    travels the SAME static-shift switch + ppermute transport as the
    payload (the 1-D case of _roll_workers_manual), times the receiver's
    own liveness — core.gossip.roll_live with the manual-region roll."""
    branches = [(lambda l, s=s: roll(l, s) * l) for s in cfg.shifts]
    return jax.lax.switch(shift_idx, branches, live)


def shard_map_gossip_round(mesh, spec, cfg, acfg, *, n_workers=None,
                           elastic: bool = False):
    """The whole packed-resident gossip round — exchange AND blend — in one
    shard_map manual region (DESIGN.md §6).

    Returns a jittable round function over global ``(W, R, LANE)`` arrays:

      * float wire (wire_format None/"dtype"):
        ``round(packed, pgrads, buf, buf_idx, step, shift_idx, block_idx)
        -> (new_packed, sent, gates)``
      * int8 wire (wire_format="int8"):
        ``round(packed, pgrads, buf, buf_scales, buf_idx, step, shift_idx,
        block_idx) -> (new_packed, sent, sent_scales, gates)`` — the
        exchanged slice is quantized per shard (core/packing.py
        quantize_rows), the ``lax.ppermute`` moves the int8 payload plus
        the per-block_rows f32 scales (|w|/(4p) + ~|w|/(4p·block_rows·LANE)
        wire bytes), and the resident kernel dequantizes in-register.

    ``step`` is the round counter driving the round-1 staleness guard
    (core/gossip.py staleness_valid): with delay > 0 the first round's
    zero init buffer is explicitly gated out.

    Inside the region each data shard sees its ``(W_local, R, LANE)`` slice;
    the partial exchange is a static row-slice ``lax.ppermute`` over the
    (pod+)data axes (the paper's one-peer send) and the blend is the
    row-range resident Pallas kernel (``gossip_blend_w_resident``) —
    exchange and blend share one manual region, so XLA never re-lays-out
    the packed ensemble between them.  The GSPMD path
    (core.gossip.asgd_gossip_apply_packed) remains the in-jit formulation
    of the same round; this is the production wiring.

    spec: group-contiguous WPackSpec (core/packing.py); cfg/acfg:
    GossipConfig/ASGDConfig; n_workers: global worker count (defaults to
    the mesh's data-shard count — W_local == 1).

    elastic=True (DESIGN.md §8) appends two split ``(W,)`` operands —
    ``buf_live`` (the buffered payload's recorded validity) and ``live``
    (this round's per-peer mask) — and one extra split output
    ``sent_live``: a masked ppermute payload arrives as eq.-3 zeros and
    its gate is closed, so the receiving shard DROPS it rather than
    blending it.
    """
    import jax.numpy as jnp

    from ..core.gossip import mask_live_rows

    axis_name, n_shards, w_local, ranges, wire, split, rep = _region_ctx(
        mesh, spec, cfg, n_workers)
    ranges_arr = jnp.asarray(ranges, jnp.int32)

    def roll(x, s):
        return _roll_workers_manual(x, s, axis_name, n_shards, w_local)

    def exchange(packed, shift_idx, block_idx):
        return _exchange_switch(packed, shift_idx, block_idx, cfg=cfg,
                                spec=spec, ranges=ranges, wire=wire,
                                roll=roll)

    def blend(packed, pgrads, ext, ext_scales, ext_idx, step, lives=()):
        # the round's buf argument is a SINGLE received block (the caller
        # feeds last round's sent back in), so the guard clamps to depth
        # 1 whatever cfg.delay claims — see staleness_valid
        return _region_blend(packed, pgrads, ext, ext_scales, ext_idx,
                             step, cfg=cfg, acfg=acfg, spec=spec,
                             ranges_arr=ranges_arr,
                             depth=min(cfg.delay, 1), lives=lives)

    if wire == "int8":
        def round_fn(packed, pgrads, buf, buf_scales, buf_idx, step,
                     shift_idx, block_idx, *elastic_args):
            sent, sent_scales = exchange(packed, shift_idx, block_idx)
            lives, sent_live = (), None
            if elastic:
                buf_live, live = elastic_args
                sent_live = _roll_live_manual(live, shift_idx, cfg, roll)
                sent = mask_live_rows(sent, sent_live)
                sent_scales = mask_live_rows(sent_scales, sent_live)
                pgrads = mask_live_rows(pgrads, live)
            if cfg.delay == 0:
                ext, ext_scales, ext_idx = sent, sent_scales, block_idx
                if elastic:
                    lives = (sent_live, live)
            else:
                ext, ext_scales, ext_idx = buf, buf_scales, buf_idx
                if elastic:
                    lives = (buf_live, live)
            new_packed, gates = blend(packed, pgrads, ext, ext_scales,
                                      ext_idx, step, lives)
            if elastic:
                return new_packed, sent, sent_scales, gates, sent_live
            return new_packed, sent, sent_scales, gates

        n_split_in, n_out = 4, 4
    else:
        def round_fn(packed, pgrads, buf, buf_idx, step, shift_idx,
                     block_idx, *elastic_args):
            sent = exchange(packed, shift_idx, block_idx)
            lives, sent_live = (), None
            if elastic:
                buf_live, live = elastic_args
                sent_live = _roll_live_manual(live, shift_idx, cfg, roll)
                sent = mask_live_rows(sent, sent_live)
                pgrads = mask_live_rows(pgrads, live)
            if cfg.delay == 0:
                ext, ext_idx = sent, block_idx
                if elastic:
                    lives = (sent_live, live)
            else:
                ext, ext_idx = buf, buf_idx
                if elastic:
                    lives = (buf_live, live)
            new_packed, gates = blend(packed, pgrads, ext, None, ext_idx,
                                      step, lives)
            if elastic:
                return new_packed, sent, gates, sent_live
            return new_packed, sent, gates

        n_split_in, n_out = 3, 3

    if elastic:
        # buf_live + live ride as trailing split operands; sent_live as a
        # trailing split output
        return jax.shard_map(
            round_fn, mesh=mesh,
            in_specs=(split,) * n_split_in + (rep,) * 4 + (split,) * 2,
            out_specs=(split,) * (n_out + 1),
            check_vma=False)
    return jax.shard_map(
        round_fn, mesh=mesh,
        in_specs=(split,) * n_split_in + (rep,) * 4,
        out_specs=(split,) * n_out,
        check_vma=False)


# ---------------------------------------------------------------------------
# pipelined manual regions (DESIGN.md §7): the exchange is split into its
# own region so the train step can ISSUE the payload ppermute before the
# forward/backward — the collective overlaps the compute — while the blend
# region stays communication-free and consumes the payload launched a round
# earlier (the caller-carried FIFO head)
# ---------------------------------------------------------------------------

def shard_map_initiate_exchange(mesh, spec, cfg, *, n_workers=None,
                                elastic: bool = False):
    """The INITIATE half as its own manual region: ONLY the partial-row
    ``lax.ppermute`` of this round's payload, launched from the pre-blend
    ensemble.

    Returns a jittable ``initiate(packed, shift_idx, block_idx)`` over the
    global ``(W, R, LANE)`` array -> ``sent`` (float wires) or
    ``(sent, sent_scales)`` (int8 wire).  Its inputs are train-step
    program inputs, so placed before the forward/backward the collective
    runs concurrently with it; the product is consumed only by the NEXT
    round's blend (DESIGN.md §7 timeline).

    elastic=True appends a split ``live`` operand and a trailing
    ``sent_live`` output: dead peers' payload rows leave the region as
    eq.-3 zeros (the masked ppermute payload is dropped, DESIGN.md §8)."""
    from ..core.gossip import mask_live_rows

    axis_name, n_shards, w_local, ranges, wire, split, rep = _region_ctx(
        mesh, spec, cfg, n_workers)

    def roll(x, s):
        return _roll_workers_manual(x, s, axis_name, n_shards, w_local)

    def initiate(packed, shift_idx, block_idx, *elastic_args):
        out = _exchange_switch(packed, shift_idx, block_idx, cfg=cfg,
                               spec=spec, ranges=ranges, wire=wire,
                               roll=roll)
        if not elastic:
            return out
        (live,) = elastic_args
        sent_live = _roll_live_manual(live, shift_idx, cfg, roll)
        if wire == "int8":
            sent, sent_scales = out
            return (mask_live_rows(sent, sent_live),
                    mask_live_rows(sent_scales, sent_live), sent_live)
        return mask_live_rows(out, sent_live), sent_live

    n_out = (2 if wire == "int8" else 1) + (1 if elastic else 0)
    return jax.shard_map(
        initiate, mesh=mesh,
        in_specs=(split,) + (rep,) * 2 + ((split,) if elastic else ()),
        out_specs=(split,) * n_out if n_out > 1 else split,
        check_vma=False)


def shard_map_consume_blend(mesh, spec, cfg, acfg, *, n_workers=None,
                            pipelined: bool = True,
                            elastic: bool = False):
    """The CONSUME half as its own manual region: the resident fused
    blend + eq.-1 update of the FIFO-head payload — COMMUNICATION-FREE
    (the only collective a configuration can add is the tiny
    ``gate_psum_axes`` accumulator psum), which is the structural proof
    that the wire is off the blend's critical path.

    Returns ``consume(packed, pgrads, ext[, ext_scales], ext_idx, step)
    -> (new_packed, gates)``; ``pipelined=True`` (default) applies the
    delay+1 staleness threshold of the pipelined schedule
    (staleness_valid extra=1).  elastic=True appends two split ``(W,)``
    operands ``ext_live`` (the FIFO head's recorded launch validity) and
    ``live`` (this round's mask) — both close the gates through the same
    gate_scale path as the scalar guard, and dead workers' local steps
    are masked (DESIGN.md §8)."""
    import jax.numpy as jnp

    from ..core.gossip import mask_live_rows

    _, _, _, ranges, wire, split, rep = _region_ctx(mesh, spec, cfg,
                                                    n_workers)
    ranges_arr = jnp.asarray(ranges, jnp.int32)
    extra = 1 if pipelined else 0

    if wire == "int8":
        def consume(packed, pgrads, ext, ext_scales, ext_idx, step,
                    *elastic_args):
            lives = ()
            if elastic:
                ext_live, live = elastic_args
                lives = (ext_live, live)
                pgrads = mask_live_rows(pgrads, live)
            return _region_blend(packed, pgrads, ext, ext_scales, ext_idx,
                                 step, cfg=cfg, acfg=acfg, spec=spec,
                                 ranges_arr=ranges_arr, extra=extra,
                                 lives=lives)
        n_split_in = 4   # packed, pgrads, ext, ext_scales
    else:
        def consume(packed, pgrads, ext, ext_idx, step, *elastic_args):
            lives = ()
            if elastic:
                ext_live, live = elastic_args
                lives = (ext_live, live)
                pgrads = mask_live_rows(pgrads, live)
            return _region_blend(packed, pgrads, ext, None, ext_idx, step,
                                 cfg=cfg, acfg=acfg, spec=spec,
                                 ranges_arr=ranges_arr, extra=extra,
                                 lives=lives)
        n_split_in = 3   # packed, pgrads, ext

    return jax.shard_map(
        consume, mesh=mesh,
        in_specs=(split,) * n_split_in + (rep,) * 2
        + ((split,) * 2 if elastic else ()),  # ext_idx, step[, lives]
        out_specs=(split, split),
        check_vma=False)


def shard_map_pipelined_round(mesh, spec, cfg, acfg, *, n_workers=None,
                              elastic: bool = False):
    """The whole PIPELINED round in one manual region (DESIGN.md §7):
    blend the caller-carried FIFO-head payload ``ext`` (launched delay+1
    rounds ago), and launch this round's payload from the PRE-blend
    ensemble — the ppermute shares no dependency with the blend, so XLA
    is free to overlap the two inside the region.

    Signatures over global ``(W, R, LANE)`` arrays:

      * float wire: ``round(packed, pgrads, ext, ext_idx, step, shift_idx,
        block_idx) -> (new_packed, sent, gates)``
      * int8 wire: ``round(packed, pgrads, ext, ext_scales, ext_idx, step,
        shift_idx, block_idx) -> (new_packed, sent, sent_scales, gates)``

    The FIFO pop/push lives with the caller (the GSPMD engine
    core/gossip.py asgd_gossip_apply_pipelined is the in-jit formulation
    of the identical round; parity is asserted in
    tests/test_gossip_pipelined.py on 8 fake devices).

    elastic=True (DESIGN.md §8) appends two split ``(W,)`` operands —
    ``ext_live`` (the consumed payload's recorded launch validity) and
    ``live`` (this round's mask) — and a trailing split output
    ``sent_live`` recording the validity of the payload launched this
    round."""
    import jax.numpy as jnp

    from ..core.gossip import mask_live_rows

    axis_name, n_shards, w_local, ranges, wire, split, rep = _region_ctx(
        mesh, spec, cfg, n_workers)
    ranges_arr = jnp.asarray(ranges, jnp.int32)

    def roll(x, s):
        return _roll_workers_manual(x, s, axis_name, n_shards, w_local)

    def exchange(packed, shift_idx, block_idx):
        return _exchange_switch(packed, shift_idx, block_idx, cfg=cfg,
                                spec=spec, ranges=ranges, wire=wire,
                                roll=roll)

    def blend(packed, pgrads, ext, ext_scales, ext_idx, step, lives=()):
        return _region_blend(packed, pgrads, ext, ext_scales, ext_idx,
                             step, cfg=cfg, acfg=acfg, spec=spec,
                             ranges_arr=ranges_arr, extra=1, lives=lives)

    if wire == "int8":
        def round_fn(packed, pgrads, ext, ext_scales, ext_idx, step,
                     shift_idx, block_idx, *elastic_args):
            lives, sent_live = (), None
            if elastic:
                ext_live, live = elastic_args
                lives = (ext_live, live)
                pgrads = mask_live_rows(pgrads, live)
            new_packed, gates = blend(packed, pgrads, ext, ext_scales,
                                      ext_idx, step, lives)
            sent, sent_scales = exchange(packed, shift_idx, block_idx)
            if elastic:
                sent_live = _roll_live_manual(live, shift_idx, cfg, roll)
                sent = mask_live_rows(sent, sent_live)
                sent_scales = mask_live_rows(sent_scales, sent_live)
                return new_packed, sent, sent_scales, gates, sent_live
            return new_packed, sent, sent_scales, gates

        n_split_in, n_out = 4, 4
    else:
        def round_fn(packed, pgrads, ext, ext_idx, step, shift_idx,
                     block_idx, *elastic_args):
            lives, sent_live = (), None
            if elastic:
                ext_live, live = elastic_args
                lives = (ext_live, live)
                pgrads = mask_live_rows(pgrads, live)
            new_packed, gates = blend(packed, pgrads, ext, None, ext_idx,
                                      step, lives)
            sent = exchange(packed, shift_idx, block_idx)
            if elastic:
                sent_live = _roll_live_manual(live, shift_idx, cfg, roll)
                sent = mask_live_rows(sent, sent_live)
                return new_packed, sent, gates, sent_live
            return new_packed, sent, gates

        n_split_in, n_out = 3, 3

    if elastic:
        return jax.shard_map(
            round_fn, mesh=mesh,
            in_specs=(split,) * n_split_in + (rep,) * 4 + (split,) * 2,
            out_specs=(split,) * (n_out + 1),
            check_vma=False)
    return jax.shard_map(
        round_fn, mesh=mesh,
        in_specs=(split,) * n_split_in + (rep,) * 4,
        out_specs=(split,) * n_out,
        check_vma=False)

"""CLI trainer: ASGD (paper) / SimuParallelSGD / sync-BATCH on any
assigned architecture.

Examples (CPU-host scale):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \\
      --reduced --steps 50 --algo asgd --workers 4 --batch 2 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch granite-moe-1b-a400m \\
      --reduced --steps 20 --algo sync

Mesh: the trainer builds a (data, model=1) mesh over the devices it is
given (all of ``jax.devices()`` by default), with data = gcd(--workers,
device count).  The worker axis of the params, the gossip FIFO, the inner
optimizer state and the batch is split over ``data``, so each device holds
W / data worker replicas; on one device every replica shares it.  The
packed engines run their Pallas kernel per device (shard_map over
``data``) and the gossip exchange lowers to a collective-permute.  Drop
--reduced for the published widths.
"""
from __future__ import annotations

import argparse
import math
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import (load_checkpoint, load_checkpoint_packed,
                          save_checkpoint, save_checkpoint_packed)
from ..configs.registry import get_arch
from ..core.asgd import ASGDConfig
from ..core.gossip import (GossipConfig, init_gossip_state,
                           init_packed_gossip_state,
                           init_pipelined_gossip_state, leaf_groups)
from ..core.packing import pack_spec_w, pack_w
from ..data.synthetic import lm_batch_iterator
from ..models import model as M
from .cache import enable_compile_cache
from .mesh import make_host_mesh
from .steps import init_inner_state, make_train_step, place_workers


def setup(argv=None, *, devices=None):
    """Parse ``argv`` and build one training run: the mesh over
    ``devices`` (default ``jax.devices()``), the initial state placed on
    it, and the jitted step.  :func:`train` runs it; :func:`main` is the
    two together."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of the arch (CPU)")
    ap.add_argument("--algo", default="asgd",
                    choices=["asgd", "silent", "sync"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--workers", type=int, default=4,
                    help="ASGD worker groups (W axis)")
    ap.add_argument("--batch", type=int, default=2, help="per-worker batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--eps", type=float, default=0.05)
    ap.add_argument("--inner", default="sgd",
                    choices=["sgd", "momentum", "adam"],
                    help="inner optimizer under the ASGD gossip "
                         "(paper: sgd)")
    ap.add_argument("--partial-blocks", type=int, default=4)
    ap.add_argument("--delay", type=int, default=1)
    ap.add_argument("--wire-format", default="none",
                    choices=["none", "int8", "bf16", "f16"],
                    help="gossip wire format (DESIGN.md §6): 'int8' ships "
                         "the exchanged block as int8 + per-block f32 "
                         "scales (wire bytes /4; on --packed-resident the "
                         "staleness buffer stays quantized and the kernel "
                         "dequantizes in-register); 'bf16'/'f16' cast the "
                         "payload dtype; 'none' sends the carrier dtype")
    ap.add_argument("--elastic", action="store_true",
                    help="fault-tolerant elastic mode (DESIGN.md §8): the "
                         "gossip state carries a per-peer liveness mask, "
                         "and --restore accepts a checkpoint saved at a "
                         "DIFFERENT --workers count (leaves re-seated onto "
                         "this run's W and re-packed; liveness gates stay "
                         "closed for the join window)")
    ap.add_argument("--elastic-blend", action="store_true",
                    help="beyond-paper elastic (EASGD-style) blending")
    ap.add_argument("--lr-schedule", default="none",
                    choices=["none", "const", "cosine", "linear"],
                    help="per-round lr schedule on the gossip step counter "
                         "(optim.lr_schedule; --pipelined only — the "
                         "consume blend takes a per-round lr operand); "
                         "'none' keeps the static --eps")
    ap.add_argument("--warmup", type=int, default=100,
                    help="lr-schedule warmup rounds")
    ap.add_argument("--packed-resident", action="store_true",
                    help="carry the packed (W, R, LANE) ensemble across "
                         "steps (DESIGN.md §6): gossip exchange + blend on "
                         "packed rows; unpack only at checkpoint "
                         "boundaries")
    ap.add_argument("--pipelined", action="store_true",
                    help="pipeline the gossip round (DESIGN.md §7, implies "
                         "--packed-resident): issue the payload exchange "
                         "before the forward/backward, blend the payload "
                         "launched delay+1 rounds ago, and differentiate "
                         "the loss directly w.r.t. the packed ensemble "
                         "(the gradient is born packed; with "
                         "--inner momentum/adam the moments are packed "
                         "too, so such checkpoints restore only into "
                         "pipelined runs — sgd checkpoints stay fully "
                         "layout-interoperable)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save", default=None, help="checkpoint path")
    ap.add_argument("--restore", default=None,
                    help="resume from checkpoint (paper §4: early-"
                         "terminated runs restart from w_0)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.key(args.seed)

    params = M.init_model(cfg, key)
    W = args.workers
    wparams = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (W,) + x.shape).copy(), params)
    wire_format, payload_dtype = {
        "none": (None, None),
        "int8": ("int8", None),
        "bf16": ("dtype", jnp.bfloat16),
        "f16": ("dtype", jnp.float16),
    }[args.wire_format]
    gcfg = GossipConfig(
        shifts=tuple(s for s in (1, 2, 4, 8) if s < max(W, 2)),
        partial_blocks=args.partial_blocks, delay=args.delay,
        wire_format=wire_format, payload_dtype=payload_dtype)
    acfg = ASGDConfig(eps=args.eps, elastic=args.elastic_blend)
    spec = None
    if args.pipelined:
        args.packed_resident = True
    if args.elastic and args.algo != "asgd":
        ap.error("--elastic requires --algo asgd (liveness gates live in "
                 "the gossip state)")
    if args.lr_schedule != "none" and not args.pipelined:
        ap.error("--lr-schedule requires --pipelined")
    schedule = None
    if args.lr_schedule != "none":
        from ..optim import lr_schedule as _mk_sched
        schedule = _mk_sched(args.lr_schedule, args.eps,
                             warmup=args.warmup, total=args.steps)
    if args.packed_resident:
        # pack ONCE at init; the ensemble stays packed until checkpoint
        # boundaries (DESIGN.md §6)
        spec = pack_spec_w(
            wparams, block_rows=gcfg.fused_block_rows,
            groups=leaf_groups(wparams, gcfg.partial_blocks),
            n_groups=gcfg.partial_blocks)
        packed = pack_w(wparams, spec)
        wire_br = spec.block_rows if wire_format == "int8" else None
        if args.pipelined:
            # pipelined FIFO (depth delay+1) + packed-shaped inner-
            # optimizer state: the gradient is born packed (DESIGN.md §7)
            gossip0 = init_pipelined_gossip_state(packed, gcfg,
                                                  block_rows=wire_br,
                                                  elastic=args.elastic)
            opt0 = init_inner_state(packed, args.inner)
        else:
            gossip0 = init_packed_gossip_state(packed, gcfg,
                                               block_rows=wire_br,
                                               elastic=args.elastic)
            opt0 = init_inner_state(wparams, args.inner)
        state = {"params": packed, "gossip": gossip0, "opt": opt0,
                 "step": jnp.int32(0)}
        if args.restore:
            state = load_checkpoint_packed(args.restore, state, spec,
                                           elastic=args.elastic)
            print(f"restored step={int(state['step'])} "
                  f"from {args.restore} (re-packed"
                  f"{', elastic' if args.elastic else ''})")
    else:
        state = {"params": wparams,
                 "gossip": init_gossip_state(wparams, gcfg,
                                             elastic=args.elastic),
                 "opt": init_inner_state(wparams, args.inner),
                 "step": jnp.int32(0)}
        if args.restore:
            state = load_checkpoint(args.restore, state,
                                    resize_workers=args.elastic)
            print(f"restored step={int(state['step'])} from {args.restore}")

    devices = jax.devices() if devices is None else list(devices)
    mesh = make_host_mesh(data=math.gcd(W, len(devices)), model=1,
                          devices=devices)
    # stacked packed FIFOs (depth >= 2) carry the worker axis second
    fifo_axis = state["gossip"].buf.ndim - 3 if args.packed_resident else 0
    state = {"params": place_workers(state["params"], mesh),
             "gossip": place_workers(state["gossip"], mesh, axis=fifo_axis),
             "opt": place_workers(state["opt"], mesh),
             "step": state["step"]}

    step_fn = jax.jit(make_train_step(
        cfg, algo=args.algo, gcfg=gcfg, acfg=acfg, inner=args.inner,
        spmd_axes="data", packed_resident=args.packed_resident,
        pack_spec=spec, pipelined=args.pipelined, lr_schedule=schedule))
    # the CLI trainer drives a fully-live fleet; a launcher that detects
    # real churn would flip entries of this mask per round (DESIGN.md §8)
    live_args = ((place_workers(jnp.ones((W,), jnp.float32), mesh),)
                 if args.elastic else ())
    its = [lm_batch_iterator(
        args.seed * 1000 + w, args.batch, args.seq, cfg.vocab,
        frontend=cfg.frontend, d_model=cfg.d_model,
        encoder_seq=cfg.encoder_seq, prefix_len=cfg.prefix_len)
        for w in range(W)]

    def next_wbatch():
        bs = [next(it) for it in its]
        return place_workers({k: np.stack([b[k] for b in bs])
                              for k in bs[0]}, mesh)

    return SimpleNamespace(args=args, cfg=cfg, spec=spec, mesh=mesh,
                           state=state, step_fn=step_fn, key=key,
                           live_args=live_args, next_wbatch=next_wbatch)


def train(run):
    """Run ``run`` (from :func:`setup`) to ``--steps`` under its mesh;
    return the per-step losses."""
    args, state = run.args, run.state
    t0 = time.time()
    losses = []
    with jax.sharding.set_mesh(run.mesh):
        for step in range(int(state["step"]), args.steps):
            batch = run.next_wbatch()
            state["params"], state["gossip"], state["opt"], metrics = \
                run.step_fn(state["params"], state["gossip"], state["opt"],
                            batch, jax.random.fold_in(run.key, step),
                            *run.live_args)
            state["step"] = jnp.int32(step + 1)
            losses.append(float(metrics["loss"]))
            if step % args.log_every == 0 or step == args.steps - 1:
                extra = ""
                if "n_good" in metrics:
                    extra = f" good_msgs={float(metrics['n_good']):.0f}"
                print(f"step {step:5d} loss {losses[-1]:.4f}"
                      f" ({time.time() - t0:.1f}s){extra}", flush=True)

    if losses:
        print(f"final: last-loss={losses[-1]:.4f} "
              f"(start {losses[0]:.4f})", flush=True)
    else:
        # restored step >= --steps: nothing to run, still save/exit clean
        print(f"final: no steps run (restored step "
              f"{int(state['step'])} >= --steps {args.steps})", flush=True)
    if args.save:
        if args.packed_resident:
            save_checkpoint_packed(args.save, state, run.spec)
        else:
            save_checkpoint(args.save, state)
        print(f"saved -> {args.save}")
    return losses


def main(argv=None, *, devices=None):
    enable_compile_cache()
    return train(setup(argv, devices=devices))


if __name__ == "__main__":
    main()

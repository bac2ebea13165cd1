"""Jittable train / prefill / decode steps + ShapeDtypeStruct input specs.

train_step (ASGD, the paper's contribution as a first-class feature):
  state = {params (leading W worker axis), gossip: GossipState, step}
  1. per-worker mini-batch loss/grads       (vmapped over W)
  2. asgd_gossip_apply: local SGD + partial-state ppermute + Parzen blend
  Baselines selectable via algo=: 'asgd' | 'silent' (SimuParallelSGD) |
  'sync' (BATCH/MapReduce analogue, all-reduce every step).

serve steps build on repro.models.model prefill/decode (no worker axis —
serving uses one replica set, tensor-parallel over `model`, batch over
`data`(+`pod`)).

All functions here are shape-polymorphic over the mesh; the dry-run calls
them with ShapeDtypeStructs via .lower()/.compile() only.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from ..core.asgd import ASGDConfig
from ..core.gossip import (GossipConfig, asgd_gossip_apply, init_gossip_state,
                           local_sgd_apply, sync_dp_apply)
from ..models import model as M
from . import sharding as SH
from .mesh import data_axes, n_worker_groups

PARAM_DTYPE = jnp.bfloat16

# train-step engines (input_specs / step_and_args / dryrun --engine):
#   pytree    — the per-leaf GSPMD formulation (the historical default)
#   packed    — the packed-resident ensemble (DESIGN.md §6)
#   pipelined — packed-resident + the one-round-deep exchange pipeline and
#               packed-native gradients (DESIGN.md §7)
ENGINES = ("pytree", "packed", "pipelined")


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs — never allocated)
# ---------------------------------------------------------------------------

def batch_struct(cfg: ModelConfig, shape: ShapeConfig, mesh, *, train: bool):
    """Host-batch ShapeDtypeStructs for one step, sharded.

    train: tokens (W, B_local, S) where W = worker groups and
    B_local = global_batch / W. serve: (B_global, S) with batch over data.
    """
    wa = data_axes(mesh)
    W = n_worker_groups(mesh)
    S = shape.seq_len
    if cfg.frontend == "vision":
        S_text = S - cfg.prefix_len
    else:
        S_text = S

    def mk(shp, dtype):
        spec = SH.batch_pspec(len(shp), worker_axes=wa, train=train)
        return jax.ShapeDtypeStruct(
            shp, dtype, sharding=jax.sharding.NamedSharding(
                mesh, spec))

    out = {}
    if train:
        B_local = max(1, shape.global_batch // W)
        lead = (W, B_local)
    else:
        lead = (shape.global_batch,)
    out["tokens"] = mk(lead + (S_text,), jnp.int32)
    if cfg.frontend == "audio":
        out["frames"] = mk(lead + (cfg.encoder_seq, cfg.d_model),
                           PARAM_DTYPE)
    if cfg.frontend == "vision":
        out["patches"] = mk(lead + (cfg.prefix_len, cfg.d_model),
                            PARAM_DTYPE)
    return out


def params_struct(cfg: ModelConfig, mesh, *, train: bool):
    """ShapeDtypeStructs for params (leading W axis when train)."""
    W = n_worker_groups(mesh)
    wa = data_axes(mesh)
    shapes = jax.eval_shape(
        lambda: M.init_model(cfg, jax.random.key(0), dtype=PARAM_DTYPE))
    if train:
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((W,) + s.shape, s.dtype), shapes)
    shardings = SH.tree_shardings(mesh, shapes, worker_axes=wa, train=train)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


def cache_struct(cfg: ModelConfig, shape: ShapeConfig, mesh):
    wa = data_axes(mesh)
    cache = jax.eval_shape(
        lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len,
                             dtype=PARAM_DTYPE))
    shardings = SH.cache_shardings(mesh, cache, cfg, worker_axes=wa)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        cache, shardings)


def gossip_struct(cfg: ModelConfig, mesh, gcfg: GossipConfig):
    p_struct = params_struct(cfg, mesh, train=True)
    state = jax.eval_shape(lambda p: init_gossip_state(p, gcfg), p_struct)
    # buffer shards like params; idx/step replicated
    buf_shard = jax.tree.map(lambda s: s.sharding, p_struct)
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def attach(s, sh):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    return type(state)(
        buf=jax.tree.map(attach, state.buf, buf_shard),
        buf_idx=attach(state.buf_idx, rep),
        step=attach(state.step, rep))


def packed_spec_for(cfg: ModelConfig, mesh, gcfg: GossipConfig):
    """Group-contiguous WPackSpec of the train-param structure.

    Built from ``eval_shape`` structs (pack_spec_w/leaf_groups only read
    shapes and sizes), so the dry-run can derive the resident layout
    without allocating a single parameter."""
    from ..core.gossip import leaf_groups
    from ..core.packing import pack_spec_w

    p_struct = params_struct(cfg, mesh, train=True)
    groups = leaf_groups(p_struct, gcfg.partial_blocks)
    return pack_spec_w(p_struct, block_rows=gcfg.fused_block_rows,
                       groups=groups, n_groups=gcfg.partial_blocks)


def _worker_split(mesh):
    wa = data_axes(mesh)
    return jax.sharding.PartitionSpec(wa if len(wa) > 1 else wa[0])


def place_workers(tree, mesh, *, axis: int = 0):
    """device_put every leaf of ``tree`` with its ``axis`` (the worker
    axis) split over the mesh's data axes; leaves of lower rank (round
    counters, partition ids) are replicated."""
    split = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*(None,) * axis,
                                         *_worker_split(mesh)))
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.tree.map(
        lambda x: jax.device_put(x, split if x.ndim > axis else rep), tree)


def packed_params_struct(cfg: ModelConfig, mesh, gcfg: GossipConfig,
                         spec=None):
    """ShapeDtypeStruct of the resident (W, rows, LANE) f32 ensemble,
    worker axis sharded over the data axes."""
    from ..kernels import LANE

    spec = spec or packed_spec_for(cfg, mesh, gcfg)
    sharding = jax.sharding.NamedSharding(mesh, _worker_split(mesh))
    return jax.ShapeDtypeStruct((spec.n_workers, spec.rows, LANE),
                                jnp.float32, sharding=sharding)


def packed_gossip_struct(cfg: ModelConfig, mesh, gcfg: GossipConfig,
                         spec=None, *, pipelined: bool = False):
    """Sharded ShapeDtypeStructs of the PackedGossipState a packed-resident
    / pipelined run carries (FIFO depth per core.gossip.fifo_depth; buf
    shards along its worker axis — axis 1 when the FIFO is stacked)."""
    from ..core.gossip import (fifo_depth, init_packed_gossip_state,
                               resolved_wire_format)

    spec = spec or packed_spec_for(cfg, mesh, gcfg)
    p_struct = packed_params_struct(cfg, mesh, gcfg, spec)
    depth = fifo_depth(gcfg, pipelined=pipelined)
    block_rows = spec.block_rows \
        if resolved_wire_format(gcfg) == "int8" else None
    state = jax.eval_shape(
        lambda p: init_packed_gossip_state(p, gcfg, block_rows=block_rows,
                                           depth=depth), p_struct)
    wsplit = _worker_split(mesh)
    buf_ps = (jax.sharding.PartitionSpec(None, *wsplit) if depth >= 2
              else wsplit)
    buf_sh = jax.sharding.NamedSharding(mesh, buf_ps)
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def attach(s, sh):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    return type(state)(
        buf=attach(state.buf, buf_sh),
        buf_scales=(None if state.buf_scales is None
                    else attach(state.buf_scales, buf_sh)),
        buf_idx=attach(state.buf_idx, rep),
        step=attach(state.step, rep))


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh,
                gcfg: GossipConfig | None = None,
                engine: str = "pytree") -> dict:
    """Everything a step function needs, as sharded ShapeDtypeStructs.

    engine: 'pytree' (per-leaf params + GossipState) or
    'packed'/'pipelined' (resident (W, rows, LANE) ensemble +
    PackedGossipState — the dry-run route for resident HLO rooflines)."""
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r} (expected {ENGINES})")
    gcfg = gcfg or GossipConfig()
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    if shape.kind == "train":
        if engine != "pytree":
            spec = packed_spec_for(cfg, mesh, gcfg)
            return {
                "params": packed_params_struct(cfg, mesh, gcfg, spec),
                "gossip": packed_gossip_struct(
                    cfg, mesh, gcfg, spec,
                    pipelined=engine == "pipelined"),
                "opt": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
                "batch": batch_struct(cfg, shape, mesh, train=True),
                "key": key,
            }
        return {
            "params": params_struct(cfg, mesh, train=True),
            "gossip": gossip_struct(cfg, mesh, gcfg),
            "opt": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            "batch": batch_struct(cfg, shape, mesh, train=True),
            "key": key,
        }
    if shape.kind == "prefill":
        return {
            "params": params_struct(cfg, mesh, train=False),
            "batch": batch_struct(cfg, shape, mesh, train=False),
        }
    # decode
    wa = data_axes(mesh)
    import math as _math
    w_size = _math.prod(mesh.shape[a] for a in wa)
    tok_spec = (jax.sharding.PartitionSpec(wa)
                if shape.global_batch % w_size == 0
                else jax.sharding.PartitionSpec(None))
    tok = jax.ShapeDtypeStruct(
        (shape.global_batch,), jnp.int32,
        sharding=jax.sharding.NamedSharding(mesh, tok_spec))
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    return {
        "params": params_struct(cfg, mesh, train=False),
        "token": tok,
        "pos": pos,
        "cache": cache_struct(cfg, shape, mesh),
    }


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, *, algo="asgd", inner="sgd",
                    gcfg: GossipConfig | None = None,
                    acfg: ASGDConfig | None = None, remat=True,
                    spmd_axes=None, packed_resident=False, pack_spec=None,
                    pipelined=False, lr_schedule=None):
    """Returns step(params, gossip, opt_state, batch, key[, live])
            -> (params, gossip, opt_state, metrics).

    algo: 'asgd' (paper) | 'silent' (SimuParallelSGD) | 'sync' (BATCH).
    inner: 'sgd' (paper-faithful) | 'momentum' | 'adam' — beyond-paper
      inner optimizers; the gossip blends PARAMS only, never optimizer
      moments (cross-worker moment mixing is known-unstable). The inner
      optimizer produces the update direction dw fed to eq. (6) as
      Delta_M, so  w <- w - eps*(attraction + dw)  holds for all of them.
    spmd_axes: mesh axes the worker-vmap dim is sharded over — lets
      sharding hints inside the per-worker model (seq_parallel, MoE
      dispatch) compose with the vmap.
    packed_resident: carry the packed (W, R, LANE) ensemble across steps
      (DESIGN.md §6): ``params`` is the packed array, ``gossip`` a
      PackedGossipState (init_packed_gossip_state(packed, gcfg,
      block_rows=pack_spec.block_rows) — int8 zeros + zero scales under
      gcfg.wire_format="int8"), and the gossip round runs entirely on
      packed rows (asgd_gossip_apply_packed) — the forward pass reads
      unpacked VIEWS of the resident buffer (XLA fuses the reshape/slice
      into the consumers) and the only per-round packing is the gradient
      tree.  Requires ``pack_spec`` (a group-contiguous WPackSpec for
      'leaves' mode).

    Wire format / staleness: gcfg.wire_format selects what the gossip
    collective ships (DESIGN.md §6 wire formats — "int8" quantizes the
    exchanged block, wire bytes /4), and every algo='asgd' round applies
    the warm-up staleness guard (delay>0 init buffer slots are gated out
    explicitly by step rather than via eq.-3 zero detection).

    pipelined (DESIGN.md §7, requires packed_resident + algo='asgd' +
    gossip_every == 1): the gossip round becomes a one-round-deep
    pipeline — the step ISSUES this round's payload ppermute before the
    forward/backward (both read only the program's input ensemble, so the
    collective overlaps the compute) and BLENDS the payload launched
    delay+1 rounds ago (core.gossip consume_exchange_packed; ``gossip``
    is the init_pipelined_gossip_state FIFO).  The loss is differentiated
    directly w.r.t. the packed ensemble through unpack_rows views, so the
    gradient is BORN packed — the per-round pack_w(grads) full-state copy
    of the unpipelined packed step disappears (bitwise the same values:
    the VJP of the unpack views IS pack_w).

    Elastic liveness (DESIGN.md §8): every returned step accepts an
    optional trailing ``live`` (W,) 0/1 mask — requires a gossip state
    initialized with elastic=True and algo='asgd'.  Dead workers freeze
    (masked update direction), their payloads drop on the wire, and the
    FIFO slots they filled gate out of the eq.-6 mean via the existing
    gate_scale path.  lr_schedule (pipelined engine only): a callable
    ``step -> lr`` (optim.optimizers.lr_schedule) evaluated each round
    on the gossip step counter and fed to the consume blend's per-round
    lr operand; None keeps the static acfg.eps.
    """
    from ..optim import (adam_update, momentum_update)

    gcfg = gcfg or GossipConfig()
    acfg = acfg or ASGDConfig(eps=0.01)
    if packed_resident and pack_spec is None:
        raise ValueError("packed_resident=True requires pack_spec "
                         "(core.packing.pack_spec_w)")
    if pipelined:
        if not packed_resident:
            raise ValueError("pipelined=True requires packed_resident=True")
        if algo != "asgd":
            raise ValueError(
                f"pipelined=True requires algo='asgd' (got {algo!r}): the "
                "pipeline overlaps the gossip exchange — sync/silent have "
                "no exchange to overlap")
        if gcfg.gossip_every > 1:
            raise ValueError(
                "pipelined=True requires gossip_every == 1 (the split "
                "initiate/consume step has no off-round branch; use "
                "core.gossip.asgd_gossip_apply_pipelined for interval "
                "gossip)")
    if lr_schedule is not None and not pipelined:
        raise ValueError(
            "lr_schedule= is only wired into the pipelined engine "
            "(pipelined=True): its consume step takes a per-round lr "
            "operand; the other engines read the static acfg.eps")

    def per_worker_loss(p, b):
        return M.loss_fn(cfg, p, b, remat=remat)

    vmap_kw = {}
    if spmd_axes:
        vmap_kw["spmd_axis_name"] = spmd_axes

    def direction(params, grads, opt_state):
        """(dw, new_opt_state): w - eps*dw is the inner-optimizer step."""
        if inner == "sgd":
            return grads, opt_state
        if inner == "momentum":
            new_p, new_s = momentum_update(params, grads, opt_state,
                                           acfg.eps)
            dw = jax.tree.map(lambda w, n: (w - n) / acfg.eps,
                              params, new_p)
            return dw, new_s
        new_p, new_s = adam_update(params, grads, opt_state, acfg.eps)
        dw = jax.tree.map(lambda w, n: (w - n) / acfg.eps, params, new_p)
        return dw, new_s

    def step(params, gossip, opt_state, batch, key, live=None):
        if live is not None and algo != "asgd":
            raise ValueError(
                f"live= (peer liveness, DESIGN.md §8) requires algo='asgd' "
                f"(got {algo!r}): sync/silent carry no gossip state to gate")
        loss, grads = jax.vmap(jax.value_and_grad(per_worker_loss),
                               **vmap_kw)(params, batch)
        dw, opt_state = direction(params, grads, opt_state)
        if algo == "sync":
            new_params = sync_dp_apply(params, dw, acfg.eps)
            new_gossip = gossip
            metrics = {"loss": jnp.mean(loss)}
        elif algo == "silent":
            new_params = local_sgd_apply(params, dw, acfg.eps)
            new_gossip = gossip
            metrics = {"loss": jnp.mean(loss)}
        else:
            new_params, new_gossip, gm = asgd_gossip_apply(
                params, dw, gossip, key, gcfg, acfg, live=live)
            metrics = {"loss": jnp.mean(loss), "n_good": gm["n_good"],
                       "gate": gm["gate"]}
        return new_params, new_gossip, opt_state, metrics

    if not packed_resident:
        return step

    from ..core.gossip import asgd_gossip_apply_packed
    from ..core.packing import pack_w, unpack_w

    if pipelined:
        from ..core.gossip import (_silent_round, consume_exchange_packed,
                                   initiate_exchange_packed)
        from ..core.packing import unpack_rows

        def pipelined_step(packed, gossip, opt_state, batch, key, live=None):
            lr = None if lr_schedule is None else lr_schedule(gossip.step)
            # 1. INITIATE: launch this round's payload from the program
            #    input — the ppermute shares no dependency with the
            #    forward/backward below, so it runs concurrently with it
            if not acfg.silent:
                if live is None:
                    sent, sent_scales, block_idx = initiate_exchange_packed(
                        packed, key, gcfg, pack_spec)
                    sent_live = None
                else:
                    sent, sent_scales, block_idx, sent_live = \
                        initiate_exchange_packed(packed, key, gcfg,
                                                 pack_spec, live=live)

            # 2. forward/backward, differentiated w.r.t. the PACKED rows:
            #    the unpack views fuse into the consumers and the VJP
            #    accumulates the gradient directly in packed layout
            def loss_of_rows(rows2d, b):
                return per_worker_loss(unpack_rows(rows2d, pack_spec), b)

            loss, pgrads = jax.vmap(jax.value_and_grad(loss_of_rows),
                                    **vmap_kw)(packed, batch)
            dw, opt_state = direction(packed, pgrads, opt_state)

            if acfg.silent:
                # SimuParallelSGD ablation: pure local step, nothing on
                # the wire, FIFO untouched — the shared silent-round body
                new_packed, new_gossip, gm = _silent_round(
                    packed, dw, gossip, acfg.eps if lr is None else lr,
                    live=live)
                metrics = {"loss": jnp.mean(loss), **gm}
                return new_packed, new_gossip, opt_state, metrics

            # 3. CONSUME: fused blend + eq.-1 update of the payload
            #    launched delay+1 rounds ago; push this round's launch
            new_packed, new_gossip, gm = consume_exchange_packed(
                packed, dw, gossip, sent, sent_scales, block_idx, gcfg,
                acfg, pack_spec, lr=lr, sent_live=sent_live, live=live)
            metrics = {"loss": jnp.mean(loss), "n_good": gm["n_good"],
                       "gate": gm["gate"]}
            return new_packed, new_gossip, opt_state, metrics

        return pipelined_step

    def packed_step(packed, gossip, opt_state, batch, key, live=None):
        if live is not None and algo != "asgd":
            raise ValueError(
                f"live= (peer liveness, DESIGN.md §8) requires algo='asgd' "
                f"(got {algo!r}): sync/silent carry no gossip state to gate")
        params = unpack_w(packed, pack_spec)   # views of the resident buf
        loss, grads = jax.vmap(jax.value_and_grad(per_worker_loss),
                               **vmap_kw)(params, batch)
        dw, opt_state = direction(params, grads, opt_state)
        pdw = pack_w(dw, pack_spec)            # the one pack per round
        if algo == "sync":
            gmean = jnp.mean(pdw, axis=0, keepdims=True)
            new_packed = packed - acfg.eps * jnp.broadcast_to(
                gmean, packed.shape)
            new_gossip = gossip
            metrics = {"loss": jnp.mean(loss)}
        elif algo == "silent":
            new_packed = packed - acfg.eps * pdw
            new_gossip = gossip
            metrics = {"loss": jnp.mean(loss)}
        else:
            new_packed, new_gossip, gm = asgd_gossip_apply_packed(
                packed, pdw, gossip, key, gcfg, acfg, pack_spec, live=live)
            metrics = {"loss": jnp.mean(loss), "n_good": gm["n_good"],
                       "gate": gm["gate"]}
        return new_packed, new_gossip, opt_state, metrics

    return packed_step


def init_inner_state(params, inner="sgd"):
    from ..optim import adam_init, momentum_init
    if inner == "sgd":
        return jnp.int32(0)  # stateless placeholder
    if inner == "momentum":
        return momentum_init(params)
    return adam_init(params)


def make_prefill_step(cfg: ModelConfig):
    import dataclasses as _dc
    # serve batches shard over `data`; batch-sharded attention is a
    # train-path optimization (worker-local batch over `model`)
    cfg = _dc.replace(cfg, attn_batch_shard=False, seq_parallel=False)

    def step(params, batch):
        last_logits, cache = M.prefill(cfg, params, batch)
        return last_logits, cache
    return step


def make_decode_step(cfg: ModelConfig):
    import dataclasses as _dc
    cfg = _dc.replace(cfg, attn_batch_shard=False, seq_parallel=False)

    def step(params, token, pos, cache):
        return M.decode_step(cfg, params, token, pos, cache)
    return step


def step_and_args(cfg: ModelConfig, shape: ShapeConfig, mesh,
                  gcfg: GossipConfig | None = None, algo="asgd",
                  engine: str = "pytree"):
    """(callable, kwargs-of-ShapeDtypeStructs) for jit().lower(**kwargs).

    engine selects the train formulation (ENGINES): 'packed'/'pipelined'
    route through make_train_step(packed_resident=True[, pipelined=True])
    on the struct-derived pack spec, so the dry-run lowers and costs the
    resident engines end-to-end (DESIGN.md §6/§7)."""
    specs = input_specs(cfg, shape, mesh, gcfg, engine=engine)
    if shape.kind == "train":
        wa = data_axes(mesh)
        spmd = wa if len(wa) > 1 else wa[0]
        if engine != "pytree":
            spec = packed_spec_for(cfg, mesh, gcfg or GossipConfig())
            fn = make_train_step(cfg, algo=algo, gcfg=gcfg, spmd_axes=spmd,
                                 packed_resident=True, pack_spec=spec,
                                 pipelined=engine == "pipelined")
        else:
            fn = make_train_step(cfg, algo=algo, gcfg=gcfg, spmd_axes=spmd)
        return fn, specs  # params, gossip, batch, key
    if shape.kind == "prefill":
        return make_prefill_step(cfg), specs
    return make_decode_step(cfg), specs

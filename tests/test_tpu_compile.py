"""Compile the main path's kernels for a described TPU v5e, no chip needed.

The TPU compiler is installed with jaxlib, and it compiles for a topology
that is described rather than attached.  These compiles catch what the
interpret-mode tests cannot: blocks that break the (8, 128) tiling rule,
layouts Mosaic rejects, scoped-VMEM overruns, and a Pallas call that GSPMD
would have to partition.  Shapes are smollm-135m's at published widths:
W = 4 workers of 262,848 packed rows of 512 lanes each.

The topology is described inside a fixture, never while a module is
imported: one process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

import repro.kernels
from repro.configs.registry import get_arch
from repro.core.asgd import ASGDConfig
from repro.core.gossip import (GossipConfig, asgd_gossip_apply_pipelined,
                               init_pipelined_gossip_state, leaf_groups)
from repro.core.packing import pack_spec_w
from repro.kernels import LANE
from repro.kernels.gossip_blend.kernel import (gossip_apply_w_resident_pallas,
                                               gossip_reduce_w_resident_pallas)
from repro.launch.mesh import make_host_mesh
from repro.models import model as M

W, P, BLOCK_ROWS = 4, 1, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler otherwise writes its logs under the temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smollm_spec():
    """Group-contiguous pack spec of W smollm-135m replicas (shapes only)."""
    p1 = jax.eval_shape(lambda: M.init_model(get_arch("smollm-135m"),
                                             jax.random.key(0)))
    wp = jax.tree.map(lambda s: jax.ShapeDtypeStruct((W,) + s.shape,
                                                     s.dtype), p1)
    return pack_spec_w(wp, block_rows=BLOCK_ROWS, groups=leaf_groups(wp, 4),
                       n_groups=4)


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("wire", ["f32", "int8"])
@pytest.mark.parametrize("kernel_pass", ["reduce", "apply"])
def test_resident_pass_compiles_at_real_width(one_chip, smollm_spec,
                                              no_compile_cache, kernel_pass,
                                              wire):
    rows = smollm_spec.rows
    assert 260_000 < rows < 265_000

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = s((W, rows, LANE))
    ext = s((W, P, rows, LANE), jnp.int8 if wire == "int8" else jnp.float32)
    scales = (s((W, P, rows // BLOCK_ROWS)),) if wire == "int8" else ()
    row_range = s((2,), jnp.int32)
    if kernel_pass == "reduce":
        def fn(rr, w, dw, e, *sc):
            return gossip_reduce_w_resident_pallas(
                rr, w, dw, e, *sc, block_rows=BLOCK_ROWS, interpret=False)
        args = (row_range, state, state, ext, *scales)
    else:
        def fn(rr, w, dw, e, g, inv, lr, *sc):
            return gossip_apply_w_resident_pallas(
                rr, w, dw, e, g, inv, lr, *sc, block_rows=BLOCK_ROWS,
                interpret=False)
        args = (row_range, state, state, ext, s((W, P)), s((W,)), s(()),
                *scales)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pipelined_round_partitions_over_four_chips(topo, smollm_spec,
                                                    no_compile_cache,
                                                    monkeypatch):
    """The pipelined round with the worker axis split over a 4-chip mesh:
    the exchange lowers to collective-permute and the kernel runs per chip
    (shard_map) — GSPMD alone refuses to partition a Mosaic kernel."""
    # this process's backend is the CPU: steer the kernels to compile
    monkeypatch.setattr(repro.kernels, "_has_tpu_backend", lambda: True)
    mesh = make_host_mesh(data=4, model=1, devices=topo.devices)
    split = NamedSharding(mesh, PartitionSpec("data"))
    fifo = NamedSharding(mesh, PartitionSpec(None, "data"))
    rep = NamedSharding(mesh, PartitionSpec())
    gcfg = GossipConfig(shifts=(1, 2), partial_blocks=4, delay=1,
                        wire_format="int8")
    acfg = ASGDConfig(eps=0.05)
    packed = jax.ShapeDtypeStruct((W, smollm_spec.rows, LANE), jnp.float32,
                                  sharding=split)
    state = jax.eval_shape(
        lambda p: init_pipelined_gossip_state(p, gcfg,
                                              block_rows=BLOCK_ROWS), packed)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=fifo if x.ndim >= 2 else rep), state)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=rep)

    def round_fn(packed, pgrads, state, key):
        return asgd_gossip_apply_pipelined(packed, pgrads, state, key, gcfg,
                                           acfg, smollm_spec)

    with jax.sharding.set_mesh(mesh):
        compiled = jax.jit(round_fn).lower(packed, packed, state,
                                           key).compile()
    hlo = compiled.as_text()
    assert "collective-permute" in hlo
    assert "tpu_custom_call" in hlo
    # the device trace names each pass by its instruction, which takes the
    # pallas_call's name: the benchmark's rooflines find them by it
    for name in ("gossip_reduce_w_resident_pallas",
                 "gossip_apply_w_resident_pallas"):
        assert re.search(rf"^\s*%{name}(\.\d+)? = .*custom-call", hlo,
                         re.M), name


def test_attention_kernels_compile_at_real_width(one_chip, no_compile_cache):
    """smollm-135m's attention layer as the train step runs it (W replicas
    under vmap, the layer under checkpoint, S 2048), forward and backward:
    lowered for the chip, the layer takes the fused kernels, and no loop of
    the jnp scan is left."""
    from repro.kernels.flash_attention import block_sizes
    from repro.models import blocks
    from repro.models.common import init_attention

    cfg = get_arch("smollm-135m")
    spec = blocks.attn_spec(cfg)
    S = 2048
    assert (spec.n_heads, spec.n_kv_heads, spec.head_dim) == (9, 3, 64)
    pos = jnp.broadcast_to(jnp.arange(S), (1, S))

    def loss(p, h):
        layer = jax.checkpoint(lambda p, h: blocks._attend_full(
            cfg, spec, p, h, pos, "G", 0))
        return jnp.sum(jax.vmap(layer, in_axes=(None, 0))(p, h) ** 2)

    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_attention(jax.random.key(0), spec)))
    h = jax.ShapeDtypeStruct((W, 1, S, cfg.d_model), jnp.float32,
                             sharding=one_chip)
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, h).compile().as_text()
    bs = block_sizes(S, spec.head_dim)
    kernels = ("flash_mha_fwd",
               f"flash_mha_bwd_dkv_block_q_major_{bs.block_q_major_dkv}",
               f"flash_mha_bwd_dq_block_q_major_{bs.block_q_dq}")
    for name in kernels:
        assert re.search(rf"^\s*%[\w.]*{name}[\w.]* = .*tpu_custom_call",
                         hlo, re.M), name
    assert not re.search(r"\bwhile\(", hlo)


@pytest.mark.parametrize("seq,heads,head_dim", [(4096, 8, 128),
                                                (2048, 4, 256)])
def test_attention_kernels_compile_for_wider_heads(one_chip, no_compile_cache,
                                                   seq, heads, head_dim):
    """The other plain-causal models' heads (qwen, phi-3.5-moe: 128;
    gemma-3's global layers: 256) fit the kernels' tiles in VMEM."""
    from repro.kernels.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct((1, seq, heads, head_dim), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=False)
                       .astype(jnp.float32))
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    for name in ("flash_mha_fwd", "flash_mha_bwd_dkv", "flash_mha_bwd_dq"):
        assert re.search(rf"^\s*%[\w.]*{name}[\w.]* = .*tpu_custom_call",
                         hlo, re.M), name


def test_attention_kernels_run_per_chip_on_a_worker_split(topo,
                                                          no_compile_cache):
    """With the workers split over four chips (the train step's vmap over
    workers names the mesh's data axis), each chip runs the kernels on its
    own worker: no all-gather, where GSPMD alone refuses the Mosaic call."""
    from repro.models import blocks
    from repro.models.common import init_attention

    cfg = get_arch("smollm-135m")
    spec = blocks.attn_spec(cfg)
    S = 2048
    mesh = make_host_mesh(data=4, model=1, devices=topo.devices)
    rep = NamedSharding(mesh, PartitionSpec())
    pos = jnp.broadcast_to(jnp.arange(S), (1, S))

    def loss(p, h):
        layer = jax.checkpoint(lambda p, h: blocks._attend_full(
            cfg, spec, p, h, pos, "G", 0))
        return jnp.sum(jax.vmap(layer, in_axes=(None, 0),
                                spmd_axis_name="data")(p, h) ** 2)

    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        jax.eval_shape(lambda: init_attention(jax.random.key(0), spec)))
    h = jax.ShapeDtypeStruct((W, 1, S, cfg.d_model), jnp.float32,
                             sharding=NamedSharding(mesh,
                                                    PartitionSpec("data")))
    with jax.sharding.set_mesh(mesh):
        hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, h).compile().as_text()
    fwd = re.search(r"^\s*%[\w.]*flash_mha_fwd[\w.]* = \(bf16\[([\d,]+)\]",
                    hlo, re.M)
    assert fwd and fwd.group(1) == f"1,{spec.n_heads},{S},{spec.head_dim}"
    assert "flash_mha_bwd_dkv" in hlo and "flash_mha_bwd_dq" in hlo
    assert not re.search(r"\ball-gather\(", hlo)

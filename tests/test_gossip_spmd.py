"""SPMD gossip tests.

Numeric behaviour is tested in-process on a single device (the gossip math is
device-count independent — the worker axis is just a batch axis). The
sharded-lowering properties (collective-permute only, no all-gather of
model-sharded leaves) run in a subprocess with 8 fake devices so the main
pytest process keeps the default 1-device view.
"""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.asgd import ASGDConfig
from repro.core.gossip import (GossipConfig, asgd_gossip_apply, exchange_rows,
                               final_average, init_gossip_state, leaf_groups,
                               local_sgd_apply, slice_rows, sync_dp_apply,
                               update_rows)


def make_params(W=4, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return {
        "wq": jax.random.normal(ks[0], (W, 16, 8)),
        "bias": jax.random.normal(ks[1], (W, 6)),
        "wo": jax.random.normal(ks[2], (W, 8, 4)),
    }


class TestLeafGroups:
    def test_partition_covers_all_leaves_balanced(self):
        params = make_params()
        groups = leaf_groups(params, 2)
        gids = jax.tree.leaves(groups)
        assert set(gids) <= {0, 1}
        # the two big leaves (16*8=128, 8*4=32 per worker) must split
        assert groups["wq"] != groups["wo"] or groups["bias"] != groups["wq"]

    def test_deterministic(self):
        params = make_params()
        assert leaf_groups(params, 4) == leaf_groups(params, 4)


class TestRowsSlicing:
    def test_slice_update_roundtrip(self):
        params = make_params()
        for p in (1, 2, 4):
            for idx in range(p):
                blk = slice_rows(params, jnp.int32(idx), p)
                rebuilt = update_rows(params, blk, jnp.int32(idx), p)
                for k in params:
                    np.testing.assert_allclose(rebuilt[k], params[k])

    def test_exchange_rows_is_roll(self):
        params = make_params()
        cfg = GossipConfig(shifts=(1, 2), partial_mode="rows")
        blk = slice_rows(params, jnp.int32(0), cfg.partial_blocks)
        out = exchange_rows(blk, jnp.int32(0), cfg)  # shift=1
        for k in blk:
            np.testing.assert_allclose(out[k], jnp.roll(blk[k], 1, axis=0))


class TestGossipApply:
    def _run(self, mode, steps=8, silent=False, delay=1, W=4):
        params = make_params(W=W)
        gcfg = GossipConfig(shifts=(1, 2), partial_blocks=2,
                            partial_mode=mode, delay=delay)
        acfg = ASGDConfig(eps=0.05, silent=silent)
        state = init_gossip_state(params, gcfg)
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        for i in range(steps):
            params, state, metrics = asgd_gossip_apply(
                params, grads, state, jax.random.key(i), gcfg, acfg)
        return params, metrics

    @pytest.mark.parametrize("mode", ["leaves", "rows"])
    def test_shapes_preserved_and_finite(self, mode):
        params, metrics = self._run(mode)
        ref = make_params()
        for k in ref:
            assert params[k].shape == ref[k].shape
            assert jnp.all(jnp.isfinite(params[k]))
        assert metrics["gate"].shape == (4,)

    @pytest.mark.parametrize("mode", ["leaves", "rows"])
    def test_silent_equals_local_sgd(self, mode):
        """paper Fig. 14: silent ASGD must follow SimuParallelSGD exactly."""
        params0 = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params0)
        got, _ = self._run(mode, steps=5, silent=True)
        expect = params0
        for _ in range(5):
            expect = local_sgd_apply(expect, grads, 0.05)
        for k in expect:
            np.testing.assert_allclose(got[k], expect[k], rtol=1e-5)

    def test_gossip_contracts_worker_spread(self):
        """With zero gradients and forced-open gate... the Parzen gate never
        opens at dw=0 (stepping nowhere can't get closer), so instead use
        aligned gradients: workers starting apart must end up closer together
        than silent workers do (the attraction term contracts the ensemble).
        """
        W = 4
        params = {"w": jnp.arange(W, dtype=jnp.float32)[:, None, None]
                  * jnp.ones((W, 8, 4))}
        grads = {"w": jnp.ones((W, 8, 4)) * 0.1}
        gcfg = GossipConfig(shifts=(1,), partial_blocks=1,
                            partial_mode="leaves", delay=1)
        state = init_gossip_state(params, gcfg)

        def spread(p):
            return float(jnp.var(p["w"][:, 0, 0]))

        p_asgd = params
        for i in range(30):
            p_asgd, state, m = asgd_gossip_apply(
                p_asgd, grads, state, jax.random.key(i),
                gcfg, ASGDConfig(eps=0.05))
        p_silent = params
        for i in range(30):
            p_silent = local_sgd_apply(p_silent, grads, 0.05)
        assert spread(p_asgd) < spread(p_silent)

    def test_sync_dp_apply_identical_workers(self):
        params = make_params()
        grads = jax.tree.map(
            lambda x: x * 0.1, make_params(seed=9))
        out = sync_dp_apply(params, grads, 0.1)
        gm = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
        for k in params:
            np.testing.assert_allclose(
                out[k], params[k] - 0.1 * gm[k][None], rtol=1e-5)

    def test_final_average(self):
        params = make_params()
        avg = final_average(params)
        for k in params:
            np.testing.assert_allclose(
                avg[k][0], jnp.mean(params[k], axis=0), rtol=1e-6)
            # broadcast: all workers hold the aggregate
            np.testing.assert_allclose(avg[k][1], avg[k][0], rtol=1e-6)


class TestFusedApplyParity:
    """ISSUE-2 acceptance: asgd_gossip_apply with use_fused=True (the
    worker-batched gossip_blend kernel on the pack-once (W, R, LANE)
    layout) blends to the same states as the use_fused=False jnp
    tree-reduction path, within dtype tolerance."""

    def _run_pair(self, mode, *, delay=1, dtype=jnp.float32, steps=4, W=4,
                  partial_blocks=2, elastic=False):
        params0 = jax.tree.map(lambda x: x.astype(dtype), make_params(W=W))
        grads = jax.tree.map(lambda x: (0.05 * jnp.sign(x)).astype(dtype),
                             params0)
        gcfg = GossipConfig(shifts=(1, 2), partial_blocks=partial_blocks,
                            partial_mode=mode, delay=delay)
        outs = {}
        for fused in (False, True):
            acfg = ASGDConfig(eps=0.05, use_fused=fused, elastic=elastic)
            p, s = params0, init_gossip_state(params0, gcfg)
            for i in range(steps):
                p, s, m = asgd_gossip_apply(
                    p, grads, s, jax.random.key(i), gcfg, acfg)
            outs[fused] = (p, m)
        return outs

    @pytest.mark.parametrize("mode", ["leaves", "rows"])
    @pytest.mark.parametrize("delay", [0, 1])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_fused_matches_reference(self, mode, delay, dtype):
        outs = self._run_pair(mode, delay=delay, dtype=dtype)
        np.testing.assert_array_equal(
            np.asarray(outs[True][1]["gate"]),
            np.asarray(outs[False][1]["gate"]))
        tol = 1e-5 if dtype == jnp.float32 else 3e-2
        for k in outs[True][0]:
            assert outs[True][0][k].dtype == dtype
            np.testing.assert_allclose(
                np.asarray(outs[True][0][k], np.float32),
                np.asarray(outs[False][0][k], np.float32),
                rtol=tol, atol=tol)

    @pytest.mark.parametrize("mode", ["leaves", "rows"])
    def test_fused_elastic_matches_reference(self, mode):
        outs = self._run_pair(mode, elastic=True)
        for k in outs[True][0]:
            np.testing.assert_allclose(outs[True][0][k], outs[False][0][k],
                                       rtol=1e-5, atol=1e-6)

    def test_fused_unmasked_single_block(self):
        """partial_blocks=1 skips the partition mask entirely (every leaf
        is exchanged every round) — the mask-free kernel variant."""
        outs = self._run_pair("leaves", partial_blocks=1)
        for k in outs[True][0]:
            np.testing.assert_allclose(outs[True][0][k], outs[False][0][k],
                                       rtol=1e-5, atol=1e-6)

    def test_fused_silent_equals_local_sgd(self):
        params0 = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params0)
        gcfg = GossipConfig(shifts=(1,), partial_blocks=2)
        acfg = ASGDConfig(eps=0.05, silent=True, use_fused=True)
        p, s = params0, init_gossip_state(params0, gcfg)
        for i in range(3):
            p, s, _ = asgd_gossip_apply(p, grads, s, jax.random.key(i),
                                        gcfg, acfg)
        expect = params0
        for _ in range(3):
            expect = local_sgd_apply(expect, grads, 0.05)
        for k in expect:
            np.testing.assert_allclose(p[k], expect[k], rtol=1e-5)


SHARD_MAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import (local_worker_count, make_host_mesh,
                                   n_worker_groups, shard_map_workers)
    from repro.kernels.gossip_blend import gossip_blend_worker_batched
    from repro.core.packing import pack_spec_w, pack_w

    mesh = make_host_mesh(data=4, model=2)
    assert n_worker_groups(mesh) == 4
    assert local_worker_count(mesh, 8) == 2

    W = 8   # oversubscribed: 2 local workers per data shard
    ks = jax.random.split(jax.random.key(0), 2)
    params = {"a": jax.random.normal(ks[0], (W, 20, 30)),
              "b": jax.random.normal(ks[1], (W, 6))}
    grads = jax.tree.map(lambda x: 0.1 * x, params)
    ext = jax.tree.map(lambda x, d: x - 0.5 * d, params, grads)

    spec = pack_spec_w(params, block_rows=8)
    w3, d3 = pack_w(params, spec), pack_w(grads, spec)
    e4 = pack_w(ext, spec)[:, None]

    def blend(w3, d3, e4):
        return gossip_blend_worker_batched(w3, d3, e4, 0.05, block_rows=8)

    ref_out, ref_gates = jax.jit(blend)(w3, d3, e4)
    out, gates = jax.jit(shard_map_workers(blend, mesh))(w3, d3, e4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(gates), np.asarray(ref_gates))

    # 'leaves'-mode partition mask: worker-SHARED (R, LANE) operand, must
    # be replicated to every shard, not split along its row axis
    from repro.core.gossip import leaf_groups
    from repro.core.packing import pack_group_mask
    mask2 = pack_group_mask(leaf_groups(params, 2), jnp.int32(0), spec)

    def blend_masked(w3, d3, e4, m2):
        return gossip_blend_worker_batched(w3, d3, e4, 0.05, mask2d=m2,
                                           block_rows=8)

    ref_out_m, ref_gates_m = jax.jit(blend_masked)(w3, d3, e4, mask2)
    out_m, gates_m = jax.jit(shard_map_workers(
        blend_masked, mesh, replicated_argnums=(3,)))(w3, d3, e4, mask2)
    np.testing.assert_allclose(np.asarray(out_m), np.asarray(ref_out_m),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(gates_m),
                                  np.asarray(ref_gates_m))
    print("SHARD-MAP-OK")
""")


@pytest.mark.slow
def test_shard_map_worker_batched_kernel(run_cpu_script):
    """8-fake-device subprocess: the worker-batched Pallas blend under
    shard_map_workers (each data shard blends its 2 local worker replicas)
    matches the single-shard kernel result."""
    r = run_cpu_script(SHARD_MAP_SCRIPT, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SHARD-MAP-OK" in r.stdout


SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import re
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.core.gossip import GossipConfig, init_gossip_state, asgd_gossip_apply
    from repro.core.asgd import ASGDConfig

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    W = 4
    params = {"a": jnp.ones((W, 16, 8)), "b": jnp.zeros((W, 6)),
              "c": jnp.ones((W, 8, 4))}
    grads = jax.tree.map(lambda x: 0.01 * jnp.ones_like(x), params)
    gcfg = GossipConfig(shifts=(1, 2), partial_blocks=2,
                        partial_mode="leaves", delay=1)
    acfg = ASGDConfig(eps=0.1)
    state = init_gossip_state(params, gcfg)
    sh = {"a": NamedSharding(mesh, P("data", "model", None)),
          "b": NamedSharding(mesh, P("data", None)),
          "c": NamedSharding(mesh, P("data", None, "model"))}
    params = jax.device_put(params, sh)

    def step(params, grads, state, key):
        return asgd_gossip_apply(params, grads, state, key, gcfg, acfg)

    txt = jax.jit(step).lower(
        params, grads, state, jax.random.key(0)).compile().as_text()
    permutes = len(re.findall(r"collective-permute", txt))
    # all-gather of a model-sharded *param leaf* would be f32[1,16,8] etc.;
    # scalar gate reductions are fine. assert no big all-gathers.
    big_ag = [l for l in txt.splitlines()
              if re.search(r"all-gather[.\\d]* = f32\\[[^\\]]*(16,8|8,4)", l)]
    assert permutes > 0, "gossip must lower to collective-permute"
    assert not big_ag, "param leaves must not be all-gathered:" + str(big_ag)
    out = jax.jit(step)(params, grads, state, jax.random.key(0))
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(out[0]))
    print("SPMD-OK")
""")


@pytest.mark.slow
def test_spmd_lowering_collective_permute_only(run_cpu_script):
    """8-fake-device subprocess: gossip -> collective-permute, never an
    all-gather of a model-sharded param leaf."""
    r = run_cpu_script(SPMD_SCRIPT, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SPMD-OK" in r.stdout

"""Packed-resident gossip engine tests (ISSUE 3; DESIGN.md §6).

Covers the group-contiguous pack layout (pack_spec_w(groups=)), the
row-range resident kernel (scalar-prefetch mask), the packed round
(asgd_gossip_apply_packed) against the unfused jnp reference across
partial_mode x delay x dtype, the pack-aware checkpoint boundary, the
packed train step, and (subprocess, 8 fake devices) the manual-region
ppermute exchange of launch.mesh.shard_map_gossip_round against the GSPMD
jnp.roll formulation.
"""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.asgd import ASGDConfig
from repro.core.gossip import (GossipConfig, asgd_gossip_apply,
                               asgd_gossip_apply_packed, exchange_packed,
                               init_gossip_state, init_packed_gossip_state,
                               leaf_groups, packed_row_ranges)
from repro.core.packing import (LANE, group_ranges_array, pack_group_mask,
                                pack_spec_w, pack_w, unpack_w)
from repro.kernels.gossip_blend import (gossip_blend_w_resident,
                                        gossip_blend_worker_batched)


def make_params(W=4, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    return {
        "wq": jax.random.normal(ks[0], (W, 16, 8)).astype(dtype),
        "bias": jax.random.normal(ks[1], (W, 6)).astype(dtype),
        "wo": jax.random.normal(ks[2], (W, 8, 4)).astype(dtype),
    }


class TestGroupContiguousPacking:
    @given(st.integers(1, 5), st.integers(0, 4))
    @settings(max_examples=12, deadline=None)
    def test_roundtrip(self, p, seed):
        """pack_w -> unpack_w is the identity on the group-contiguous
        layout for any partition count (incl. p > #leaves: empty groups)."""
        params = make_params(seed=seed)
        spec = pack_spec_w(params, block_rows=2,
                           groups=leaf_groups(params, p), n_groups=p)
        got = unpack_w(pack_w(params, spec), spec)
        for k in params:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(params[k]), rtol=1e-6)
            assert got[k].dtype == params[k].dtype

    def test_ranges_block_aligned_and_disjoint(self):
        params = make_params()
        for p in (1, 2, 3):
            spec = pack_spec_w(params, block_rows=4,
                               groups=leaf_groups(params, p), n_groups=p)
            prev_end = 0
            for r0, r1 in spec.group_row_ranges:
                assert r0 % 4 == 0 and r1 % 4 == 0 and r0 == prev_end
                prev_end = r1
            assert prev_end <= spec.rows

    def test_group_rows_isolate_group_leaves(self):
        """Zeroing all rows outside group g's range zeroes exactly the
        non-g leaves — the property that makes the exchange a row slice."""
        params = make_params()
        p = 2
        groups = leaf_groups(params, p)
        spec = pack_spec_w(params, block_rows=2, groups=groups, n_groups=p)
        packed = pack_w(params, spec)
        for g in range(p):
            r0, r1 = spec.group_row_ranges[g]
            only_g = unpack_w(
                packed.at[:, :r0].set(0.0).at[:, r1:].set(0.0), spec)
            for k in params:
                if groups[k] == g:
                    np.testing.assert_allclose(np.asarray(only_g[k]),
                                               np.asarray(params[k]),
                                               rtol=1e-6)
                else:
                    assert float(jnp.abs(only_g[k]).max()) == 0.0

    def test_range_mask_matches_real_elements(self):
        """pack_group_mask on a group-contiguous spec covers the group's
        row range and nothing outside it."""
        params = make_params()
        p = 3
        groups = leaf_groups(params, p)
        spec = pack_spec_w(params, block_rows=2, groups=groups, n_groups=p)
        for g in range(p):
            m = pack_group_mask(groups, jnp.int32(g), spec)
            r0, r1 = spec.group_row_ranges[g]
            assert m.shape == (spec.rows, LANE)
            np.testing.assert_array_equal(
                np.asarray(m[r0:r1]), np.ones((r1 - r0, LANE)))
            assert float(jnp.sum(m)) == (r1 - r0) * LANE

    def test_plain_spec_has_no_ranges(self):
        params = make_params()
        spec = pack_spec_w(params, block_rows=2)
        assert spec.group_row_ranges is None
        with pytest.raises(ValueError):
            group_ranges_array(spec)
        with pytest.raises(ValueError):
            packed_row_ranges(spec, GossipConfig(partial_mode="leaves"))


class TestRowRangeResidentKernel:
    """gossip_blend_w_resident (scalar-prefetched row range) must agree
    with gossip_blend_worker_batched given the equivalent materialized
    (R, LANE) mask — including empty ranges (all gates closed)."""

    @pytest.mark.parametrize("rr", [(0, 16), (4, 12), (0, 4), (8, 8)])
    @pytest.mark.parametrize("elastic", [False, True])
    def test_matches_masked_kernel(self, rr, elastic):
        W, P, R, br = 3, 2, 16, 4
        ks = jax.random.split(jax.random.key(0), 2)
        w3 = jax.random.normal(ks[0], (W, R, LANE))
        d3 = jax.random.normal(ks[1], (W, R, LANE)) * 0.1
        e4 = w3[:, None] - 0.5 * d3[:, None] * jnp.arange(
            1, P + 1, dtype=jnp.float32)[None, :, None, None]
        rows = jnp.arange(R)
        m2 = jnp.broadcast_to(
            ((rows >= rr[0]) & (rows < rr[1]))
            .astype(jnp.float32)[:, None], (R, LANE))
        out_r, g_r = gossip_blend_w_resident(
            w3, d3, e4, jnp.asarray(rr, jnp.int32), 0.05, block_rows=br,
            elastic=elastic)
        out_m, g_m = gossip_blend_worker_batched(
            w3, d3, e4, 0.05, mask2d=m2, block_rows=br, elastic=elastic)
        np.testing.assert_array_equal(np.asarray(g_r), np.asarray(g_m))
        np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_m),
                                   rtol=1e-6, atol=1e-6)

    def test_empty_range_is_plain_sgd(self):
        W, R, br = 2, 8, 4
        w3 = jax.random.normal(jax.random.key(1), (W, R, LANE))
        d3 = 0.1 * jnp.ones_like(w3)
        out, gates = gossip_blend_w_resident(
            w3, d3, (w3 - d3)[:, None], jnp.asarray([3, 3], jnp.int32),
            0.05, block_rows=br)
        assert float(jnp.sum(gates)) == 0.0
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(w3 - 0.05 * d3),
                                   rtol=1e-6, atol=1e-6)


class TestPackedResidentParity:
    """ISSUE-3 acceptance: asgd_gossip_apply_packed on the resident packed
    ensemble blends to the same states as asgd_gossip_apply with
    use_fused=False (the unfused jnp tree reference), in both partial
    modes, without ever unpacking mid-run."""

    def _run_leaves(self, *, delay=1, dtype=jnp.float32, steps=5, W=4,
                    p=2, elastic=False, gossip_every=1):
        params0 = make_params(W=W, dtype=dtype)
        grads = jax.tree.map(lambda x: (0.05 * jnp.sign(x)).astype(dtype),
                             params0)
        gcfg = GossipConfig(shifts=(1, 2), partial_blocks=p,
                            partial_mode="leaves", delay=delay,
                            gossip_every=gossip_every)
        acfg = ASGDConfig(eps=0.05, elastic=elastic)
        spec = pack_spec_w(params0, block_rows=2,
                           groups=leaf_groups(params0, p), n_groups=p)
        p_ref, s_ref = params0, init_gossip_state(params0, gcfg)
        packed = pack_w(params0, spec)
        s_pk = init_packed_gossip_state(packed)
        pdw = pack_w(grads, spec)
        for i in range(steps):
            key = jax.random.key(i)
            p_ref, s_ref, m_ref = asgd_gossip_apply(
                p_ref, grads, s_ref, key, gcfg, acfg)
            packed, s_pk, m_pk = asgd_gossip_apply_packed(
                packed, pdw, s_pk, key, gcfg, acfg, spec)
        return p_ref, m_ref, unpack_w(packed, spec), m_pk

    @pytest.mark.parametrize("delay", [0, 1])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_leaves_mode_parity(self, delay, dtype):
        p_ref, m_ref, p_pk, m_pk = self._run_leaves(delay=delay,
                                                    dtype=dtype)
        if dtype == jnp.float32:
            np.testing.assert_array_equal(np.asarray(m_pk["gate"]),
                                          np.asarray(m_ref["gate"]))
        tol = 1e-5 if dtype == jnp.float32 else 3e-2
        for k in p_ref:
            assert p_pk[k].dtype == dtype
            np.testing.assert_allclose(
                np.asarray(p_pk[k], np.float32),
                np.asarray(p_ref[k], np.float32), rtol=tol, atol=tol)

    def test_leaves_mode_elastic_parity(self):
        p_ref, _, p_pk, _ = self._run_leaves(elastic=True)
        for k in p_ref:
            np.testing.assert_allclose(np.asarray(p_pk[k]),
                                       np.asarray(p_ref[k]),
                                       rtol=1e-5, atol=1e-6)

    @pytest.mark.slow
    def test_gossip_every_parity(self):
        p_ref, m_ref, p_pk, m_pk = self._run_leaves(gossip_every=2,
                                                    steps=5)
        np.testing.assert_array_equal(np.asarray(m_pk["gate"]),
                                      np.asarray(m_ref["gate"]))
        for k in p_ref:
            np.testing.assert_allclose(np.asarray(p_pk[k]),
                                       np.asarray(p_ref[k]),
                                       rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("delay", [0, 1])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_rows_mode_parity(self, delay, dtype):
        """'rows' mode on the packed layout partitions the packed rows; for
        a single 2-D leaf with a block-aligned width the packed chunks
        coincide elementwise with the reference's axis-1 slices, so parity
        is exact."""
        W, rows, p = 4, 8, 2
        N = rows * LANE
        w = jax.random.normal(jax.random.key(5), (W, N)).astype(dtype)
        params0, grads = {"w": w}, {"w": (0.05 * jnp.sign(w)).astype(dtype)}
        gcfg = GossipConfig(shifts=(1, 2), partial_blocks=p,
                            partial_mode="rows", delay=delay)
        acfg = ASGDConfig(eps=0.05)
        spec = pack_spec_w(params0, block_rows=4)
        assert packed_row_ranges(spec, gcfg) == ((0, 4), (4, 8))
        p_ref, s_ref = params0, init_gossip_state(params0, gcfg)
        packed = pack_w(params0, spec)
        s_pk = init_packed_gossip_state(packed)
        pdw = pack_w(grads, spec)
        for i in range(5):
            key = jax.random.key(i)
            p_ref, s_ref, m_ref = asgd_gossip_apply(
                p_ref, grads, s_ref, key, gcfg, acfg)
            packed, s_pk, m_pk = asgd_gossip_apply_packed(
                packed, pdw, s_pk, key, gcfg, acfg, spec)
        if dtype == jnp.float32:
            np.testing.assert_array_equal(np.asarray(m_pk["gate"]),
                                          np.asarray(m_ref["gate"]))
        tol = 1e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(unpack_w(packed, spec)["w"], np.float32),
            np.asarray(p_ref["w"], np.float32), rtol=tol, atol=tol)

    def test_silent_equals_local_sgd(self):
        params0 = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params0)
        gcfg = GossipConfig(shifts=(1,), partial_blocks=2)
        acfg = ASGDConfig(eps=0.05, silent=True)
        spec = pack_spec_w(params0, block_rows=2,
                           groups=leaf_groups(params0, 2), n_groups=2)
        packed = pack_w(params0, spec)
        s_pk = init_packed_gossip_state(packed)
        pdw = pack_w(grads, spec)
        for i in range(3):
            packed, s_pk, _ = asgd_gossip_apply_packed(
                packed, pdw, s_pk, jax.random.key(i), gcfg, acfg, spec)
        got = unpack_w(packed, spec)
        for k in params0:
            np.testing.assert_allclose(
                np.asarray(got[k]),
                np.asarray(params0[k] - 3 * 0.05 * grads[k]),
                rtol=1e-5, atol=1e-6)

    def test_exchange_packed_moves_only_range(self):
        """The exchanged buffer is a worker-roll of the partition's rows
        and zero everywhere else (nothing else was sent)."""
        params = make_params()
        p = 2
        gcfg = GossipConfig(shifts=(1, 2), partial_blocks=p)
        spec = pack_spec_w(params, block_rows=2,
                           groups=leaf_groups(params, p), n_groups=p)
        packed = pack_w(params, spec)
        ranges = packed_row_ranges(spec, gcfg)
        for si, s in enumerate(gcfg.shifts):
            for g in range(p):
                sent = exchange_packed(packed, ranges, jnp.int32(si),
                                       jnp.int32(g), gcfg)
                r0, r1 = ranges[g]
                np.testing.assert_allclose(
                    np.asarray(sent[:, r0:r1]),
                    np.asarray(jnp.roll(packed[:, r0:r1], s, axis=0)),
                    rtol=1e-6)
                assert float(jnp.abs(sent[:, :r0]).max(initial=0.0)) == 0.0
                assert float(jnp.abs(sent[:, r1:]).max(initial=0.0)) == 0.0


class TestAsgdUpdatePacked:
    """core.asgd.asgd_update_packed (the single-worker pack-aware entry
    point) must agree with asgd_update_fused minus the pack/unpack
    boundary, and with the pytree reference."""

    def test_matches_fused_and_reference(self):
        from repro.core.asgd import (asgd_update, asgd_update_packed)
        from repro.core.packing import pack, pack_spec, unpack

        tree = {"a": jax.random.normal(jax.random.key(0), (40, 30)),
                "b": jax.random.normal(jax.random.key(1), (17,))}
        dw = jax.tree.map(lambda x: 0.1 * jnp.sign(x), tree)
        exts = [jax.tree.map(lambda x, d: x - 0.4 * (i + 1) * d, tree, dw)
                for i in range(3)]
        cfg = ASGDConfig(eps=0.05)
        spec = pack_spec(tree, block_rows=4)
        w2 = pack(tree, spec)
        d2 = pack(dw, spec)
        e3 = jnp.stack([pack(e, spec) for e in exts])
        out2, n_good = asgd_update_packed(w2, d2, e3, cfg, block_rows=4)
        ref, n_good_ref = asgd_update(tree, dw, exts, cfg)
        assert float(n_good) == float(n_good_ref)
        got = unpack(out2, spec)
        for k in tree:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(ref[k]),
                                       rtol=1e-5, atol=1e-6)

    def test_silent_and_empty_externals(self):
        from repro.core.asgd import asgd_update_packed
        from repro.core.packing import pack, pack_spec

        tree = {"a": jnp.ones((8, 4))}
        spec = pack_spec(tree, block_rows=2)
        w2 = pack(tree, spec)
        d2 = 0.1 * jnp.ones_like(w2)
        out, n = asgd_update_packed(
            w2, d2, jnp.zeros((0,) + w2.shape), ASGDConfig(eps=0.5))
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(w2 - 0.5 * d2), rtol=1e-6)
        assert float(n) == 0.0


class TestPackedCheckpoint:
    def test_packed_checkpoint_roundtrip_and_interop(self, tmp_path):
        """save_checkpoint_packed writes the CANONICAL pytree layout: it
        restores into both the packed and the unpacked state forms."""
        from repro.checkpoint import (load_checkpoint,
                                      load_checkpoint_packed,
                                      save_checkpoint_packed)

        params = make_params()
        p = 2
        gcfg = GossipConfig(shifts=(1,), partial_blocks=p)
        spec = pack_spec_w(params, block_rows=2,
                           groups=leaf_groups(params, p), n_groups=p)
        packed = pack_w(params, spec)
        gossip = init_packed_gossip_state(packed)
        ranges = packed_row_ranges(spec, gcfg)
        gossip.buf = exchange_packed(packed, ranges, jnp.int32(0),
                                     jnp.int32(1), gcfg)
        gossip.buf_idx = jnp.int32(1)
        state = {"params": packed, "gossip": gossip, "opt": jnp.int32(0),
                 "step": jnp.int32(7)}
        path = tmp_path / "ck.msgpack"
        save_checkpoint_packed(path, state, spec)

        # packed -> packed roundtrip
        like = {"params": jnp.zeros_like(packed),
                "gossip": init_packed_gossip_state(packed),
                "opt": jnp.int32(0), "step": jnp.int32(0)}
        back = load_checkpoint_packed(path, like, spec)
        np.testing.assert_allclose(np.asarray(back["params"]),
                                   np.asarray(packed), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(back["gossip"].buf),
                                   np.asarray(gossip.buf), rtol=1e-6)
        assert int(back["gossip"].buf_idx) == 1 and int(back["step"]) == 7

        # packed checkpoint loads into the UNPACKED state structure too
        like_plain = {"params": params,
                      "gossip": init_gossip_state(params, gcfg),
                      "opt": jnp.int32(0), "step": jnp.int32(0)}
        plain = load_checkpoint(path, like_plain)
        for k in params:
            np.testing.assert_allclose(np.asarray(plain["params"][k]),
                                       np.asarray(params[k]), rtol=1e-6)

    @pytest.mark.parametrize("w_new", [2, 8])
    def test_elastic_worker_count_migration(self, tmp_path, w_new):
        """A packed checkpoint saved at W=4 restores at W=2 / W=8 via
        load_checkpoint_packed(elastic=True): the unpacked pytree of the
        restored ensemble equals resize_worker_axis of the saved
        canonical tree FLOAT-EXACTLY (shrink slices the leading axis,
        grow tiles it cyclically; the per-worker row layout is
        W-invariant, so only the worker axis moves), the step counter
        survives, and the restored state keeps the elastic init's zero
        liveness mask — every worker re-enters through the join window
        (DESIGN.md §8)."""
        from repro.checkpoint import (load_checkpoint_packed,
                                      save_checkpoint_packed)
        from repro.core.packing import resize_worker_axis

        W, p = 4, 2
        params = make_params(W=W)
        gcfg = GossipConfig(shifts=(1,), partial_blocks=p)
        spec = pack_spec_w(params, block_rows=2,
                           groups=leaf_groups(params, p), n_groups=p)
        packed = pack_w(params, spec)
        gossip = init_packed_gossip_state(packed)
        ranges = packed_row_ranges(spec, gcfg)
        gossip.buf = exchange_packed(packed, ranges, jnp.int32(0),
                                     jnp.int32(1), gcfg)
        state = {"params": packed, "gossip": gossip, "opt": jnp.int32(0),
                 "step": jnp.int32(3)}
        path = tmp_path / "w4.msgpack"
        save_checkpoint_packed(path, state, spec)

        params_new = make_params(W=w_new)   # same per-worker shapes
        spec_new = pack_spec_w(params_new, block_rows=2,
                               groups=leaf_groups(params_new, p),
                               n_groups=p)
        packed_new = pack_w(params_new, spec_new)
        like = {"params": jnp.zeros_like(packed_new),
                "gossip": init_packed_gossip_state(packed_new,
                                                   elastic=True),
                "opt": jnp.int32(0), "step": jnp.int32(0)}
        back = load_checkpoint_packed(path, like, spec_new, elastic=True)

        got = unpack_w(back["params"], spec_new)
        want = resize_worker_axis(params, w_new)
        for k in params:
            assert got[k].shape[0] == w_new
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))
        got_buf = unpack_w(back["gossip"].buf, spec_new)
        want_buf = resize_worker_axis(unpack_w(gossip.buf, spec), w_new)
        for k in params:
            np.testing.assert_array_equal(np.asarray(got_buf[k]),
                                          np.asarray(want_buf[k]))
        assert int(back["step"]) == 3
        np.testing.assert_array_equal(
            np.asarray(back["gossip"].buf_live),
            np.zeros((w_new,), np.float32))

    def test_non_elastic_restore_rejects_other_worker_count(self,
                                                            tmp_path):
        """Without elastic=True a worker-count mismatch stays a loud
        error — the migration path is opt-in."""
        from repro.checkpoint import (load_checkpoint_packed,
                                      save_checkpoint_packed)

        params = make_params(W=4)
        p = 2
        spec = pack_spec_w(params, block_rows=2,
                           groups=leaf_groups(params, p), n_groups=p)
        packed = pack_w(params, spec)
        state = {"params": packed,
                 "gossip": init_packed_gossip_state(packed),
                 "opt": jnp.int32(0), "step": jnp.int32(1)}
        path = tmp_path / "w4.msgpack"
        save_checkpoint_packed(path, state, spec)

        params2 = make_params(W=2)
        spec2 = pack_spec_w(params2, block_rows=2,
                            groups=leaf_groups(params2, p), n_groups=p)
        packed2 = pack_w(params2, spec2)
        like = {"params": packed2,
                "gossip": init_packed_gossip_state(packed2),
                "opt": jnp.int32(0), "step": jnp.int32(0)}
        with pytest.raises(ValueError, match="shape mismatch"):
            load_checkpoint_packed(path, like, spec2)


class TestPackedTrainStep:
    @pytest.mark.slow
    def test_packed_step_matches_pytree_step(self):
        """make_train_step(packed_resident=True) follows the pytree ASGD
        step (use_fused=False jnp reference) loss-for-loss on a reduced
        arch — the end-to-end threading check."""
        from repro.configs.registry import get_arch
        from repro.launch.steps import init_inner_state, make_train_step
        from repro.models import model as M

        cfg = get_arch("smollm-135m").reduced()
        W, B, S = 2, 1, 16
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (W,) + x.shape).copy(),
            M.init_model(cfg, jax.random.key(0)))
        tokens = jax.random.randint(jax.random.key(1), (W, B, S), 0,
                                    cfg.vocab)
        batch = {"tokens": tokens}
        gcfg = GossipConfig(shifts=(1,), partial_blocks=2)
        acfg = ASGDConfig(eps=0.01)
        spec = pack_spec_w(params, block_rows=8,
                           groups=leaf_groups(params, 2), n_groups=2)

        step_ref = make_train_step(cfg, algo="asgd", gcfg=gcfg, acfg=acfg)
        step_pk = make_train_step(cfg, algo="asgd", gcfg=gcfg, acfg=acfg,
                                  packed_resident=True, pack_spec=spec)
        p_ref, g_ref = params, init_gossip_state(params, gcfg)
        packed = pack_w(params, spec)
        g_pk = init_packed_gossip_state(packed)
        opt = init_inner_state(params)
        for i in range(2):
            key = jax.random.key(i)
            p_ref, g_ref, opt_r, m_ref = step_ref(p_ref, g_ref, opt,
                                                  batch, key)
            packed, g_pk, opt_p, m_pk = step_pk(packed, g_pk, opt,
                                                batch, key)
            np.testing.assert_allclose(float(m_pk["loss"]),
                                       float(m_ref["loss"]), rtol=1e-4)
        got = unpack_w(packed, spec)
        for kp, a in jax.tree_util.tree_leaves_with_path(got):
            b = dict(jax.tree_util.tree_leaves_with_path(p_ref))[kp]
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-2, atol=2e-3)


PPERMUTE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.asgd import ASGDConfig
    from repro.core.gossip import (GossipConfig, exchange_packed,
                                   leaf_groups, packed_row_ranges)
    from repro.core.packing import pack_spec_w, pack_w
    from repro.kernels.gossip_blend import gossip_blend_w_resident
    from repro.launch.mesh import make_host_mesh, shard_map_gossip_round

    mesh = make_host_mesh(data=4, model=2)
    W = 8   # oversubscribed: W_local = 2 -> the two-ppermute roll path
    ks = jax.random.split(jax.random.key(0), 2)
    params = {"a": jax.random.normal(ks[0], (W, 20, 30)),
              "b": jax.random.normal(ks[1], (W, 6))}
    grads = jax.tree.map(lambda x: 0.1 * x, params)
    gcfg = GossipConfig(shifts=(1, 2, 3, 5), partial_blocks=2,
                        partial_mode="leaves", delay=1)
    acfg = ASGDConfig(eps=0.05)
    spec = pack_spec_w(params, block_rows=8,
                       groups=leaf_groups(params, 2), n_groups=2)
    packed, pdw = pack_w(params, spec), pack_w(grads, spec)
    ranges = packed_row_ranges(spec, gcfg)
    buf = exchange_packed(packed, ranges, jnp.int32(0), jnp.int32(1), gcfg)

    round_m = jax.jit(shard_map_gossip_round(mesh, spec, gcfg, acfg,
                                             n_workers=W))
    rr = jnp.asarray(ranges, jnp.int32)[jnp.int32(1)]
    out_ref, gates_ref = gossip_blend_w_resident(
        packed, pdw, buf[:, None], rr, acfg.eps,
        block_rows=spec.block_rows)
    for si in range(4):
        for bi in range(2):
            # step=1: buf is a real received block, the round-1 staleness
            # guard must not close the gates
            out, sent, gates = round_m(packed, pdw, buf, jnp.int32(1),
                                       jnp.int32(1),
                                       jnp.int32(si), jnp.int32(bi))
            # the in-region ppermute exchange == the GSPMD jnp.roll one
            sent_ref = exchange_packed(packed, ranges, jnp.int32(si),
                                       jnp.int32(bi), gcfg)
            np.testing.assert_allclose(np.asarray(sent),
                                       np.asarray(sent_ref),
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(out_ref),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_array_equal(np.asarray(gates),
                                          np.asarray(gates_ref[:, 0]))
    txt = round_m.lower(packed, pdw, buf, jnp.int32(1), jnp.int32(1),
                        jnp.int32(0), jnp.int32(0)).compile().as_text()
    assert "collective-permute" in txt, "exchange must be collective-permute"
    print("PPERMUTE-ROUND-OK")
""")


@pytest.mark.slow
def test_shard_map_gossip_round_matches_gspmd_roll(run_cpu_script):
    """8-fake-device subprocess: the manual-region exchange+blend
    (ppermute + resident kernel inside ONE shard_map) reproduces the GSPMD
    jnp.roll exchange and the single-shard resident blend, for every
    static shift and partition."""
    r = run_cpu_script(PPERMUTE_SCRIPT, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "PPERMUTE-ROUND-OK" in r.stdout

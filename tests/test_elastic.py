"""Elastic fault-tolerance tests (ISSUE 7; DESIGN.md §8).

Covers the per-peer liveness gates across all three SPMD engines
(pytree / packed-resident / pipelined): the elastic-state contract
(live= requires elastic=True, live=ones is BITWISE the legacy run across
wire_format x delay), dead-peer parity across engines under a churn
schedule, the join window after an elastic worker-count restore
(checkpoint saved at one W, restored at another, gates closed until real
exchanges refill the FIFO), the chaos harness of the threaded GASPI
simulator (seeded kill/revive schedules, deterministic bitwise replay,
convergence within 1.5x of the stable run — the ISSUE acceptance), and
(subprocess, 8 fake devices, slow) the manual-region elastic round: a
masked ppermute payload is DROPPED, not blended, and the dead worker's
shard stays frozen mid-run.
"""
import dataclasses
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.asgd import ASGDConfig
from repro.core.async_sim import (AsyncSimConfig, make_kill_schedule,
                                  run_async_asgd)
from repro.core.gossip import (GossipConfig, asgd_gossip_apply,
                               asgd_gossip_apply_packed,
                               asgd_gossip_apply_pipelined,
                               consume_exchange_packed, init_gossip_state,
                               init_packed_gossip_state,
                               init_pipelined_gossip_state,
                               initiate_exchange_packed, leaf_groups)
from repro.core import kmeans
from repro.core.packing import pack_spec_w, pack_w, unpack_w


def make_params(W=4, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    return {
        "wq": jax.random.normal(ks[0], (W, 16, 8)).astype(dtype),
        "bias": jax.random.normal(ks[1], (W, 6)).astype(dtype),
        "wo": jax.random.normal(ks[2], (W, 8, 4)).astype(dtype),
    }


def make_spec(params, p=2):
    return pack_spec_w(params, block_rows=2,
                       groups=leaf_groups(params, p), n_groups=p)


def wire_cfg(wf, **kw):
    return GossipConfig(wire_format=wf,
                        payload_dtype=jnp.bfloat16 if wf == "dtype"
                        else None, **kw)


def churn_live(W, t, dead=1, t0=2, k=2):
    """The canonical test schedule: worker ``dead`` is down for rounds
    [t0, t0+k)."""
    live = np.ones(W, np.float32)
    if t0 <= t < t0 + k:
        live[dead] = 0.0
    return jnp.asarray(live)


class TestElasticStateContract:
    """buf_live exists iff the state was initialized elastic=True; passing
    live= into a non-elastic state is a loud error (a lazily appearing
    mask would change the carried pytree structure mid-jit)."""

    def test_pytree_requires_elastic_state(self):
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        gcfg = GossipConfig(shifts=(1,), partial_blocks=2)
        state = init_gossip_state(params, gcfg)
        assert state.buf_live is None
        with pytest.raises(ValueError, match="elastic=True"):
            asgd_gossip_apply(params, grads, state, jax.random.key(0),
                              gcfg, ASGDConfig(eps=0.05),
                              live=jnp.ones((4,), jnp.float32))

    def test_packed_and_pipelined_require_elastic_state(self):
        params = make_params()
        gcfg = GossipConfig(shifts=(1,), partial_blocks=2)
        acfg = ASGDConfig(eps=0.05)
        spec = make_spec(params)
        packed = pack_w(params, spec)
        pdw = 0.05 * jnp.sign(packed)
        ones = jnp.ones((4,), jnp.float32)
        st = init_packed_gossip_state(packed, gcfg)
        assert st.buf_live is None
        with pytest.raises(ValueError, match="elastic=True"):
            asgd_gossip_apply_packed(packed, pdw, st, jax.random.key(0),
                                     gcfg, acfg, spec, live=ones)
        st_p = init_pipelined_gossip_state(packed, gcfg)
        with pytest.raises(ValueError, match="elastic=True"):
            asgd_gossip_apply_pipelined(packed, pdw, st_p,
                                        jax.random.key(0), gcfg, acfg,
                                        spec, live=ones)

    def test_elastic_init_opens_with_closed_gates(self):
        """An elastic init's buf_live is ZEROS — the join window: the
        zero-init FIFO slot reads as dead until a real exchange fills
        it."""
        params = make_params()
        gcfg = GossipConfig(shifts=(1,), partial_blocks=2, delay=1)
        state = init_gossip_state(params, gcfg, elastic=True)
        np.testing.assert_array_equal(np.asarray(state.buf_live),
                                      np.zeros(4, np.float32))
        spec = make_spec(params)
        packed = pack_w(params, spec)
        st = init_packed_gossip_state(packed, gcfg, elastic=True)
        np.testing.assert_array_equal(np.asarray(st.buf_live),
                                      np.zeros(4, np.float32))


class TestLiveOnesIsBitwiseLegacy:
    """The liveness machinery composes to the IDENTITY when everyone is
    alive: elastic state + live=ones reproduces the legacy (non-elastic)
    run bitwise, across engine x wire_format x delay — the jnp-reference
    parity of the liveness gates."""

    @pytest.mark.parametrize("wf", [None, "dtype", "int8"])
    @pytest.mark.parametrize("delay", [0, 1, 2])
    def test_packed(self, wf, delay):
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        gcfg = wire_cfg(wf, shifts=(1, 2), partial_blocks=2, delay=delay)
        acfg = ASGDConfig(eps=0.05)
        spec = make_spec(params)
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        wire_br = spec.block_rows if wf == "int8" else None
        st_a = init_packed_gossip_state(packed, gcfg, block_rows=wire_br)
        st_b = init_packed_gossip_state(packed, gcfg, block_rows=wire_br,
                                        elastic=True)
        ones = jnp.ones((4,), jnp.float32)
        pk_a = pk_b = packed
        for i in range(5):
            key = jax.random.key(i)
            pk_a, st_a, m_a = asgd_gossip_apply_packed(
                pk_a, pdw, st_a, key, gcfg, acfg, spec)
            pk_b, st_b, m_b = asgd_gossip_apply_packed(
                pk_b, pdw, st_b, key, gcfg, acfg, spec, live=ones)
            np.testing.assert_array_equal(np.asarray(pk_b),
                                          np.asarray(pk_a))
            np.testing.assert_array_equal(np.asarray(st_b.buf),
                                          np.asarray(st_a.buf))
            np.testing.assert_array_equal(np.asarray(m_b["gate"]),
                                          np.asarray(m_a["gate"]))

    @pytest.mark.parametrize("wf", [None, "dtype", "int8"])
    @pytest.mark.parametrize("delay", [0, 1, 2])
    def test_pipelined(self, wf, delay):
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        gcfg = wire_cfg(wf, shifts=(1, 2), partial_blocks=2, delay=delay)
        acfg = ASGDConfig(eps=0.05)
        spec = make_spec(params)
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        wire_br = spec.block_rows if wf == "int8" else None
        st_a = init_pipelined_gossip_state(packed, gcfg,
                                           block_rows=wire_br)
        st_b = init_pipelined_gossip_state(packed, gcfg,
                                           block_rows=wire_br,
                                           elastic=True)
        ones = jnp.ones((4,), jnp.float32)
        pk_a = pk_b = packed
        for i in range(5):
            key = jax.random.key(i)
            pk_a, st_a, m_a = asgd_gossip_apply_pipelined(
                pk_a, pdw, st_a, key, gcfg, acfg, spec)
            pk_b, st_b, m_b = asgd_gossip_apply_pipelined(
                pk_b, pdw, st_b, key, gcfg, acfg, spec, live=ones)
            np.testing.assert_array_equal(np.asarray(pk_b),
                                          np.asarray(pk_a))
            np.testing.assert_array_equal(np.asarray(m_b["gate"]),
                                          np.asarray(m_a["gate"]))

    @pytest.mark.parametrize("wf", [None, "dtype", "int8"])
    @pytest.mark.parametrize("delay", [0, 1, 2])
    def test_pytree(self, wf, delay):
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        gcfg = wire_cfg(wf, shifts=(1, 2), partial_blocks=2, delay=delay)
        acfg = ASGDConfig(eps=0.05)
        st_a = init_gossip_state(params, gcfg)
        st_b = init_gossip_state(params, gcfg, elastic=True)
        ones = jnp.ones((4,), jnp.float32)
        p_a, p_b = params, params
        for i in range(5):
            key = jax.random.key(i)
            p_a, st_a, m_a = asgd_gossip_apply(p_a, grads, st_a, key,
                                               gcfg, acfg)
            p_b, st_b, m_b = asgd_gossip_apply(p_b, grads, st_b, key,
                                               gcfg, acfg, live=ones)
            for k in params:
                np.testing.assert_array_equal(np.asarray(p_b[k]),
                                              np.asarray(p_a[k]))
            np.testing.assert_array_equal(np.asarray(m_b["gate"]),
                                          np.asarray(m_a["gate"]))

    def test_elastic_state_defaults_live_to_ones(self):
        """live=None on an elastic state means 'everyone alive' — the two
        call forms are bitwise identical (so a driver can flip between
        them without re-jitting different structures)."""
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        gcfg = GossipConfig(shifts=(1, 2), partial_blocks=2, delay=1)
        acfg = ASGDConfig(eps=0.05)
        st_a = init_gossip_state(params, gcfg, elastic=True)
        st_b = init_gossip_state(params, gcfg, elastic=True)
        ones = jnp.ones((4,), jnp.float32)
        p_a, p_b = params, params
        for i in range(3):
            key = jax.random.key(i)
            p_a, st_a, _ = asgd_gossip_apply(p_a, grads, st_a, key, gcfg,
                                             acfg)
            p_b, st_b, _ = asgd_gossip_apply(p_b, grads, st_b, key, gcfg,
                                             acfg, live=ones)
            for k in params:
                np.testing.assert_array_equal(np.asarray(p_b[k]),
                                              np.asarray(p_a[k]))


class TestDeadPeerCrossEngine:
    """The same churn schedule produces the same trajectory on every
    engine: packed follows the pytree jnp reference, pipelined(delay)
    follows packed(delay+1) bitwise — the liveness gates commute with
    the engine formulations."""

    @pytest.mark.parametrize("delay", [0, 1])
    def test_packed_matches_pytree_under_churn(self, delay):
        W = 4
        params = make_params(W=W)
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        gcfg = GossipConfig(shifts=(1, 2), partial_blocks=2, delay=delay)
        acfg = ASGDConfig(eps=0.05, use_parzen=False)
        spec = make_spec(params)
        p_ref = params
        s_ref = init_gossip_state(params, gcfg, elastic=True)
        packed = pack_w(params, spec)
        s_pk = init_packed_gossip_state(packed, gcfg, elastic=True)
        pdw = pack_w(grads, spec)
        for t in range(7):
            live = churn_live(W, t, dead=1, t0=2, k=2)
            key = jax.random.key(t)
            p_ref, s_ref, m_ref = asgd_gossip_apply(
                p_ref, grads, s_ref, key, gcfg, acfg, live=live)
            packed, s_pk, m_pk = asgd_gossip_apply_packed(
                packed, pdw, s_pk, key, gcfg, acfg, spec, live=live)
            np.testing.assert_array_equal(np.asarray(m_pk["gate"]),
                                          np.asarray(m_ref["gate"]))
        got = unpack_w(packed, spec)
        for k in params:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(p_ref[k]),
                                       rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("wf", [None, "int8"])
    @pytest.mark.parametrize("delay", [0, 1])
    def test_pipelined_matches_packed_delay_plus_1_under_churn(self, wf,
                                                               delay):
        W = 4
        params = make_params(W=W)
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = wire_cfg(wf, shifts=(1, 2), partial_blocks=2, delay=delay)
        ref_cfg = dataclasses.replace(cfg, delay=delay + 1)
        acfg = ASGDConfig(eps=0.05, use_parzen=False)
        spec = make_spec(params)
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        wire_br = spec.block_rows if wf == "int8" else None
        st_p = init_pipelined_gossip_state(packed, cfg,
                                           block_rows=wire_br,
                                           elastic=True)
        st_r = init_packed_gossip_state(packed, ref_cfg,
                                        block_rows=wire_br, elastic=True)
        pk_p = pk_r = packed
        opened = 0.0
        for t in range(7):
            live = churn_live(W, t, dead=2, t0=3, k=2)
            key = jax.random.key(t)
            pk_p, st_p, m_p = asgd_gossip_apply_pipelined(
                pk_p, pdw, st_p, key, cfg, acfg, spec, live=live)
            pk_r, st_r, m_r = asgd_gossip_apply_packed(
                pk_r, pdw, st_r, key, ref_cfg, acfg, spec, live=live)
            np.testing.assert_array_equal(np.asarray(m_p["gate"]),
                                          np.asarray(m_r["gate"]))
            if wf == "int8":
                np.testing.assert_allclose(np.asarray(pk_p),
                                           np.asarray(pk_r),
                                           rtol=1e-6, atol=1e-6)
            else:
                np.testing.assert_array_equal(np.asarray(pk_p),
                                              np.asarray(pk_r))
            opened += float(jnp.sum(m_p["gate"]))
        assert opened > 0.0   # churn must not degenerate to silent SGD

    def test_split_halves_thread_sent_live(self):
        """initiate/consume (the train step's formulation) compose to the
        pipelined engine under churn — sent_live crosses the split."""
        W = 4
        params = make_params(W=W)
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1, 2), partial_blocks=2, delay=1)
        acfg = ASGDConfig(eps=0.05, use_parzen=False)
        spec = make_spec(params)
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        st_a = init_pipelined_gossip_state(packed, cfg, elastic=True)
        st_b = init_pipelined_gossip_state(packed, cfg, elastic=True)
        pk_a = pk_b = packed
        for t in range(6):
            live = churn_live(W, t, dead=0, t0=2, k=2)
            key = jax.random.key(t)
            pk_a, st_a, m_a = asgd_gossip_apply_pipelined(
                pk_a, pdw, st_a, key, cfg, acfg, spec, live=live)
            sent, ss, bi, sent_live = initiate_exchange_packed(
                pk_b, key, cfg, spec, live=live)
            pk_b, st_b, m_b = consume_exchange_packed(
                pk_b, pdw, st_b, sent, ss, bi, cfg, acfg, spec,
                sent_live=sent_live, live=live)
            np.testing.assert_array_equal(np.asarray(pk_b),
                                          np.asarray(pk_a))
            np.testing.assert_array_equal(np.asarray(m_b["gate"]),
                                          np.asarray(m_a["gate"]))


class TestJoinWindowAfterElasticRestore:
    def test_restore_at_new_w_gates_closed_then_open(self, tmp_path):
        """ISSUE acceptance: a packed checkpoint saved at W=4 restores
        and trains at W=2 via the elastic path, with the liveness gates
        CLOSED for the join window (the restored buffer slot carries
        buf_live=0 — stale cross-W content must not blend) and open
        again once a real exchange refills the FIFO."""
        from repro.checkpoint import (load_checkpoint_packed,
                                      save_checkpoint_packed)

        W, p = 4, 2
        params = make_params(W=W)
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        gcfg = GossipConfig(shifts=(1,), partial_blocks=p, delay=1)
        acfg = ASGDConfig(eps=0.05, use_parzen=False)
        spec = make_spec(params, p)
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        st = init_packed_gossip_state(packed, gcfg)
        for t in range(3):     # warm: buffer holds a real payload
            packed, st, _ = asgd_gossip_apply_packed(
                packed, pdw, st, jax.random.key(t), gcfg, acfg, spec)
        state = {"params": packed, "gossip": st, "opt": jnp.int32(0),
                 "step": jnp.int32(3)}
        path = tmp_path / "w4.msgpack"
        save_checkpoint_packed(path, state, spec)

        W2 = 2
        params2 = make_params(W=W2)
        spec2 = make_spec(params2, p)
        packed2 = pack_w(params2, spec2)
        like = {"params": jnp.zeros_like(packed2),
                "gossip": init_packed_gossip_state(packed2, gcfg,
                                                   elastic=True),
                "opt": jnp.int32(0), "step": jnp.int32(0)}
        back = load_checkpoint_packed(path, like, spec2, elastic=True)
        np.testing.assert_array_equal(np.asarray(back["gossip"].buf_live),
                                      np.zeros(W2, np.float32))
        # the restored buffer is NON-zero (real stale payload rows made
        # it across the resize) — only the liveness gate keeps it out
        assert float(jnp.abs(back["gossip"].buf).max()) > 0.0

        pk, g = back["params"], back["gossip"]
        pdw2 = pack_w(jax.tree.map(lambda x: 0.05 * jnp.sign(x),
                                   unpack_w(pk, spec2)), spec2)
        ones = jnp.ones((W2,), jnp.float32)
        gates = []
        for t in range(3):
            pk, g, m = asgd_gossip_apply_packed(
                pk, pdw2, g, jax.random.key(100 + t), gcfg, acfg, spec2,
                live=ones)
            gates.append(float(jnp.sum(m["gate"])))
        # round 0: join window — the restored slot's gate is closed
        assert gates[0] == 0.0
        # once a real (live) exchange has refilled the slot, gates open
        assert sum(gates[1:]) > 0.0

    def test_unpacked_elastic_restore_migrates_and_trains(self,
                                                          tmp_path):
        """The pytree engine's elastic restore: save at W=4, restore at
        W=8 with resize_workers, keep training — buf_live stays the
        like's zeros (transient, never on disk)."""
        from repro.checkpoint import load_checkpoint, save_checkpoint

        params = make_params(W=4)
        gcfg = GossipConfig(shifts=(1,), partial_blocks=2, delay=1)
        acfg = ASGDConfig(eps=0.05, use_parzen=False)
        state = {"params": params,
                 "gossip": init_gossip_state(params, gcfg),
                 "step": jnp.int32(5)}
        path = tmp_path / "w4.msgpack"
        save_checkpoint(path, state)

        params8 = make_params(W=8)
        like = {"params": params8,
                "gossip": init_gossip_state(params8, gcfg, elastic=True),
                "step": jnp.int32(0)}
        back = load_checkpoint(path, like, resize_workers=True)
        for k in params:
            assert back["params"][k].shape[0] == 8
            # cyclic tiling: workers 4..7 mirror 0..3
            np.testing.assert_array_equal(np.asarray(back["params"][k][4:]),
                                          np.asarray(back["params"][k][:4]))
        np.testing.assert_array_equal(np.asarray(back["gossip"].buf_live),
                                      np.zeros(8, np.float32))
        assert int(back["step"]) == 5
        p, g = back["params"], back["gossip"]
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), p)
        p, g, _ = asgd_gossip_apply(p, grads, g, jax.random.key(0),
                                    GossipConfig(shifts=(1, 2),
                                                 partial_blocks=2,
                                                 delay=1),
                                    acfg, live=jnp.ones((8,), jnp.float32))
        assert all(bool(jnp.all(jnp.isfinite(x)))
                   for x in jax.tree.leaves(p))


# ---------------------------------------------------------------------------
# chaos harness (threaded GASPI simulator)
# ---------------------------------------------------------------------------

def _kmeans_data():
    x, _, _ = kmeans.synthetic_clusters(jax.random.key(0), k=6, d=8,
                                        m=16000)
    x = np.asarray(x, np.float64)
    return x, x[:6].copy()


class TestChaosHarness:
    def test_kill_revive_converges_within_1p5x(self):
        """ISSUE acceptance: killing + reviving 1 of 4 simulated ranks
        mid-run converges within 1.5x of the stable run's final
        objective, deterministically under a fixed seed."""
        x, w0 = _kmeans_data()
        asgd = ASGDConfig(eps=0.1, batch=100)
        stable = run_async_asgd(
            AsyncSimConfig(ranks=4, rounds=60, deterministic=True,
                           asgd=asgd), x, w0, seed=2)
        chaos = run_async_asgd(
            AsyncSimConfig(ranks=4, rounds=60, deterministic=True,
                           chaos_kills=1, chaos_seed=7, asgd=asgd),
            x, w0, seed=2)
        assert len(chaos["kill_schedule"]) == 1
        r, k, v = chaos["kill_schedule"][0]
        assert 0 <= r < 4 and 15 <= k <= 30 and k < v <= 59  # mid-run
        assert chaos["msgs_dropped"].sum() > 0     # writes really lost
        assert chaos["error_first"] <= 1.5 * stable["error_first"]
        assert chaos["error_mean_aggregate"] <= \
            1.5 * stable["error_mean_aggregate"]
        # churn really cost messages: fewer delivered than the stable run
        assert chaos["msgs_sent"].sum() < stable["msgs_sent"].sum()

    def test_seeded_determinism_regression(self):
        """Satellite: same kill-schedule seed => bitwise-identical
        trajectory; different seed => different kill steps."""
        x, w0 = _kmeans_data()
        cfg = AsyncSimConfig(ranks=4, rounds=60, deterministic=True,
                             chaos_kills=1, chaos_seed=7,
                             asgd=ASGDConfig(eps=0.1, batch=100))
        c1 = run_async_asgd(cfg, x, w0, seed=2)
        c2 = run_async_asgd(cfg, x, w0, seed=2)
        np.testing.assert_array_equal(c1["w_first"], c2["w_first"])
        np.testing.assert_array_equal(c1["w_mean"], c2["w_mean"])
        np.testing.assert_array_equal(c1["msgs_sent"], c2["msgs_sent"])
        np.testing.assert_array_equal(c1["msgs_good"], c2["msgs_good"])
        np.testing.assert_array_equal(c1["msgs_dropped"],
                                      c2["msgs_dropped"])
        assert c1["err_trace"] == c2["err_trace"]
        assert c1["kill_schedule"] == c2["kill_schedule"]

        other = dataclasses.replace(cfg, chaos_seed=8)
        c3 = run_async_asgd(other, x, w0, seed=2)
        assert c3["kill_schedule"] != c1["kill_schedule"]
        # and the schedule function itself is the pure source of truth
        assert c1["kill_schedule"] == make_kill_schedule(4, 60, 1, 7)
        assert c3["kill_schedule"] == make_kill_schedule(4, 60, 1, 8)

    def test_explicit_schedule_and_frozen_victim(self):
        """An explicit chaos_schedule overrides the seeded one; the
        victim's error trace pauses while dead (no compute happens)."""
        x, w0 = _kmeans_data()
        sched = ((2, 10, 30),)
        cfg = AsyncSimConfig(ranks=4, rounds=60, deterministic=True,
                             chaos_schedule=sched, chaos_kills=5,
                             asgd=ASGDConfig(eps=0.1, batch=100))
        out = run_async_asgd(cfg, x, w0, seed=3)
        assert out["kill_schedule"] == sched
        # err_trace appends at t % 10 == 0: rank 2 misses t=10, 20 only
        assert len(out["err_trace"][2]) == len(out["err_trace"][0]) - 2
        assert out["msgs_dropped"].sum() > 0

    def test_threaded_chaos_completes(self):
        """The racy threaded mode survives churn too (no determinism
        claim — just liveness of the harness and message accounting)."""
        x, w0 = _kmeans_data()
        cfg = AsyncSimConfig(ranks=4, rounds=40, chaos_kills=1,
                             chaos_seed=5,
                             asgd=ASGDConfig(eps=0.1, batch=100))
        out = run_async_asgd(cfg, x, w0, seed=1)
        assert len(out["kill_schedule"]) == 1
        total = out["msgs_sent"].sum() + out["msgs_dropped"].sum()
        # dead rounds send nothing at all: strictly fewer attempts than
        # the churn-free invariant ranks * rounds * fanout
        assert total < 4 * 40
        assert np.isfinite(out["error_first"])

    def test_no_chaos_invariants_unchanged(self):
        """chaos_kills=0 keeps the legacy accounting: every round sends,
        nothing drops, schedule is empty (regression guard for the
        refactored per-round body)."""
        x, w0 = _kmeans_data()
        cfg = AsyncSimConfig(ranks=4, rounds=30,
                             asgd=ASGDConfig(eps=0.1, batch=100))
        out = run_async_asgd(cfg, x, w0, seed=4)
        assert out["kill_schedule"] == ()
        assert out["msgs_sent"].sum() == 4 * 30
        assert out["msgs_dropped"].sum() == 0


# ---------------------------------------------------------------------------
# multi-device subprocess: kill a rank mid-run inside the manual region
# ---------------------------------------------------------------------------

ELASTIC_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.asgd import ASGDConfig
    from repro.core.gossip import (GossipConfig, asgd_gossip_apply_packed,
                                   init_packed_gossip_state, leaf_groups)
    from repro.core.packing import pack_spec_w, pack_w
    from repro.launch.mesh import make_host_mesh, shard_map_gossip_round

    mesh = make_host_mesh(data=4, model=2)
    W = 8   # oversubscribed: W_local = 2 -> the two-ppermute roll path
    ks = jax.random.split(jax.random.key(0), 2)
    params = {"a": jax.random.normal(ks[0], (W, 20, 30)),
              "b": jax.random.normal(ks[1], (W, 6))}
    grads = jax.tree.map(lambda x: 0.1 * x, params)
    gcfg = GossipConfig(shifts=(1,), partial_blocks=2,
                        partial_mode="leaves", delay=1)
    acfg = ASGDConfig(eps=0.05, use_parzen=False)
    spec = pack_spec_w(params, block_rows=8,
                       groups=leaf_groups(params, 2), n_groups=2)
    packed, pdw = pack_w(params, spec), pack_w(grads, spec)

    # GSPMD elastic reference
    st = init_packed_gossip_state(packed, gcfg, elastic=True)
    pk_ref = packed
    # manual-region elastic round; caller carries (buf, buf_idx,
    # buf_live) and feeds last round's (sent, block_idx, sent_live) back
    round_m = jax.jit(shard_map_gossip_round(mesh, spec, gcfg, acfg,
                                             n_workers=W, elastic=True))
    pk_man = packed
    buf = jnp.zeros_like(packed)
    buf_idx = jnp.int32(0)
    buf_live = jnp.zeros((W,), jnp.float32)
    DEAD, T0, K = 5, 2, 2
    froze = checked_closed = False
    for t in range(7):
        live_np = np.ones(W, np.float32)
        if T0 <= t < T0 + K:
            live_np[DEAD] = 0.0
        live = jnp.asarray(live_np)
        key = jax.random.key(t)
        prev_ref = pk_ref
        pk_ref, st, m_ref = asgd_gossip_apply_packed(
            pk_ref, pdw, st, key, gcfg, acfg, spec, live=live)
        k_shift, k_blk = jax.random.split(key)
        si = jax.random.randint(k_shift, (), 0, len(gcfg.shifts))
        bi = jax.random.randint(k_blk, (), 0, gcfg.partial_blocks)
        pk_man, sent, gates, sent_live = round_m(
            pk_man, pdw, buf, buf_idx, jnp.int32(t), si, bi,
            buf_live, live)
        buf, buf_idx, buf_live = sent, bi, sent_live
        np.testing.assert_allclose(np.asarray(pk_man),
                                   np.asarray(pk_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(gates),
                                      np.asarray(m_ref["gate"]))
        if T0 <= t < T0 + K:
            # the killed worker's shard is bitwise frozen mid-run
            np.testing.assert_array_equal(np.asarray(pk_ref[DEAD]),
                                          np.asarray(prev_ref[DEAD]))
            froze = True
            assert float(sent_live[(DEAD + 1) % W]) == 0.0
        if t == T0 + gcfg.delay:
            # the dropped payload's gate is closed at the receiver
            assert float(gates[(DEAD + 1) % W]) == 0.0
            checked_closed = True
        if t >= T0 + K + gcfg.delay:
            # revived: the post-revival payload blends again
            assert float(gates[(DEAD + 1) % W]) > 0.0
    assert froze and checked_closed
    txt = round_m.lower(pk_man, pdw, buf, buf_idx, jnp.int32(0),
                        jnp.int32(0), jnp.int32(0), buf_live,
                        jnp.ones((W,), jnp.float32)).compile().as_text()
    assert "collective-permute" in txt
    print("ELASTIC-MESH-OK")
""")


@pytest.mark.slow
def test_shard_map_elastic_round_kills_rank_mid_run(run_cpu_script):
    """8-fake-device subprocess: the manual-region elastic round under a
    mid-run kill/revive reproduces the GSPMD elastic engine exactly —
    the masked ppermute payload is DROPPED at the receiver (gate
    closed), the dead worker's shard stays bitwise frozen, and the
    revived worker re-enters after the delay window."""
    r = run_cpu_script(ELASTIC_MESH_SCRIPT, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ELASTIC-MESH-OK" in r.stdout

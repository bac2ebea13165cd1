"""Pipelined gossip-round tests (ISSUE 5; DESIGN.md §7).

Covers the pipelined packed-resident engine (initiate/consume split,
asgd_gossip_apply_pipelined) against the unpipelined engine run at
delay+1 across partial_mode x wire_format x delay (the acceptance
bit-parity), the generalized staleness FIFO of the unpipelined engine
(delay >= 2), the fused-update resident kernel's runtime ``lr`` operand
against the jnp gossip_blend_w_resident_ref extension, the
choose_block_rows autotune default, the pipelined train step
(packed-native gradients) against the unpipelined packed step at delay+1,
the stacked-FIFO checkpoint boundary, the packed/pipelined dry-run input
specs, and (subprocess, 8 fake devices, slow) the manual-region pipelined
round: ppermute parity vs the GSPMD engine, the collective confined to
the initiate region, and a communication-free consume region.
"""
import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.asgd import ASGDConfig
from repro.core.gossip import (GossipConfig, asgd_gossip_apply_packed,
                               asgd_gossip_apply_pipelined,
                               consume_exchange_packed, fifo_depth,
                               init_packed_gossip_state,
                               init_pipelined_gossip_state,
                               initiate_exchange_packed, leaf_groups,
                               staleness_valid)
from repro.core.packing import (LANE, pack_spec_w, pack_w, quantize_rows,
                                unpack_rows, unpack_w)
from repro.kernels.gossip_blend import (choose_block_rows,
                                        gossip_blend_w_resident)
from repro.kernels.gossip_blend.ref import (gossip_blend_w_resident_ref,
                                            run_pipelined_parity)


def make_params(W=4, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    return {
        "wq": jax.random.normal(ks[0], (W, 16, 8)).astype(dtype),
        "bias": jax.random.normal(ks[1], (W, 6)).astype(dtype),
        "wo": jax.random.normal(ks[2], (W, 8, 4)).astype(dtype),
    }


def make_spec(params, p, mode):
    if mode == "leaves":
        return pack_spec_w(params, block_rows=2,
                           groups=leaf_groups(params, p), n_groups=p)
    return pack_spec_w(params, block_rows=2)


class TestFifoState:
    """init_pipelined_gossip_state / init_packed_gossip_state depth
    layouts and the generalized staleness guard."""

    def test_depths(self):
        assert fifo_depth(GossipConfig(delay=0)) == 1
        assert fifo_depth(GossipConfig(delay=1)) == 1
        assert fifo_depth(GossipConfig(delay=2)) == 2
        assert fifo_depth(GossipConfig(delay=0), pipelined=True) == 1
        assert fifo_depth(GossipConfig(delay=1), pipelined=True) == 2

    def test_single_slot_layout_unchanged(self):
        packed = jnp.ones((4, 8, LANE))
        st = init_packed_gossip_state(packed, GossipConfig(delay=1))
        assert st.buf.shape == packed.shape and st.buf_idx.shape == ()
        st0 = init_pipelined_gossip_state(packed, GossipConfig(delay=0))
        assert st0.buf.shape == packed.shape

    def test_stacked_layout(self):
        packed = jnp.ones((4, 8, LANE))
        cfg = GossipConfig(delay=1, wire_format="int8")
        st = init_pipelined_gossip_state(packed, cfg, block_rows=2)
        assert st.buf.shape == (2, 4, 8, LANE)
        assert st.buf.dtype == jnp.int8
        assert st.buf_scales.shape == (2, 4, 4)
        assert st.buf_idx.shape == (2,)
        st3 = init_packed_gossip_state(packed, GossipConfig(delay=3))
        assert st3.buf.shape == (3, 4, 8, LANE)
        assert st3.buf.dtype == packed.dtype

    def test_staleness_valid_thresholds(self):
        cfg = GossipConfig(delay=1)
        assert staleness_valid(jnp.int32(0), cfg) == 0.0
        assert staleness_valid(jnp.int32(1), cfg) == 1.0
        # pipelined: one extra in-flight round
        assert staleness_valid(jnp.int32(1), cfg, extra=1) == 0.0
        assert staleness_valid(jnp.int32(2), cfg, extra=1) == 1.0
        assert staleness_valid(jnp.int32(0), GossipConfig(delay=0)) is None
        assert staleness_valid(jnp.int32(2),
                               GossipConfig(delay=3)) == 0.0


class TestGeneralizedDelay:
    """The unpipelined packed engine with delay >= 2 (the pipelined
    engine's parity oracle): warm-up guard depth and FIFO ordering."""

    def test_warmup_rounds_are_plain_sgd(self):
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1, 2), partial_blocks=2, delay=2)
        acfg = ASGDConfig(eps=0.05)
        spec = make_spec(params, 2, "leaves")
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        st = init_packed_gossip_state(packed, cfg)
        assert st.buf.shape[0] == 2
        for i in range(4):
            new_packed, st, m = asgd_gossip_apply_packed(
                packed, pdw, st, jax.random.key(i), cfg, acfg, spec)
            if i < 2:   # guard closed: plain SGD on placeholder slots
                assert float(jnp.sum(m["gate"])) == 0.0
                np.testing.assert_allclose(
                    np.asarray(new_packed),
                    np.asarray(packed - acfg.eps * pdw),
                    rtol=1e-6, atol=1e-7)
            packed = new_packed
        assert float(jnp.sum(m["gate"])) > 0.0

    def test_fifo_blends_oldest_payload(self):
        """At delay=2 round t must blend the payload launched at t-2:
        check the FIFO head equals the sent buffer from two rounds ago."""
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1,), partial_blocks=2, delay=2)
        acfg = ASGDConfig(eps=0.05)
        spec = make_spec(params, 2, "leaves")
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        st = init_packed_gossip_state(packed, cfg)
        heads, sents = [], []
        from repro.core.gossip import exchange_packed, packed_row_ranges
        ranges = packed_row_ranges(spec, cfg)
        for i in range(3):
            key = jax.random.key(i)
            heads.append(np.asarray(st.buf[0]))
            k_shift, k_blk = jax.random.split(key)
            si = jax.random.randint(k_shift, (), 0, 1)
            bi = jax.random.randint(k_blk, (), 0, 2)
            sents.append(np.asarray(
                exchange_packed(packed, ranges, si, bi, cfg)))
            packed, st, _ = asgd_gossip_apply_packed(
                packed, pdw, st, key, cfg, acfg, spec)
        np.testing.assert_array_equal(heads[2], sents[0])


class TestSingleSlotGuardClamp:
    """The single-slot pytree engines must clamp the warm-up guard to
    their real buffered depth (1): with cfg.delay >= 2 the payload
    received at step 0 is a REAL block and must not be gated out at
    step 1 (regression for the staleness_valid generalization)."""

    @pytest.mark.parametrize("mode", ["leaves", "rows"])
    def test_pytree_engine_delay2_blends_at_step1(self, mode):
        from repro.core.gossip import asgd_gossip_apply, init_gossip_state

        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1,), partial_blocks=2,
                           partial_mode=mode, delay=2)
        # use_parzen=False: any real (non-empty) payload is admitted, so
        # an open gate at step 1 is exactly the no-over-gating property
        acfg = ASGDConfig(eps=0.05, use_parzen=False)
        state = init_gossip_state(params, cfg)
        params1, state, m0 = asgd_gossip_apply(
            params, grads, state, jax.random.key(0), cfg, acfg)
        assert float(jnp.sum(m0["gate"])) == 0.0   # init placeholder
        _, _, m1 = asgd_gossip_apply(
            params1, grads, state, jax.random.key(1), cfg, acfg)
        assert float(jnp.sum(m1["gate"])) > 0.0    # real payload blended


class TestPipelinedParity:
    """ISSUE-5 acceptance: the pipelined engine is bit-identical (float
    wire) / tolerance-equal (int8 wire) to the unpipelined engine run at
    delay+1, on the same key schedule, across
    partial_mode x wire_format x delay.  (The W_local > 1 axis of the
    matrix lives in the 8-device subprocess test below.)"""

    @pytest.mark.parametrize("mode", ["leaves", "rows"])
    @pytest.mark.parametrize("wf", [None, "dtype", "int8"])
    @pytest.mark.parametrize("delay", [0, 1])
    def test_matches_unpipelined_at_delay_plus_1(self, mode, wf, delay):
        W, p = 4, 2
        if mode == "leaves":
            params = make_params(W=W)
        else:   # 'rows' + int8 needs >= p * block_rows packed rows
            params = {"w": jax.random.normal(jax.random.key(0),
                                             (W, 8, LANE))}
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1, 2), partial_blocks=p,
                           partial_mode=mode, delay=delay, wire_format=wf,
                           payload_dtype=jnp.bfloat16 if wf == "dtype"
                           else None)
        acfg = ASGDConfig(eps=0.05)
        spec = make_spec(params, p, mode)
        per_round, state = run_pipelined_parity(params, grads, cfg, acfg,
                                                spec, rounds=5)
        opened = 0.0
        for r in per_round:
            np.testing.assert_array_equal(np.asarray(r["pipe_gate"]),
                                          np.asarray(r["ref_gate"]))
            if wf == "int8":
                np.testing.assert_allclose(np.asarray(r["pipe_packed"]),
                                           np.asarray(r["ref_packed"]),
                                           rtol=1e-6, atol=1e-6)
            else:
                np.testing.assert_array_equal(
                    np.asarray(r["pipe_packed"]),
                    np.asarray(r["ref_packed"]))
            opened += float(jnp.sum(r["pipe_gate"]))
        # the pipeline must not degenerate to silent SGD: gates open
        # once the warm-up rounds (delay+1) have passed
        assert opened > 0.0
        # the engine really carried a depth-(delay+1) FIFO
        depth = fifo_depth(cfg, pipelined=True)
        if depth >= 2:
            assert state.buf.shape[0] == depth
        if wf == "int8":
            assert state.buf.dtype == jnp.int8

    def test_elastic_parity(self):
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1, 2), partial_blocks=2, delay=1)
        acfg = ASGDConfig(eps=0.05, elastic=True)
        spec = make_spec(params, 2, "leaves")
        per_round, _ = run_pipelined_parity(params, grads, cfg, acfg,
                                            spec, rounds=4)
        for r in per_round:
            np.testing.assert_array_equal(np.asarray(r["pipe_packed"]),
                                          np.asarray(r["ref_packed"]))

    def test_gossip_every_parity(self):
        """Interval gossip through the composed engine: off-rounds are
        plain SGD with an untouched FIFO, matching the unpipelined engine
        at delay+1 and the same interval."""
        import dataclasses
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1, 2), partial_blocks=2, delay=0,
                           gossip_every=2)
        acfg = ASGDConfig(eps=0.05)
        spec = make_spec(params, 2, "leaves")
        ref_cfg = dataclasses.replace(cfg, delay=1)
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        st_p = init_pipelined_gossip_state(packed, cfg)
        st_r = init_packed_gossip_state(packed, ref_cfg)
        pk_p = pk_r = packed
        for i in range(5):
            key = jax.random.key(i)
            pk_p, st_p, m_p = asgd_gossip_apply_pipelined(
                pk_p, pdw, st_p, key, cfg, acfg, spec)
            pk_r, st_r, m_r = asgd_gossip_apply_packed(
                pk_r, pdw, st_r, key, ref_cfg, acfg, spec)
            np.testing.assert_array_equal(np.asarray(pk_p),
                                          np.asarray(pk_r))
            np.testing.assert_array_equal(np.asarray(m_p["gate"]),
                                          np.asarray(m_r["gate"]))

    def test_silent_is_plain_sgd(self):
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1,), partial_blocks=2, delay=1)
        acfg = ASGDConfig(eps=0.05, silent=True)
        spec = make_spec(params, 2, "leaves")
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        st = init_pipelined_gossip_state(packed, cfg)
        out, st, m = asgd_gossip_apply_pipelined(
            packed, pdw, st, jax.random.key(0), cfg, acfg, spec)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(packed - 0.05 * pdw),
                                   rtol=1e-6, atol=1e-7)
        assert float(m["n_good"]) == 0.0

    def test_initiate_consume_compose_to_engine(self):
        """The split halves (the train step's formulation) compose to
        exactly asgd_gossip_apply_pipelined."""
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1, 2), partial_blocks=2, delay=1)
        acfg = ASGDConfig(eps=0.05)
        spec = make_spec(params, 2, "leaves")
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        st_a = init_pipelined_gossip_state(packed, cfg)
        st_b = init_pipelined_gossip_state(packed, cfg)
        pk_a = pk_b = packed
        for i in range(3):
            key = jax.random.key(i)
            pk_a, st_a, m_a = asgd_gossip_apply_pipelined(
                pk_a, pdw, st_a, key, cfg, acfg, spec)
            sent, ss, bi = initiate_exchange_packed(pk_b, key, cfg, spec)
            pk_b, st_b, m_b = consume_exchange_packed(
                pk_b, pdw, st_b, sent, ss, bi, cfg, acfg, spec)
            np.testing.assert_array_equal(np.asarray(pk_a),
                                          np.asarray(pk_b))
            np.testing.assert_array_equal(np.asarray(m_a["gate"]),
                                          np.asarray(m_b["gate"]))


class TestFusedUpdateKernel:
    """The resident kernel's runtime ``lr`` operand vs the jnp
    gossip_blend_w_resident_ref extension."""

    @pytest.mark.parametrize("elastic", [False, True])
    @pytest.mark.parametrize("int8", [False, True])
    def test_lr_operand_matches_ref(self, elastic, int8):
        W, P, R, br = 3, 2, 16, 4
        ks = jax.random.split(jax.random.key(0), 2)
        w3 = jax.random.normal(ks[0], (W, R, LANE))
        d3 = jax.random.normal(ks[1], (W, R, LANE)) * 0.1
        ext = w3[:, None] - 0.5 * d3[:, None] * jnp.arange(
            1, P + 1, dtype=jnp.float32)[None, :, None, None]
        scales = None
        if int8:
            ext, scales = quantize_rows(ext, br)
        rr = jnp.asarray([4, 12], jnp.int32)
        # lr deliberately different from the gate's eps
        out_k, g_k = gossip_blend_w_resident(
            w3, d3, ext, rr, 0.05, lr=0.11, ext_scales=scales,
            block_rows=br, elastic=elastic)
        out_r, g_r = gossip_blend_w_resident_ref(
            w3, d3, ext, rr, 0.05, lr=0.11, ext_scales=scales,
            block_rows=br, elastic=elastic)
        np.testing.assert_array_equal(np.asarray(g_k), np.asarray(g_r))
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-6, atol=1e-6)

    def test_lr_defaults_to_eps(self):
        W, R, br = 2, 8, 4
        w3 = jax.random.normal(jax.random.key(1), (W, R, LANE))
        d3 = 0.1 * jnp.sign(w3)
        ext = (w3 - 0.5 * d3)[:, None]
        rr = jnp.asarray([0, R], jnp.int32)
        out_a, _ = gossip_blend_w_resident(w3, d3, ext, rr, 0.05,
                                           block_rows=br)
        out_b, _ = gossip_blend_w_resident(w3, d3, ext, rr, 0.05, lr=0.05,
                                           block_rows=br)
        np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))

    def test_traced_lr_under_jit(self):
        """lr is a runtime operand: one compile serves every lr value."""
        W, R, br = 2, 8, 4
        w3 = jax.random.normal(jax.random.key(2), (W, R, LANE))
        d3 = 0.1 * jnp.sign(w3)
        ext = (w3 - 0.5 * d3)[:, None]
        rr = jnp.asarray([0, R], jnp.int32)

        @jax.jit
        def f(lr):
            return gossip_blend_w_resident(w3, d3, ext, rr, 0.05, lr=lr,
                                           block_rows=br)[0]

        for lr in (0.01, 0.05, 0.2):
            ref, _ = gossip_blend_w_resident_ref(
                w3, d3, ext, rr, 0.05, lr=lr, block_rows=br)
            np.testing.assert_allclose(np.asarray(f(jnp.float32(lr))),
                                       np.asarray(ref),
                                       rtol=1e-6, atol=1e-6)


class TestChooseBlockRows:
    """The block_rows autotune default (ISSUE-5 satellite)."""

    def _bench_file(self, tmp_path, records, backend="tpu"):
        p = tmp_path / "bench.json"
        p.write_text(json.dumps({"backend": backend, "records": records}))
        return p

    def test_picks_fastest_divisor(self, tmp_path):
        recs = [
            {"name": "block_rows_sweep", "block_rows": 32,
             "wire_format": "f32", "pallas_interpret_ms": 5.0},
            {"name": "block_rows_sweep", "block_rows": 64,
             "wire_format": "f32", "pallas_interpret_ms": 2.0},
            {"name": "block_rows_sweep", "block_rows": 128,
             "wire_format": "f32", "pallas_interpret_ms": 9.0},
        ]
        path = self._bench_file(tmp_path, recs)
        assert choose_block_rows(256, bench_path=path) == 64
        # 64 does not divide 96 -> next-best candidate that does
        assert choose_block_rows(96, bench_path=path) == 32

    def test_wire_format_filter(self, tmp_path):
        recs = [
            {"name": "block_rows_sweep", "block_rows": 32,
             "wire_format": "f32", "pallas_interpret_ms": 1.0},
            {"name": "block_rows_sweep", "block_rows": 64,
             "wire_format": "f32", "pallas_interpret_ms": 3.0},
            {"name": "block_rows_sweep", "block_rows": 32,
             "wire_format": "int8", "pallas_interpret_ms": 7.0},
            {"name": "block_rows_sweep", "block_rows": 64,
             "wire_format": "int8", "pallas_interpret_ms": 2.0},
        ]
        path = self._bench_file(tmp_path, recs)
        assert choose_block_rows(128, wire_format="f32",
                                 bench_path=path) == 32
        assert choose_block_rows(128, wire_format="int8",
                                 bench_path=path) == 64

    def test_missing_file_falls_back(self, tmp_path):
        path = tmp_path / "missing.json"
        assert choose_block_rows(128, bench_path=path) == 64
        # largest power-of-two divisor when 64 does not divide
        assert choose_block_rows(48, bench_path=path) == 16

    def test_cpu_artifact_is_not_trusted(self, tmp_path):
        """Interpret-mode (CPU) records time the interpreter, not HBM —
        a non-TPU artifact must not move the default off 64."""
        recs = [{"name": "block_rows_sweep", "block_rows": 256,
                 "wire_format": "f32", "pallas_interpret_ms": 0.001}]
        path = self._bench_file(tmp_path, recs, backend="cpu")
        assert choose_block_rows(512, bench_path=path) == 64

    def test_repo_bench_records_usable(self):
        """The committed BENCH_gossip_blend.json must yield a valid
        default for the benchmark shapes (the autotune is live; on the
        CPU-measured committed artifact it conservatively keeps 64)."""
        br = choose_block_rows(512)
        assert 512 % br == 0 and br >= 1

    def test_resident_wrapper_resolves_none(self):
        """block_rows=None on gossip_blend_w_resident resolves through
        the autotune (f32) / the quantization tile (int8) and matches an
        explicit call."""
        W, R = 2, 8
        w3 = jax.random.normal(jax.random.key(3), (W, R, LANE))
        d3 = 0.1 * jnp.sign(w3)
        ext = (w3 - 0.5 * d3)[:, None]
        rr = jnp.asarray([0, R], jnp.int32)
        out_auto, g_auto = gossip_blend_w_resident(w3, d3, ext, rr, 0.05)
        out_ref, g_ref = gossip_blend_w_resident_ref(
            w3, d3, ext, rr, 0.05, block_rows=choose_block_rows(
                R, wire_format="f32"))
        np.testing.assert_array_equal(np.asarray(g_auto),
                                      np.asarray(g_ref))
        np.testing.assert_allclose(np.asarray(out_auto),
                                   np.asarray(out_ref),
                                   rtol=1e-6, atol=1e-6)
        # int8: the scales' tile fixes block_rows exactly
        q, s = quantize_rows(ext, 4)
        out_q, _ = gossip_blend_w_resident(w3, d3, q, rr, 0.05,
                                           ext_scales=s)
        out_qr, _ = gossip_blend_w_resident_ref(
            w3, d3, q, rr, 0.05, ext_scales=s, block_rows=4)
        np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_qr),
                                   rtol=1e-6, atol=1e-6)


class TestPipelinedCheckpoint:
    def test_stacked_fifo_roundtrip(self, tmp_path):
        """save/load_checkpoint_packed round-trips the depth-2 pipelined
        FIFO (canonical float slots on disk; int8 re-quantized on load)."""
        from repro.checkpoint import (load_checkpoint_packed,
                                      save_checkpoint_packed)

        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1, 2), partial_blocks=2, delay=1,
                           wire_format="int8")
        acfg = ASGDConfig(eps=0.05)
        spec = make_spec(params, 2, "leaves")
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        st = init_pipelined_gossip_state(packed, cfg,
                                         block_rows=spec.block_rows)
        for i in range(3):
            packed, st, _ = asgd_gossip_apply_pipelined(
                packed, pdw, st, jax.random.key(i), cfg, acfg, spec)
        state = {"params": packed, "gossip": st, "opt": jnp.int32(0),
                 "step": jnp.int32(3)}
        path = tmp_path / "ck_pipe.msgpack"
        save_checkpoint_packed(path, state, spec)
        like = {"params": jnp.zeros_like(packed),
                "gossip": init_pipelined_gossip_state(
                    packed, cfg, block_rows=spec.block_rows),
                "opt": jnp.int32(0), "step": jnp.int32(0)}
        back = load_checkpoint_packed(path, like, spec)
        np.testing.assert_allclose(np.asarray(back["params"]),
                                   np.asarray(packed), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(back["gossip"].buf),
                                      np.asarray(st.buf))
        np.testing.assert_allclose(np.asarray(back["gossip"].buf_scales),
                                   np.asarray(st.buf_scales), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(back["gossip"].buf_idx),
                                      np.asarray(st.buf_idx))
        assert int(back["step"]) == 3


class TestPackedInputSpecs:
    """input_specs/step_and_args engine routing (the dry-run follow-up:
    resident HLO rooflines) — structure only, no compile."""

    def test_packed_and_pipelined_specs(self):
        import dataclasses as dc

        from repro.configs.registry import get_arch, get_shape
        from repro.launch import steps as ST
        from repro.launch.mesh import make_host_mesh

        cfg = dc.replace(get_arch("smollm-135m").reduced(), name="smoke")
        shape = dc.replace(get_shape("train_4k"), seq_len=32,
                           global_batch=2)
        mesh = make_host_mesh(data=1, model=1)
        gcfg = GossipConfig(shifts=(1,), partial_blocks=2, delay=1)
        spec = ST.packed_spec_for(cfg, mesh, gcfg)
        for engine, depth in (("packed", 1), ("pipelined", 2)):
            specs = ST.input_specs(cfg, shape, mesh, gcfg, engine=engine)
            p = specs["params"]
            assert p.shape == (spec.n_workers, spec.rows, LANE)
            assert p.dtype == jnp.float32
            g = specs["gossip"]
            want = (depth,) + p.shape if depth >= 2 else p.shape
            assert g.buf.shape == want
            assert g.buf_scales is None   # float wire
        with pytest.raises(ValueError):
            ST.input_specs(cfg, shape, mesh, gcfg, engine="bogus")

    def test_pipelined_step_validations(self):
        from repro.configs.registry import get_arch
        from repro.launch.steps import make_train_step

        cfg = get_arch("smollm-135m").reduced()
        with pytest.raises(ValueError, match="packed_resident"):
            make_train_step(cfg, pipelined=True)
        params = make_params(W=2)
        spec = make_spec(params, 2, "leaves")
        with pytest.raises(ValueError, match="algo"):
            make_train_step(cfg, algo="sync", packed_resident=True,
                            pack_spec=spec, pipelined=True)
        with pytest.raises(ValueError, match="gossip_every"):
            make_train_step(cfg, packed_resident=True, pack_spec=spec,
                            pipelined=True,
                            gcfg=GossipConfig(gossip_every=2))
        with pytest.raises(ValueError, match="lr_schedule"):
            make_train_step(cfg, lr_schedule=lambda s: jnp.float32(0.01))
        with pytest.raises(ValueError, match="lr_schedule"):
            make_train_step(cfg, packed_resident=True, pack_spec=spec,
                            lr_schedule=lambda s: jnp.float32(0.01))


class TestUnpackRows:
    def test_matches_unpack_w_per_worker(self):
        params = make_params()
        spec = make_spec(params, 2, "leaves")
        pk = pack_w(params, spec)
        whole = unpack_w(pk, spec)
        for w in range(pk.shape[0]):
            one = unpack_rows(pk[w], spec)
            for k in params:
                np.testing.assert_array_equal(np.asarray(one[k]),
                                              np.asarray(whole[k][w]))
                assert one[k].dtype == params[k].dtype

    def test_grad_through_views_is_pack_w(self):
        """The VJP of the unpack_rows views IS pack_w — bit-for-bit (the
        property that lets the pipelined step skip the grad pack)."""
        params = make_params(dtype=jnp.bfloat16)
        spec = make_spec(params, 2, "leaves")
        pk = pack_w(params, spec)

        def loss_rows(rows2d):
            t = unpack_rows(rows2d, spec)
            return sum(jnp.sum(x.astype(jnp.float32) ** 2)
                       for x in jax.tree.leaves(t))

        def loss_tree(t):
            return sum(jnp.sum(x.astype(jnp.float32) ** 2)
                       for x in jax.tree.leaves(t))

        g_packed = jax.vmap(jax.grad(loss_rows))(pk)
        g_tree = jax.vmap(jax.grad(loss_tree))(params)
        np.testing.assert_array_equal(np.asarray(g_packed),
                                      np.asarray(pack_w(g_tree, spec)))


class TestPipelinedTrainStep:
    @pytest.mark.slow
    def test_pipelined_step_matches_packed_step_at_delay_plus_1(self):
        """make_train_step(pipelined=True) — packed-native gradients +
        initiate/consume split — follows the unpipelined packed step run
        at delay+1 loss-for-loss and state-for-state on a reduced arch."""
        import dataclasses as dc

        from repro.configs.registry import get_arch
        from repro.launch.steps import init_inner_state, make_train_step
        from repro.models import model as M

        cfg = get_arch("smollm-135m").reduced()
        W, B, S = 2, 1, 16
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (W,) + x.shape).copy(),
            M.init_model(cfg, jax.random.key(0)))
        batch = {"tokens": jax.random.randint(jax.random.key(1),
                                              (W, B, S), 0, cfg.vocab)}
        gcfg = GossipConfig(shifts=(1,), partial_blocks=2, delay=0)
        gcfg_ref = dc.replace(gcfg, delay=1)
        # use_parzen=False: every real payload is admitted, so the fused
        # blend path is guaranteed to run after the 1-round warm-up (the
        # Parzen-gated parity lives in TestPipelinedParity; near-identical
        # tiny-model replicas rarely open the eq.-4 gate in 3 rounds)
        acfg = ASGDConfig(eps=0.01, use_parzen=False)
        spec = pack_spec_w(params, block_rows=8,
                           groups=leaf_groups(params, 2), n_groups=2)
        step_pipe = make_train_step(cfg, algo="asgd", gcfg=gcfg,
                                    acfg=acfg, packed_resident=True,
                                    pack_spec=spec, pipelined=True)
        step_ref = make_train_step(cfg, algo="asgd", gcfg=gcfg_ref,
                                   acfg=acfg, packed_resident=True,
                                   pack_spec=spec)
        packed = pack_w(params, spec)
        g_pipe = init_pipelined_gossip_state(packed, gcfg)
        g_ref = init_packed_gossip_state(packed, gcfg_ref)
        pk_p = pk_r = packed
        opt = init_inner_state(packed)
        opened = 0.0
        for i in range(3):
            key = jax.random.key(i)
            pk_p, g_pipe, _, m_p = step_pipe(pk_p, g_pipe, opt, batch,
                                             key)
            pk_r, g_ref, _, m_r = step_ref(pk_r, g_ref, opt, batch, key)
            np.testing.assert_allclose(float(m_p["loss"]),
                                       float(m_r["loss"]), rtol=1e-5)
            np.testing.assert_array_equal(np.asarray(m_p["gate"]),
                                          np.asarray(m_r["gate"]))
            opened += float(m_p["n_good"])
        np.testing.assert_allclose(np.asarray(pk_p), np.asarray(pk_r),
                                   rtol=1e-5, atol=1e-6)
        assert opened > 0.0

        # silent ablation through the SAME pipelined step builder: pure
        # local SGD, nothing blended, FIFO untouched (regression: the
        # pipelined step must honor acfg.silent like the other engines)
        step_sil = make_train_step(
            cfg, algo="asgd", gcfg=gcfg, acfg=dc.replace(acfg, silent=True),
            packed_resident=True, pack_spec=spec, pipelined=True)
        g0 = init_pipelined_gossip_state(packed, gcfg)
        out_s, g_s, _, m_s = step_sil(packed, g0, opt, batch,
                                      jax.random.key(0))
        assert float(m_s["n_good"]) == 0.0
        np.testing.assert_array_equal(np.asarray(g_s.buf),
                                      np.asarray(g0.buf))
        assert int(g_s.step) == 1
        # the silent update equals the packed algo='silent' local SGD
        # step (packed-native grads are bitwise pack_w of the pytree
        # grads, so the two formulations must agree exactly)
        step_algo = make_train_step(cfg, algo="silent", gcfg=gcfg,
                                    acfg=acfg, packed_resident=True,
                                    pack_spec=spec)
        out_a, _, _, _ = step_algo(packed,
                                   init_packed_gossip_state(packed, gcfg),
                                   opt, batch, jax.random.key(0))
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_a),
                                   rtol=1e-6, atol=1e-7)

    @pytest.mark.slow
    def test_lr_schedule_const_matches_fixed_lr(self):
        """make_train_step(lr_schedule=...) feeds the consume blend's
        runtime lr operand: a constant schedule reproduces the fixed-lr
        pipelined run BITWISE.  (optim.lr_schedule('const') carries a
        warmup ramp — lr=0 at step 0 — so the bitwise reference is a
        plain constant lambda, not kind='const'.)"""
        import dataclasses as dc

        from repro.configs.registry import get_arch
        from repro.launch.steps import init_inner_state, make_train_step
        from repro.models import model as M

        cfg = get_arch("smollm-135m").reduced()
        W, B, S = 2, 1, 16
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (W,) + x.shape).copy(),
            M.init_model(cfg, jax.random.key(0)))
        batch = {"tokens": jax.random.randint(jax.random.key(1),
                                              (W, B, S), 0, cfg.vocab)}
        gcfg = GossipConfig(shifts=(1,), partial_blocks=2, delay=0)
        acfg = ASGDConfig(eps=0.01, use_parzen=False)
        spec = pack_spec_w(params, block_rows=8,
                           groups=leaf_groups(params, 2), n_groups=2)
        kw = dict(algo="asgd", gcfg=gcfg, acfg=acfg,
                  packed_resident=True, pack_spec=spec, pipelined=True)
        step_fix = make_train_step(cfg, **kw)
        step_sch = make_train_step(
            cfg, lr_schedule=lambda s: jnp.float32(acfg.eps), **kw)
        packed = pack_w(params, spec)
        opt = init_inner_state(packed)
        pk_f = pk_s = packed
        g_f = init_pipelined_gossip_state(packed, gcfg)
        g_s = init_pipelined_gossip_state(packed, gcfg)
        for i in range(3):
            key = jax.random.key(i)
            pk_f, g_f, _, m_f = step_fix(pk_f, g_f, opt, batch, key)
            pk_s, g_s, _, m_s = step_sch(pk_s, g_s, opt, batch, key)
            np.testing.assert_array_equal(np.asarray(pk_s),
                                          np.asarray(pk_f))
            np.testing.assert_array_equal(np.asarray(g_s.buf),
                                          np.asarray(g_f.buf))
            np.testing.assert_array_equal(np.asarray(m_s["gate"]),
                                          np.asarray(m_f["gate"]))
        # a real (warmup-ramped) schedule must CHANGE the trajectory —
        # the operand is live, not folded away
        from repro.optim import lr_schedule as mk_sched
        step_ramp = make_train_step(
            cfg, lr_schedule=mk_sched("cosine", acfg.eps, warmup=2,
                                      total=6), **kw)
        g_r = init_pipelined_gossip_state(packed, gcfg)
        pk_r, _, _, _ = step_ramp(packed, g_r, opt, batch,
                                  jax.random.key(0))
        assert not np.array_equal(np.asarray(pk_r), np.asarray(pk_f))

        # silent ablation honors the schedule too (step_lr = lr)
        step_sil_s = make_train_step(
            cfg, lr_schedule=lambda s: jnp.float32(acfg.eps),
            **{**kw, "acfg": dc.replace(acfg, silent=True)})
        step_sil_f = make_train_step(
            cfg, **{**kw, "acfg": dc.replace(acfg, silent=True)})
        g0 = init_pipelined_gossip_state(packed, gcfg)
        out_a, _, _, _ = step_sil_s(packed, g0, opt, batch,
                                    jax.random.key(0))
        g0 = init_pipelined_gossip_state(packed, gcfg)
        out_b, _, _, _ = step_sil_f(packed, g0, opt, batch,
                                    jax.random.key(0))
        np.testing.assert_array_equal(np.asarray(out_a),
                                      np.asarray(out_b))


PIPELINED_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.asgd import ASGDConfig
    from repro.core.gossip import (GossipConfig, _fifo_head,
                                   asgd_gossip_apply_pipelined,
                                   consume_exchange_packed, fifo_depth,
                                   init_pipelined_gossip_state,
                                   initiate_exchange_packed, leaf_groups)
    from repro.core.packing import pack_spec_w, pack_w
    from repro.launch.mesh import (make_host_mesh, shard_map_consume_blend,
                                   shard_map_initiate_exchange,
                                   shard_map_pipelined_round)

    mesh = make_host_mesh(data=4, model=2)
    W = 8   # oversubscribed: W_local = 2 -> the two-ppermute roll path
    ks = jax.random.split(jax.random.key(0), 2)
    params = {"a": jax.random.normal(ks[0], (W, 20, 30)),
              "b": jax.random.normal(ks[1], (W, 6))}
    grads = jax.tree.map(lambda x: 0.1 * x, params)
    acfg = ASGDConfig(eps=0.05)
    for wf in (None, "int8"):
        gcfg = GossipConfig(shifts=(1, 3), partial_blocks=2,
                            partial_mode="leaves", delay=1, wire_format=wf)
        spec = pack_spec_w(params, block_rows=8,
                           groups=leaf_groups(params, 2), n_groups=2)
        pk = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        wire_br = spec.block_rows if wf == "int8" else None
        st = init_pipelined_gossip_state(pk, gcfg, block_rows=wire_br)
        # warm the FIFO through the GSPMD engine (3 rounds: gates open)
        for i in range(3):
            pk, st, _ = asgd_gossip_apply_pipelined(
                pk, pdw, st, jax.random.key(i), gcfg, acfg, spec)
        key = jax.random.key(3)
        sent_ref, ss_ref, bi_ref = initiate_exchange_packed(
            pk, key, gcfg, spec)
        out_ref, st_ref, m_ref = consume_exchange_packed(
            pk, pdw, st, sent_ref, ss_ref, bi_ref, gcfg, acfg, spec)
        # manual-region pipelined round must reproduce it exactly
        stacked = fifo_depth(gcfg, pipelined=True) >= 2
        ext, ext_s, ext_idx, ext_live = _fifo_head(st, stacked)
        assert ext_live is None   # non-elastic state carries no mask
        k_shift, k_blk = jax.random.split(key)
        si = jax.random.randint(k_shift, (), 0, len(gcfg.shifts))
        bi = jax.random.randint(k_blk, (), 0, 2)
        round_m = jax.jit(shard_map_pipelined_round(
            mesh, spec, gcfg, acfg, n_workers=W))
        if wf == "int8":
            out, sent, sent_s, gates = round_m(pk, pdw, ext, ext_s,
                                               ext_idx, st.step, si, bi)
            np.testing.assert_array_equal(np.asarray(sent),
                                          np.asarray(sent_ref))
            np.testing.assert_allclose(np.asarray(sent_s),
                                       np.asarray(ss_ref),
                                       rtol=1e-6, atol=1e-7)
        else:
            out, sent, gates = round_m(pk, pdw, ext, ext_idx, st.step,
                                       si, bi)
            np.testing.assert_allclose(np.asarray(sent),
                                       np.asarray(sent_ref),
                                       rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(gates),
                                      np.asarray(m_ref["gate"]))
        assert float(jnp.sum(gates)) > 0.0, "warm round must open gates"
        # overlap structure: the collective lives ONLY in the initiate
        # region; the consume region is communication-free
        init_m = jax.jit(shard_map_initiate_exchange(mesh, spec, gcfg,
                                                     n_workers=W))
        cons_m = jax.jit(shard_map_consume_blend(mesh, spec, gcfg, acfg,
                                                 n_workers=W))
        txt_i = init_m.lower(pk, si, bi).compile().as_text()
        assert "collective-permute" in txt_i, "initiate must ppermute"
        if wf == "int8":
            assert "s8[" in txt_i, "int8 payload must be on the wire"
            cons_args = (pk, pdw, ext, ext_s, ext_idx, st.step)
        else:
            cons_args = (pk, pdw, ext, ext_idx, st.step)
        out2, gates2 = cons_m(*cons_args)
        txt_c = cons_m.lower(*cons_args).compile().as_text()
        for op in ("collective-permute", "all-reduce", "all-gather",
                   "all-to-all"):
            assert op not in txt_c, f"consume region must not {op}"
        np.testing.assert_allclose(np.asarray(out2), np.asarray(out_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(gates2),
                                      np.asarray(m_ref["gate"]))
    print("PIPELINED-MESH-OK")
""")


@pytest.mark.slow
def test_shard_map_pipelined_round_matches_gspmd(run_cpu_script):
    """8-fake-device subprocess (W_local=2, both wire formats): the
    manual-region pipelined round reproduces the GSPMD pipelined engine;
    the initiate region carries the collective-permute (int8 payload on
    the int8 wire) and the consume region lowers with NO collective —
    the structural overlap proof."""
    r = run_cpu_script(PIPELINED_MESH_SCRIPT, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "PIPELINED-MESH-OK" in r.stdout

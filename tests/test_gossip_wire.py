"""Wire-format + round-1 staleness-guard tests (ISSUE 4; DESIGN.md §6).

Covers the int8 row quantizers (core/packing.py quantize_rows /
dequantize_rows / fake_quant_rows), the unified wire_roundtrip() semantics
(the staleness buffer stores carrier-dtype values in every engine), the
fused in-kernel dequantization of the resident kernel against the
bit-identical jnp fake-quant reference (gossip_blend_w_resident_ref), the
packed GSPMD engine under wire_format="int8" across partial_mode x delay,
the explicit step == 0 staleness guard in all four blend paths, the
int8-aware packed checkpoint boundary (scales transient, never written),
and (subprocess, 8 fake devices, slow) the manual-region int8 ppermute
exchange of launch.mesh.shard_map_gossip_round against the GSPMD engine.
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
                               leaf_groups, packed_row_ranges,
                               resolved_wire_format, wire_roundtrip)
from repro.core.packing import (LANE, dequantize_rows, fake_quant_rows,
                                pack_spec_w, pack_w, quantize_rows,
                                scale_blocks, unpack_w)
from repro.kernels.gossip_blend import (gossip_blend_w_resident,
                                        gossip_blend_worker_batched)
from repro.kernels.gossip_blend.ref import (gossip_blend_w_resident_ref,
                                            run_quantized_parity)


def make_params(W=4, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    return {
        "wq": jax.random.normal(ks[0], (W, 16, 8)).astype(dtype),
        "bias": jax.random.normal(ks[1], (W, 6)).astype(dtype),
        "wo": jax.random.normal(ks[2], (W, 8, 4)).astype(dtype),
    }


class TestQuantizeRows:
    @given(st.integers(0, 6), st.sampled_from([1, 2, 4]))
    @settings(max_examples=10, deadline=None)
    def test_roundtrip_error_bounded(self, seed, br):
        """|x - dq(q(x))| <= scale/2 per tile (round-to-nearest int8)."""
        blk = jax.random.normal(jax.random.key(seed), (3, 8, LANE))
        q, scales = quantize_rows(blk, br)
        assert q.dtype == jnp.int8
        assert scales.shape == (3, 8 // br)
        dq = dequantize_rows(q, scales, br)
        bound = np.asarray(scales).max() * 0.5 + 1e-7
        assert float(jnp.max(jnp.abs(dq - blk))) <= bound

    def test_zero_tiles_stay_exactly_zero(self):
        """Paper eq. 3: 'all-zero == no message' survives the wire
        bit-exactly — zero tiles quantize to zero with zero scale."""
        blk = jnp.zeros((2, 4, LANE))
        q, scales = quantize_rows(blk, 2)
        assert int(jnp.abs(q).max()) == 0
        assert float(jnp.abs(scales).max()) == 0.0
        np.testing.assert_array_equal(
            np.asarray(dequantize_rows(q, scales, 2)), np.zeros(blk.shape))

    def test_mixed_zero_and_live_tiles(self):
        blk = jnp.zeros((1, 4, LANE)).at[:, 2:].set(1.0)
        q, scales = quantize_rows(blk, 2)
        np.testing.assert_allclose(np.asarray(scales),
                                   [[0.0, 1.0 / 127.0]], rtol=1e-6)
        dq = dequantize_rows(q, scales, 2)
        np.testing.assert_allclose(np.asarray(dq[:, 2:]), 1.0, rtol=1e-6)
        assert float(jnp.abs(dq[:, :2]).max()) == 0.0

    def test_fake_quant_is_the_composition(self):
        blk = jax.random.normal(jax.random.key(3), (2, 6, LANE))
        q, scales = quantize_rows(blk, 3)
        np.testing.assert_array_equal(
            np.asarray(fake_quant_rows(blk, 3)),
            np.asarray(dequantize_rows(q, scales, 3)))

    def test_unaligned_rows_raise(self):
        with pytest.raises(ValueError):
            quantize_rows(jnp.zeros((2, 5, LANE)), 2)
        with pytest.raises(ValueError):
            scale_blocks(5, 2)


class TestWireRoundtrip:
    def test_resolution_and_backcompat(self):
        assert resolved_wire_format(GossipConfig()) is None
        # pre-wire_format configs: payload_dtype alone selects "dtype"
        assert resolved_wire_format(
            GossipConfig(payload_dtype=jnp.bfloat16)) == "dtype"
        assert resolved_wire_format(GossipConfig(wire_format="int8")) \
            == "int8"
        with pytest.raises(ValueError):
            resolved_wire_format(GossipConfig(wire_format="dtype"))
        with pytest.raises(ValueError):
            resolved_wire_format(GossipConfig(wire_format="int4"))
        with pytest.raises(ValueError, match="ignores payload_dtype"):
            # conflicting combination: int8 would silently drop the cast
            resolved_wire_format(GossipConfig(wire_format="int8",
                                              payload_dtype=jnp.bfloat16))

    def test_dtype_roundtrip_values_and_carrier_dtype(self):
        cfg = GossipConfig(wire_format="dtype", payload_dtype=jnp.bfloat16)
        tree = make_params()
        out = wire_roundtrip(tree, cfg)
        for k in tree:
            assert out[k].dtype == tree[k].dtype
            np.testing.assert_array_equal(
                np.asarray(out[k]),
                np.asarray(tree[k].astype(jnp.bfloat16)
                           .astype(tree[k].dtype)))

    def test_int8_fake_quant_per_worker(self):
        cfg = GossipConfig(wire_format="int8")
        tree = {"w": jax.random.normal(jax.random.key(1), (4, 32))}
        out = wire_roundtrip(tree, cfg)
        assert out["w"].dtype == tree["w"].dtype
        # per-worker absmax scale: error bounded by scale/2 per row
        scale = np.abs(np.asarray(tree["w"])).max(axis=1) / 127.0
        err = np.abs(np.asarray(out["w"] - tree["w"])).max(axis=1)
        assert (err <= scale * 0.5 + 1e-7).all()
        # zeros stay zero
        z = wire_roundtrip({"w": jnp.zeros((2, 8))}, cfg)
        assert float(jnp.abs(z["w"]).max()) == 0.0

    @pytest.mark.parametrize("mode", ["leaves", "rows"])
    @pytest.mark.parametrize("wf,pd", [("dtype", jnp.bfloat16),
                                       ("int8", None)])
    def test_buffer_dtype_unified_across_modes(self, mode, wf, pd):
        """ISSUE-4 satellite: the staleness buffer stores CARRIER-dtype
        values in both partial modes (historically 'rows' cast after the
        roll and 'leaves' before it, leaving wire-dtype buffers)."""
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1,), partial_blocks=2, partial_mode=mode,
                           wire_format=wf, payload_dtype=pd)
        state = init_gossip_state(params, cfg)
        for leaf in jax.tree.leaves(state.buf):
            assert leaf.dtype == jnp.float32
        params, state, _ = asgd_gossip_apply(
            params, grads, state, jax.random.key(0), cfg,
            ASGDConfig(eps=0.05))
        for leaf in jax.tree.leaves(state.buf):
            assert leaf.dtype == jnp.float32


def _garbage_buffer(state, params, grads, eps):
    """Overwrite the init staleness buffer with an 'ahead' state that the
    Parzen gate WOULD admit (w - 0.5*eps*dw lies along the local descent
    direction) — only the explicit step==0 guard keeps round 1 clean."""
    ahead = jax.tree.map(
        lambda w, g: (w.astype(jnp.float32)
                      - 0.5 * eps * g.astype(jnp.float32)).astype(w.dtype),
        params, grads)
    buf = jax.tree.map(lambda b, a: a[..., :b.shape[-1]]
                       if b.shape != a.shape else a,
                       state.buf, ahead)
    return type(state)(buf=buf, buf_idx=state.buf_idx, step=state.step)


class TestRound1StalenessGuard:
    """ISSUE-4 satellite: with delay > 0, round 1 must NOT blend the init
    buffer even when its content would pass the Parzen gate — the guard is
    the explicit step == 0 check, not eq.-3 zero-detection."""

    @pytest.mark.parametrize("mode", ["leaves", "rows"])
    @pytest.mark.parametrize("use_fused", [False, True])
    def test_round1_is_plain_sgd_despite_admissible_buffer(self, mode,
                                                           use_fused):
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1,), partial_blocks=2, partial_mode=mode,
                           delay=1)
        acfg = ASGDConfig(eps=0.05, use_fused=use_fused)
        state = init_gossip_state(params, cfg)
        if mode == "leaves":   # block-tree shapes differ in 'rows' mode
            state = _garbage_buffer(state, params, grads, acfg.eps)
        else:
            from repro.core.gossip import slice_rows
            ahead = jax.tree.map(lambda w, g: w - 0.5 * 0.05 * g,
                                 params, grads)
            state = type(state)(
                buf=slice_rows(ahead, state.buf_idx, 2),
                buf_idx=state.buf_idx, step=state.step)
        new_params, new_state, m = asgd_gossip_apply(
            params, grads, state, jax.random.key(0), cfg, acfg)
        assert float(jnp.sum(m["gate"])) == 0.0
        for k in params:
            np.testing.assert_allclose(
                np.asarray(new_params[k]),
                np.asarray(params[k] - 0.05 * grads[k]),
                rtol=1e-6, atol=1e-7)
        # round 2 blends a genuinely received block: gates may open
        _, _, m2 = asgd_gossip_apply(
            new_params, grads, new_state, jax.random.key(1), cfg, acfg)
        assert float(jnp.sum(m2["gate"])) > 0.0

    def test_packed_round1_is_plain_sgd(self):
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        p = 2
        cfg = GossipConfig(shifts=(1,), partial_blocks=p, delay=1)
        acfg = ASGDConfig(eps=0.05)
        spec = pack_spec_w(params, block_rows=2,
                           groups=leaf_groups(params, p), n_groups=p)
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        state = init_packed_gossip_state(packed)
        # admissible garbage: an 'ahead' state in the buffered partition
        r0, r1 = spec.group_row_ranges[0]
        ahead = packed - 0.5 * acfg.eps * pdw
        state = type(state)(
            buf=jnp.zeros_like(packed).at[:, r0:r1].set(ahead[:, r0:r1]),
            buf_idx=state.buf_idx, step=state.step)
        out, new_state, m = asgd_gossip_apply_packed(
            packed, pdw, state, jax.random.key(0), cfg, acfg, spec)
        assert float(jnp.sum(m["gate"])) == 0.0
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(packed - acfg.eps * pdw),
                                   rtol=1e-6, atol=1e-7)
        _, _, m2 = asgd_gossip_apply_packed(
            out, pdw, new_state, jax.random.key(1), cfg, acfg, spec)
        assert float(jnp.sum(m2["gate"])) > 0.0

    def test_delay0_round1_can_blend(self):
        """delay=0 blends the just-received block — no guard applies."""
        params = make_params()
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1,), partial_blocks=1, delay=0)
        state = init_gossip_state(params, cfg)
        _, _, m = asgd_gossip_apply(params, grads, state, jax.random.key(3),
                                    cfg, ASGDConfig(eps=0.05))
        assert float(jnp.sum(m["gate"])) > 0.0


class TestQuantizedResidentKernel:
    """The fused in-kernel dequantization must agree with (a) the jnp
    fake-quant reference bit-for-bit in the gates, and (b) the same kernel
    fed the pre-dequantized f32 external."""

    @pytest.mark.parametrize("rr", [(0, 8), (4, 12), (8, 8)])
    @pytest.mark.parametrize("elastic", [False, True])
    def test_matches_fake_quant_reference(self, rr, elastic):
        W, P, R, br = 3, 2, 16, 4
        ks = jax.random.split(jax.random.key(0), 2)
        w3 = jax.random.normal(ks[0], (W, R, LANE))
        d3 = jax.random.normal(ks[1], (W, R, LANE)) * 0.1
        ext_f = w3[:, None] - 0.5 * d3[:, None] * jnp.arange(
            1, P + 1, dtype=jnp.float32)[None, :, None, None]
        q, scales = quantize_rows(ext_f, br)
        rr_arr = jnp.asarray(rr, jnp.int32)
        out_k, g_k = gossip_blend_w_resident(
            w3, d3, q, rr_arr, 0.05, ext_scales=scales, block_rows=br,
            elastic=elastic)
        out_r, g_r = gossip_blend_w_resident_ref(
            w3, d3, q, rr_arr, 0.05, ext_scales=scales, block_rows=br,
            elastic=elastic)
        np.testing.assert_array_equal(np.asarray(g_k), np.asarray(g_r))
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-6, atol=1e-6)

    def test_fused_dequant_equals_prematerialized_f32(self):
        W, R, br = 2, 8, 4
        w3 = jax.random.normal(jax.random.key(5), (W, R, LANE))
        d3 = 0.1 * jnp.sign(w3)
        q, scales = quantize_rows((w3 - 0.5 * d3)[:, None], br)
        rr = jnp.asarray([0, R], jnp.int32)
        out_q, g_q = gossip_blend_w_resident(
            w3, d3, q, rr, 0.05, ext_scales=scales, block_rows=br)
        ext_f = dequantize_rows(q, scales, br)
        out_f, g_f = gossip_blend_w_resident(
            w3, d3, ext_f, rr, 0.05, block_rows=br)
        np.testing.assert_array_equal(np.asarray(g_q), np.asarray(g_f))
        np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_f),
                                   rtol=1e-6, atol=1e-6)

    def test_gate_scale_closes_gates(self):
        W, R, br = 2, 8, 4
        w3 = jax.random.normal(jax.random.key(6), (W, R, LANE))
        d3 = 0.1 * jnp.sign(w3)
        ext = (w3 - 0.5 * d3)[:, None]
        rr = jnp.asarray([0, R], jnp.int32)
        out0, g0 = gossip_blend_w_resident(
            w3, d3, ext, rr, 0.05, block_rows=br,
            gate_scale=jnp.float32(0.0))
        assert float(jnp.sum(g0)) == 0.0
        np.testing.assert_allclose(np.asarray(out0),
                                   np.asarray(w3 - 0.05 * d3),
                                   rtol=1e-6, atol=1e-6)
        _, g1 = gossip_blend_w_resident(
            w3, d3, ext, rr, 0.05, block_rows=br,
            gate_scale=jnp.float32(1.0))
        assert float(jnp.sum(g1)) > 0.0
        _, gw = gossip_blend_worker_batched(
            w3, d3, ext, 0.05, block_rows=br, gate_scale=jnp.float32(0.0))
        assert float(jnp.sum(gw)) == 0.0


class TestRowsModeRanges:
    """packed_row_ranges 'rows' mode: block alignment applies ONLY to the
    int8 wire (the float kernels handle unaligned ranges), and an
    alignment that would leave empty partitions raises instead of
    silently shipping the whole state on 1/p of the rounds."""

    def test_float_wire_keeps_exact_chunks(self):
        params = {"w": jax.random.normal(jax.random.key(0), (2, 3, LANE))}
        spec = pack_spec_w(params, block_rows=4)   # rows padded to 4
        cfg = GossipConfig(partial_mode="rows", partial_blocks=3)
        # unaligned ceil(4/3)=2 chunks — the pre-int8 behaviour, unchanged
        assert packed_row_ranges(spec, cfg) == ((0, 2), (2, 4), (4, 4))

    def test_int8_wire_aligns_chunks_all_nonempty(self):
        params = {"w": jax.random.normal(jax.random.key(0), (2, 8, LANE))}
        spec = pack_spec_w(params, block_rows=2)
        cfg = GossipConfig(partial_mode="rows", partial_blocks=3,
                           wire_format="int8")
        ranges = packed_row_ranges(spec, cfg)
        assert ranges == ((0, 2), (2, 6), (6, 8))
        assert all(r1 > r0 and r0 % 2 == 0 and r1 % 2 == 0
                   for r0, r1 in ranges)

    def test_int8_wire_unsatisfiable_raises(self):
        params = {"w": jax.random.normal(jax.random.key(0), (2, 2, LANE))}
        spec = pack_spec_w(params, block_rows=2)
        cfg = GossipConfig(partial_mode="rows", partial_blocks=2,
                           wire_format="int8")
        with pytest.raises(ValueError, match="unsatisfiable"):
            packed_row_ranges(spec, cfg)


class TestQuantizedWireParity:
    """Tentpole acceptance: the packed GSPMD engine under
    wire_format="int8" follows the step-by-step jnp fake-quant reference
    across partial_mode x delay.  The whole side-by-side driver
    (run_quantized_parity) is shared with the quantized_wire benchmark
    gate, so the two assert the same thing."""

    @pytest.mark.parametrize("mode", ["leaves", "rows"])
    @pytest.mark.parametrize("delay", [0, 1])
    def test_int8_engine_matches_fake_quant_reference(self, mode, delay):
        W, p = 4, 2
        if mode == "leaves":
            params = make_params(W=W)
        else:
            # 'rows' + int8 needs >= p * block_rows packed rows (block-
            # aligned chunks must all be non-empty — packed_row_ranges)
            params = {"w": jax.random.normal(jax.random.key(0),
                                             (W, 8, LANE))}
        grads = jax.tree.map(lambda x: 0.05 * jnp.sign(x), params)
        cfg = GossipConfig(shifts=(1, 2), partial_blocks=p,
                           partial_mode=mode, delay=delay,
                           wire_format="int8")
        acfg = ASGDConfig(eps=0.05)
        spec = (pack_spec_w(params, block_rows=2,
                            groups=leaf_groups(params, p), n_groups=p)
                if mode == "leaves"
                else pack_spec_w(params, block_rows=2))
        per_round, state = run_quantized_parity(params, grads, cfg, acfg,
                                                spec, rounds=4)
        for r in per_round:
            np.testing.assert_array_equal(np.asarray(r["engine_gate"]),
                                          np.asarray(r["ref_gate"]))
            np.testing.assert_allclose(np.asarray(r["engine_packed"]),
                                       np.asarray(r["ref_packed"]),
                                       rtol=1e-6, atol=1e-6)
        # the engine really carried a QUANTIZED buffer the whole way
        assert state.buf.dtype == jnp.int8
        assert state.buf_scales.shape == (W, spec.rows // spec.block_rows)

    def test_int8_gates_open_and_blend_converges(self):
        """End-to-end sanity: int8-wire gossip still contracts the worker
        ensemble (the quantization error does not defeat the attraction)."""
        W = 4
        params = {"w": jnp.arange(W, dtype=jnp.float32)[:, None, None]
                  * jnp.ones((W, 8, 4))}
        grads = {"w": jnp.ones((W, 8, 4)) * 0.1}
        cfg = GossipConfig(shifts=(1,), partial_blocks=1,
                           partial_mode="leaves", delay=1,
                           wire_format="int8")
        acfg = ASGDConfig(eps=0.05)
        spec = pack_spec_w(params, block_rows=1,
                           groups=leaf_groups(params, 1), n_groups=1)
        packed = pack_w(params, spec)
        pdw = pack_w(grads, spec)
        state = init_packed_gossip_state(packed, cfg,
                                         block_rows=spec.block_rows)
        opened = 0.0
        for i in range(30):
            packed, state, m = asgd_gossip_apply_packed(
                packed, pdw, state, jax.random.key(i), cfg, acfg, spec)
            opened += float(m["n_good"])
        assert opened > 0.0
        spread0 = float(jnp.var(jnp.asarray([0., 1., 2., 3.])))
        w = unpack_w(packed, spec)["w"][:, 0, 0]
        assert float(jnp.var(w)) < spread0


class TestPackedInt8Checkpoint:
    def test_scales_transient_and_interop(self, tmp_path):
        """save_checkpoint_packed on an int8-wire state writes the SAME
        canonical float layout as a float-wire run (scales never hit
        disk); loading back re-quantizes bit-exactly."""
        from repro.checkpoint import (load_checkpoint_packed,
                                      save_checkpoint_packed)

        params = make_params()
        p = 2
        cfg = GossipConfig(shifts=(1,), partial_blocks=p,
                           wire_format="int8")
        spec = pack_spec_w(params, block_rows=2,
                           groups=leaf_groups(params, p), n_groups=p)
        packed = pack_w(params, spec)
        ranges = packed_row_ranges(spec, cfg)
        buf_q, buf_s = exchange_packed(packed, ranges, jnp.int32(0),
                                       jnp.int32(1), cfg,
                                       block_rows=spec.block_rows)
        gossip = init_packed_gossip_state(packed, cfg,
                                          block_rows=spec.block_rows)
        gossip.buf, gossip.buf_scales = buf_q, buf_s
        gossip.buf_idx = jnp.int32(1)
        state = {"params": packed, "gossip": gossip, "opt": jnp.int32(0),
                 "step": jnp.int32(5)}
        path = tmp_path / "ck_int8.msgpack"
        save_checkpoint_packed(path, state, spec)

        # the file layout equals a float-wire checkpoint's (leaf count and
        # shapes) — scales were canonicalized away
        import msgpack
        payload = msgpack.unpackb(path.read_bytes(), raw=False)
        f_state = {"params": packed,
                   "gossip": init_packed_gossip_state(packed),
                   "opt": jnp.int32(0), "step": jnp.int32(5)}
        f_path = tmp_path / "ck_f32.msgpack"
        save_checkpoint_packed(f_path, f_state, spec)
        payload_f = msgpack.unpackb(f_path.read_bytes(), raw=False)
        assert len(payload["leaves"]) == len(payload_f["leaves"])

        # int8 -> int8 roundtrip: buffer and scales recovered bit-exactly
        like = {"params": jnp.zeros_like(packed),
                "gossip": init_packed_gossip_state(
                    packed, cfg, block_rows=spec.block_rows),
                "opt": jnp.int32(0), "step": jnp.int32(0)}
        back = load_checkpoint_packed(path, like, spec)
        np.testing.assert_allclose(np.asarray(back["params"]),
                                   np.asarray(packed), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(back["gossip"].buf),
                                      np.asarray(buf_q))
        np.testing.assert_allclose(np.asarray(back["gossip"].buf_scales),
                                   np.asarray(buf_s), rtol=1e-6)
        assert int(back["gossip"].buf_idx) == 1 and int(back["step"]) == 5

        # ...and the same file restores into a FLOAT-wire packed state
        like_f = {"params": jnp.zeros_like(packed),
                  "gossip": init_packed_gossip_state(packed),
                  "opt": jnp.int32(0), "step": jnp.int32(0)}
        back_f = load_checkpoint_packed(path, like_f, spec)
        np.testing.assert_allclose(
            np.asarray(back_f["gossip"].buf),
            np.asarray(dequantize_rows(buf_q, buf_s, spec.block_rows)),
            rtol=1e-6, atol=1e-7)
        assert back_f["gossip"].buf_scales is None


INT8_PPERMUTE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.asgd import ASGDConfig
    from repro.core.gossip import (GossipConfig, exchange_packed,
                                   init_packed_gossip_state, leaf_groups,
                                   packed_row_ranges)
    from repro.core.packing import pack_spec_w, pack_w
    from repro.kernels.gossip_blend import gossip_blend_w_resident
    from repro.launch.mesh import make_host_mesh, shard_map_gossip_round

    mesh = make_host_mesh(data=4, model=2)
    W = 8   # oversubscribed: W_local = 2 -> the two-ppermute roll path
    ks = jax.random.split(jax.random.key(0), 2)
    params = {"a": jax.random.normal(ks[0], (W, 20, 30)),
              "b": jax.random.normal(ks[1], (W, 6))}
    grads = jax.tree.map(lambda x: 0.1 * x, params)
    gcfg = GossipConfig(shifts=(1, 3), partial_blocks=2,
                        partial_mode="leaves", delay=1, wire_format="int8")
    acfg = ASGDConfig(eps=0.05)
    spec = pack_spec_w(params, block_rows=8,
                       groups=leaf_groups(params, 2), n_groups=2)
    packed, pdw = pack_w(params, spec), pack_w(grads, spec)
    ranges = packed_row_ranges(spec, gcfg)
    buf_q, buf_s = exchange_packed(packed, ranges, jnp.int32(0),
                                   jnp.int32(1), gcfg,
                                   block_rows=spec.block_rows)

    round_m = jax.jit(shard_map_gossip_round(mesh, spec, gcfg, acfg,
                                             n_workers=W))
    rr = jnp.asarray(ranges, jnp.int32)[jnp.int32(1)]
    out_ref, gates_ref = gossip_blend_w_resident(
        packed, pdw, buf_q[:, None], rr, acfg.eps,
        ext_scales=buf_s[:, None], block_rows=spec.block_rows)
    for si in range(2):
        for bi in range(2):
            out, sent, sent_s, gates = round_m(
                packed, pdw, buf_q, buf_s, jnp.int32(1), jnp.int32(1),
                jnp.int32(si), jnp.int32(bi))
            # the in-region int8 ppermute == the GSPMD quantized roll
            sent_ref, sent_s_ref = exchange_packed(
                packed, ranges, jnp.int32(si), jnp.int32(bi), gcfg,
                block_rows=spec.block_rows)
            np.testing.assert_array_equal(np.asarray(sent),
                                          np.asarray(sent_ref))
            np.testing.assert_allclose(np.asarray(sent_s),
                                       np.asarray(sent_s_ref),
                                       rtol=1e-6, atol=1e-7)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(out_ref),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_array_equal(np.asarray(gates),
                                          np.asarray(gates_ref[:, 0]))
    txt = round_m.lower(packed, pdw, buf_q, buf_s, jnp.int32(1),
                        jnp.int32(1), jnp.int32(0),
                        jnp.int32(0)).compile().as_text()
    assert "collective-permute" in txt, "exchange must be collective-permute"
    assert "s8[" in txt, "int8 payload must appear in the lowered HLO"
    # round-1 staleness guard inside the manual region: step=0 closes gates
    out0, _, _, gates0 = round_m(packed, pdw, buf_q, buf_s, jnp.int32(1),
                                 jnp.int32(0), jnp.int32(0), jnp.int32(0))
    assert float(jnp.sum(gates0)) == 0.0
    np.testing.assert_allclose(np.asarray(out0),
                               np.asarray(packed - acfg.eps * pdw),
                               rtol=1e-6, atol=1e-6)
    print("INT8-PPERMUTE-OK")
""")


@pytest.mark.slow
def test_shard_map_int8_round_matches_gspmd(run_cpu_script):
    """8-fake-device subprocess: the manual-region int8 exchange+blend
    (quantize -> int8 ppermute + scales -> fused-dequant resident kernel,
    all inside ONE shard_map) reproduces the GSPMD quantized roll and the
    single-shard kernel, and the step==0 staleness guard holds inside the
    manual region."""
    r = run_cpu_script(INT8_PPERMUTE_SCRIPT, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "INT8-PPERMUTE-OK" in r.stdout

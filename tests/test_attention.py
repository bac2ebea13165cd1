"""Attention-path equivalence tests: chunked flash (the jnp scan, and the
fused Pallas kernel in interpret mode) vs dense oracle across mask types,
GQA expansion, qk-norm/bias variants; and which path a layer takes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.models import blocks
from repro.models.common import (AttnSpec, attention_decode, attention_dense,
                                 attention_flash, attention_flash_kernel,
                                 causal_mask, init_attention, init_kv_cache,
                                 prefix_mask, sliding_mask)

# The kernel feeds the MXU bf16 q/k/v and probabilities (f32 scores and
# accumulators); on the CPU the jnp paths run in f32.  Readings of the
# kernel against the dense oracle, ||kernel - dense|| / ||dense||: the
# output 2.9e-3, the gradients of x and of wq/wk/wv/wo 2.7e-3-3.5e-3
# (S 512, Dh 64, W 2); elementwise, |diff| <= 7.5e-3 where |out| <= 3.8.
# The limits give that reading about 3x of room; the kernel with its
# causal mask or its softmax scale left out reads 0.77 or more.
KERNEL_REL_NORM = 1e-2
KERNEL_TOL = {"rtol": 2e-2, "atol": 2e-2}
SCAN_TOL = {"rtol": 2e-4, "atol": 2e-5}


def flash_path(path):
    """The two forms of full-sequence causal attention under test."""
    if path == "scan":
        return lambda params, spec, x, pos: attention_flash(
            params, spec, x, pos, block_q=512, block_k=512)
    return attention_flash_kernel


def make(spec_kw=None, B=2, S=2048, seed=0):
    spec = AttnSpec(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                    **(spec_kw or {}))
    params = init_attention(jax.random.key(seed), spec)
    x = 0.5 * jax.random.normal(jax.random.key(seed + 1), (B, S, 64))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    return spec, params, x, positions


class TestFlashVsDense:
    @pytest.mark.parametrize("path", ["scan", "kernel"])
    @pytest.mark.parametrize(
        "S", [2048, pytest.param(4096, marks=pytest.mark.slow)])
    def test_causal(self, S, path):
        spec, params, x, pos = make(B=1, S=S)
        out_f = flash_path(path)(params, spec, x, pos)
        qpos = pos[0]
        out_d = attention_dense(params, spec, x, pos,
                                causal_mask(qpos, qpos))
        np.testing.assert_allclose(
            out_f, out_d, **(SCAN_TOL if path == "scan" else KERNEL_TOL))

    @pytest.mark.parametrize("window", [64, 512, 1500])
    def test_sliding_window(self, window):
        spec, params, x, pos = make(B=1, S=2048)
        out_f = attention_flash(params, spec, x, pos, window=window,
                                block_q=512, block_k=512)
        qpos = pos[0]
        out_d = attention_dense(params, spec, x, pos,
                                sliding_mask(qpos, qpos, window))
        np.testing.assert_allclose(out_f, out_d, rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("prefix", [64, 700])
    def test_prefix_lm(self, prefix):
        spec, params, x, pos = make(B=1, S=2048)
        out_f = attention_flash(params, spec, x, pos, prefix_len=prefix,
                                block_q=512, block_k=512)
        qpos = pos[0]
        out_d = attention_dense(params, spec, x, pos,
                                prefix_mask(qpos, qpos, prefix))
        np.testing.assert_allclose(out_f, out_d, rtol=2e-4, atol=2e-5)

    @pytest.mark.slow
    @pytest.mark.parametrize("path", ["scan", "kernel"])
    def test_qkv_bias_and_qknorm_variants(self, path):
        variants = [{"qkv_bias": True}, {"qk_norm": True},
                    {"qkv_bias": True, "qk_norm": True},
                    {"softcap": 30.0}, {"use_rope": False}]
        if path == "kernel":  # softcapped scores never take the kernel
            variants.remove({"softcap": 30.0})
        for kw in variants:
            spec, params, x, pos = make(spec_kw=kw, B=1, S=2048)
            out_f = flash_path(path)(params, spec, x, pos)
            qpos = pos[0]
            out_d = attention_dense(params, spec, x, pos,
                                    causal_mask(qpos, qpos))
            tol = ({"rtol": 3e-4, "atol": 3e-5} if path == "scan"
                   else KERNEL_TOL)
            np.testing.assert_allclose(out_f, out_d, err_msg=str(kw), **tol)


class TestFlashKernel:
    """The fused kernel against both jnp paths, forward and gradients, as
    the train step calls it: under vmap over workers and checkpoint."""

    W, S = 2, 512

    def grads(self, attn, spec, params, x, pos):
        ct = jax.random.normal(jax.random.key(7), x.shape)

        def loss(params, x):
            y = jax.vmap(jax.checkpoint(lambda p, xw: attn(p, spec, xw, pos)),
                         in_axes=(None, 0))(params, x)
            return jnp.sum(y * ct), y

        (_, y), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(params, x)
        return y, {"x": g[1], **g[0]}

    def test_kernel_matches_its_oracle(self):
        """The kernel alone on (B, S, H, Dh) bf16 operands, against the f32
        oracle, output and the three operands' gradients."""
        ks = jax.random.split(jax.random.key(3), 4)
        q, k, v, ct = (jax.random.normal(kk, (1, self.S, 4, 64))
                       for kk in ks)
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))

        def vjp(f):
            y, pull = jax.vjp(lambda *a: f(*a).astype(jnp.float32), q, k, v)
            return (y, *pull(ct))
        for name, a, b in zip(["out", "dq", "dk", "dv"],
                              vjp(flash_attention), vjp(flash_attention_ref)):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            assert rel < KERNEL_REL_NORM, (name, rel)

    def test_output_and_grads_match_scan_and_dense(self):
        spec = AttnSpec(d_model=128, n_heads=4, n_kv_heads=2, head_dim=64)
        params = init_attention(jax.random.key(0), spec)
        x = 0.5 * jax.random.normal(jax.random.key(1),
                                    (self.W, 1, self.S, 128))
        pos = jnp.broadcast_to(jnp.arange(self.S), (1, self.S))
        mask = causal_mask(pos[0], pos[0])
        y_k, g_k = self.grads(attention_flash_kernel, spec, params, x, pos)
        others = {
            "scan": lambda p, s, xw, ps: attention_flash(p, s, xw, ps),
            "dense": lambda p, s, xw, ps: attention_dense(p, s, xw, ps,
                                                          mask),
        }
        for name, attn in others.items():
            y, g = self.grads(attn, spec, params, x, pos)
            pairs = {"out": (y_k, y), **{k: (g_k[k], g[k]) for k in g}}
            assert set(pairs) == {"out", "x", "wq", "wk", "wv", "wo"}
            for leaf, (a, b) in pairs.items():
                rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                assert rel < KERNEL_REL_NORM, (name, leaf, rel)
            np.testing.assert_allclose(y_k, y, **KERNEL_TOL)


def _smollm_layer(ltype="G", **cfg_kw):
    """smollm-135m's attention at a reduced width, and its full-sequence
    call at S 2048 (the scan / kernel size) under the model.attention
    scope."""
    cfg = dataclasses.replace(get_arch("smollm-135m"), d_model=64,
                              n_heads=4, n_kv_heads=2, head_dim=16, **cfg_kw)
    spec = blocks.attn_spec(cfg)
    params = init_attention(jax.random.key(0), spec)
    S = 2048
    x = jax.ShapeDtypeStruct((1, S, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (1, S))

    def call(p, h, prefix_len=0):
        return blocks._attend_full(cfg, spec, p, h, pos, ltype, prefix_len)
    return params, x, call


class TestDispatch:
    def test_plain_causal_on_cpu_lowers_to_the_scan(self):
        params, x, call = _smollm_layer()
        lowered = jax.jit(jax.grad(lambda p, h: jnp.sum(call(p, h)))
                          ).lower(params, x)
        text = lowered.as_text()
        assert "tpu_custom_call" not in text
        assert "flash_mha" not in text
        assert "while" in text          # the scan over KV blocks
        assert "model.attention" in lowered.as_text(debug_info=True)

    @pytest.mark.parametrize("variant", ["causal", "window", "prefix",
                                         "softcap", "encoder"])
    def test_only_plain_causal_traces_the_kernel(self, variant,
                                                 monkeypatch):
        calls = []
        monkeypatch.setattr(
            blocks, "attention_flash_kernel",
            lambda *a, **k: calls.append(1) or attention_flash(*a[:4]))
        ltype, cfg_kw, prefix = "G", {}, 0
        if variant == "window":
            ltype, cfg_kw = "L", {"sliding_window": 512}
        elif variant == "prefix":
            prefix = 64
        elif variant == "softcap":
            cfg_kw = {"attn_softcap": 30.0}
        elif variant == "encoder":
            ltype = "E"
        params, x, call = _smollm_layer(ltype, **cfg_kw)
        jax.eval_shape(lambda p, h: call(p, h, prefix), params, x)
        assert bool(calls) == (variant == "causal")


class TestDecodeVsDense:
    def test_decode_matches_last_row_of_dense(self):
        spec, params, x, pos = make(B=2, S=64)
        qpos = pos[0]
        out_d = attention_dense(params, spec, x, pos,
                                causal_mask(qpos, qpos))
        # build cache from the first S-1 positions, decode position S-1
        from repro.models.common import _project_qkv
        _, k, v = _project_qkv(params, spec, x, pos)
        cache = init_kv_cache(2, 64, 2, 16, jnp.float32)
        cache["k"] = cache["k"].at[:, :63].set(k[:, :63])
        cache["v"] = cache["v"].at[:, :63].set(v[:, :63])
        out, _ = attention_decode(params, spec, x[:, 63:64],
                                  jnp.int32(63), cache)
        np.testing.assert_allclose(out[:, 0], out_d[:, 63],
                                   rtol=2e-3, atol=2e-4)

    def test_decode_sliding_window_ignores_old(self):
        """With window w, keys older than w must not affect the output."""
        spec, params, x, pos = make(B=1, S=64)
        from repro.models.common import _project_qkv
        _, k, v = _project_qkv(params, spec, x, pos)
        cache = init_kv_cache(1, 64, 2, 16, jnp.float32)
        cache["k"] = cache["k"].at[:, :63].set(k[:, :63])
        cache["v"] = cache["v"].at[:, :63].set(v[:, :63])
        out1, _ = attention_decode(params, spec, x[:, 63:64],
                                   jnp.int32(63), cache, window=8)
        # corrupt keys outside the window: result must not change
        cache2 = dict(cache)
        cache2["k"] = cache["k"].at[:, :40].set(99.0)
        cache2["v"] = cache["v"].at[:, :40].set(-99.0)
        out2, _ = attention_decode(params, spec, x[:, 63:64],
                                   jnp.int32(63), cache2, window=8)
        np.testing.assert_allclose(out1, out2, rtol=1e-5)

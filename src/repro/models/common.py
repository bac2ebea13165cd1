"""Shared model building blocks: norms, RoPE, masks, attention.

Pure-JAX (no flax): params are nested dicts of jnp arrays; every block is a
pair of functions ``init_*(key, cfg...) -> params`` and ``apply(params, x)``.

Attention comes in four execution forms:
  * ``attention_dense``  — materializes (S, S) scores; smoke-test scale only.
  * ``attention_flash``  — chunked online-softmax (scan over KV blocks inside
    a scan over Q blocks); O(block_q * block_k) live memory. This is the
    train/prefill path at 4k-32k sequence lengths: XLA does NOT fuse
    softmax(QK^T)V into a flash pattern by itself, and a materialized
    32768^2 score tensor is ~4GB/head — the dry-run memory analysis gates
    this (see EXPERIMENTS.md §Dry-run).
  * ``attention_flash_kernel`` — the same for plain causal layers on a TPU,
    in fused Pallas kernels (kernels/flash_attention).
  * ``attention_decode`` — one query position against a KV cache.

GQA throughout: n_kv_heads <= n_heads, queries grouped onto KV heads.
Sliding-window masking implements the local-attention layers of gemma-3 and
recurrentgemma; prefix (bidirectional) masking implements PaliGemma.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import flash_attention

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis=-2, dtype=jnp.float32):
    """LeCun-normal (fan-in) — the de-facto default for LM training."""
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d, dtype=jnp.float32):
    return {"scale": jnp.zeros((d,), dtype)}


def rmsnorm(params, x, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    # gemma-style (1 + scale): zero-init scale == identity
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)


def init_layernorm(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(x.dtype)


def make_norm(norm_type):
    if norm_type == "rmsnorm":
        return init_rmsnorm, rmsnorm
    return init_layernorm, layernorm


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim, theta):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                     / head_dim)


def apply_rope(x, positions, theta=10000.0):
    """x: (..., S, H, Dh), positions: (..., S) int32."""
    freqs = rope_freqs(x.shape[-1], theta)                    # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    angles = angles[..., None, :]                              # (..., S, 1, Dh/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# masks  (True == may attend)
# ---------------------------------------------------------------------------

def causal_mask(q_pos, k_pos):
    return q_pos[:, None] >= k_pos[None, :]


def sliding_mask(q_pos, k_pos, window):
    c = causal_mask(q_pos, k_pos)
    return c & (q_pos[:, None] - k_pos[None, :] < window)


def prefix_mask(q_pos, k_pos, prefix_len):
    """PaliGemma prefix-LM: bidirectional over the first prefix_len
    positions, causal afterwards."""
    return causal_mask(q_pos, k_pos) | (k_pos[None, :] < prefix_len)


# ---------------------------------------------------------------------------
# attention parameter block
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    softcap: float | None = None


def init_attention(key, spec: AttnSpec, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    D, H, KV, Dh = spec.d_model, spec.n_heads, spec.n_kv_heads, spec.head_dim
    p = {
        "wq": dense_init(ks[0], (D, H, Dh), in_axis=0, dtype=dtype),
        "wk": dense_init(ks[1], (D, KV, Dh), in_axis=0, dtype=dtype),
        "wv": dense_init(ks[2], (D, KV, Dh), in_axis=0, dtype=dtype),
        "wo": dense_init(ks[3], (H, Dh, D), in_axis=1, dtype=dtype),
    }
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((H, Dh), dtype)
        p["bk"] = jnp.zeros((KV, Dh), dtype)
        p["bv"] = jnp.zeros((KV, Dh), dtype)
    if spec.qk_norm:
        p["q_norm"] = init_rmsnorm(Dh, dtype)
        p["k_norm"] = init_rmsnorm(Dh, dtype)
    return p


def _project_qkv(params, spec: AttnSpec, x, positions):
    """x: (B, S, D) -> q: (B, S, H, Dh), k/v: (B, S, KV, Dh)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if spec.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if spec.qk_norm:  # qwen3-style per-head RMS norm before RoPE
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if spec.use_rope:
        q = apply_rope(q, positions, spec.rope_theta)
        k = apply_rope(k, positions, spec.rope_theta)
    return q, k, v


def _gqa_expand(k, n_heads):
    """(B, S, KV, Dh) -> (B, S, H, Dh) by repeating KV heads."""
    kv = k.shape[-2]
    if kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // kv, axis=-2)


def _softcap(scores, cap):
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


# ---------------------------------------------------------------------------
# dense attention (smoke-test scale)
# ---------------------------------------------------------------------------

def attention_dense(params, spec: AttnSpec, x, positions, mask):
    """mask: (S, S) bool (True == attend). Materializes scores — small S only."""
    q, k, v = _project_qkv(params, spec, x, positions)
    k = _gqa_expand(k, spec.n_heads)
    v = _gqa_expand(v, spec.n_heads)
    scale = spec.head_dim ** -0.5
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * scale
    scores = _softcap(scores, spec.softcap)
    scores = jnp.where(mask[None, None], scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
    return jnp.einsum("bqhk,hkd->bqd", out, params["wo"])


# ---------------------------------------------------------------------------
# flash attention (chunked online softmax) — train/prefill path
# ---------------------------------------------------------------------------

def attention_flash(params, spec: AttnSpec, x, positions, *,
                    window: int | None = None, prefix_len: int | None = None,
                    block_q: int = 512, block_k: int = 1024):
    """Causal (optionally sliding-window / prefix) chunked attention.

    Scans over Q blocks; inside, scans over KV blocks with running
    (max, sum, acc) online-softmax state. Sliding-window layers skip KV
    blocks wholly outside the window via masking (XLA hoists the band
    structure after unrolling the block mask). On a TPU, plain causal
    layers take :func:`attention_flash_kernel` instead (the dispatch is
    ``blocks._attend_full``); this path serves the CPU, sliding windows,
    prefix masks and softcapped scores.
    """
    B, S, D = x.shape
    q, k, v = _project_qkv(params, spec, x, positions)
    k = _gqa_expand(k, spec.n_heads)
    v = _gqa_expand(v, spec.n_heads)
    H, Dh = spec.n_heads, spec.head_dim
    scale = Dh ** -0.5

    bq = min(block_q, S)
    bk = min(block_k, S)
    nq = -(-S // bq)
    nk = -(-S // bk)
    pad_s = nq * bq  # assume S divisible by bq/bk in production shapes
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)

    qb = q.reshape(B, nq, bq, H, Dh).transpose(1, 0, 3, 2, 4)  # (nq,B,H,bq,Dh)
    kb = k.reshape(B, nk, bk, H, Dh).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(B, nk, bk, H, Dh).transpose(1, 0, 3, 2, 4)
    posb = positions.reshape(B, nq, bq) if positions.ndim == 2 else None
    qpos = positions[0] if positions.ndim == 2 else positions  # (S,)

    def q_block(qi, q_i):
        q_i = q_i * scale
        qp = jax.lax.dynamic_slice_in_dim(qpos, qi * bq, bq)   # (bq,)

        def kv_block(carry, inp):
            m, l, acc = carry
            ki, k_j, v_j = inp
            kp = jax.lax.dynamic_slice_in_dim(qpos, ki * bk, bk)
            s = jnp.einsum("bhqd,bhkd->bhqk", q_i, k_j).astype(jnp.float32)
            s = _softcap(s, spec.softcap)
            msk = causal_mask(qp, kp)
            if window is not None:
                msk = msk & (qp[:, None] - kp[None, :] < window)
            if prefix_len is not None:
                msk = msk | (kp[None, :] < prefix_len)
            s = jnp.where(msk[None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = (acc * alpha[..., None]
                       + jnp.einsum("bhqk,bhkd->bhqd", p.astype(v_j.dtype),
                                    v_j).astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        a0 = jnp.zeros((B, H, bq, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(x.dtype)  # (B,H,bq,Dh)

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq), qb))                   # (nq,B,H,bq,Dh)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, S, H, Dh)
    return jnp.einsum("bqhk,hkd->bqd", out, params["wo"])


def attention_flash_kernel(params, spec: AttnSpec, x, positions, *,
                           interpret=None):
    """Plain causal attention through the fused Pallas flash kernels
    (kernels/flash_attention): the score and probability tiles stay in
    VMEM, and KV blocks above the diagonal are skipped. Masks by index, so
    ``positions`` must count 0..S-1, as the full-sequence forward's do."""
    q, k, v = _project_qkv(params, spec, x, positions)
    out = flash_attention(q, _gqa_expand(k, spec.n_heads),
                          _gqa_expand(v, spec.n_heads), interpret=interpret)
    return jnp.einsum("bqhk,hkd->bqd", out.astype(x.dtype), params["wo"])


# ---------------------------------------------------------------------------
# decode attention (1 query vs KV cache)
# ---------------------------------------------------------------------------

def attention_decode(params, spec: AttnSpec, x, pos, cache,
                     *, window: int | None = None):
    """x: (B, 1, D); pos: scalar int32 — current position; cache: dict with
    k/v (B, S_max, KV, Dh) and is updated functionally at `pos`.

    Returns (out (B, 1, D), new_cache). Reads the full cache each step —
    the decode roofline is cache-bandwidth-bound by construction.
    """
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(params, spec, x, positions)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
    k = _gqa_expand(k_cache, spec.n_heads)
    v = _gqa_expand(v_cache, spec.n_heads)
    scale = spec.head_dim ** -0.5
    s = jnp.einsum("bqhk,bshk->bhqs", q * scale, k).astype(jnp.float32)
    s = _softcap(s, spec.softcap)
    kpos = jnp.arange(cache["k"].shape[1])
    valid = kpos[None, :] <= pos
    if window is not None:
        valid = valid & (pos - kpos[None, :] < window)
    s = jnp.where(valid[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqs,bshk->bqhk", p, v)
    out = jnp.einsum("bqhk,hkd->bqd", out, params["wo"])
    return out, {"k": k_cache, "v": v_cache}


def init_kv_cache(batch, max_seq, n_kv_heads, head_dim, dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, max_seq, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_seq, n_kv_heads, head_dim), dtype),
    }


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def activation(name):
    return {
        "silu": jax.nn.silu,
        "gelu": partial(jax.nn.gelu, approximate=True),
        "gelu_exact": partial(jax.nn.gelu, approximate=False),
        "relu": jax.nn.relu,
    }[name]

"""Per-layer blocks: init + apply for every layer type.

Layer types ('G' global attn, 'L' sliding-window attn, 'R' RG-LRU,
'S' mamba-2 SSD) share a pre-norm residual skeleton:

    x = x + mixer(norm1(x))          temporal mixing
    x = x + cross_attn(norm_c(x))    (whisper decoder only)
    x = x + ffn(norm2(x))            channel mixing (absent for 'S': the
                                      mamba block already channel-mixes)

Each apply has three modes: full-sequence (train/prefill, optionally
returning the decode cache) and single-token decode against a cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels.flash_attention import supports as flash_kernel_supports
from .common import (AttnSpec, attention_decode, attention_dense,
                     attention_flash, attention_flash_kernel, causal_mask,
                     init_attention, init_kv_cache, make_norm, prefix_mask,
                     sliding_mask)
from .mlp import apply_mlp, apply_mlp_nonglu, init_mlp, init_mlp_nonglu
from .moe import apply_moe, apply_moe_decode, init_moe
from .rglru import (apply_rglru, apply_rglru_decode, init_rglru,
                    init_rglru_cache)
from .ssm import apply_ssd, apply_ssd_decode, init_ssd, init_ssd_cache

FLASH_MIN_SEQ = 2048  # below this, dense attention is cheaper & simpler


def attn_spec(cfg: ModelConfig) -> AttnSpec:
    return AttnSpec(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim,
        qkv_bias=cfg.qkv_bias,
        qk_norm=cfg.qk_norm,
        rope_theta=cfg.rope_theta,
        use_rope=cfg.use_rope,
        softcap=cfg.attn_softcap,
    )


def cross_spec(cfg: ModelConfig) -> AttnSpec:
    """Cross-attention: no RoPE (positions don't align), no qk-norm."""
    return AttnSpec(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim,
        use_rope=False,
    )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, ltype: str, *, is_decoder=True,
               dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    norm_init, _ = make_norm(cfg.norm_type)
    p = {"ln1": norm_init(cfg.d_model, dtype)}
    if ltype in ("G", "L", "E"):
        p["attn"] = init_attention(ks[0], attn_spec(cfg), dtype)
    elif ltype == "R":
        p["rglru"] = init_rglru(ks[0], cfg.d_model,
                                cfg.lru_width or cfg.d_model, dtype=dtype)
    elif ltype == "S":
        p["ssm"] = init_ssd(
            ks[0], cfg.d_model, expand=cfg.ssm_expand,
            head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
            n_groups=cfg.ssm_groups, dtype=dtype)
    else:
        raise ValueError(ltype)

    if cfg.cross_attention and is_decoder and ltype != "E":
        p["ln_cross"] = norm_init(cfg.d_model, dtype)
        p["cross"] = init_attention(ks[1], cross_spec(cfg), dtype)

    if cfg.d_ff > 0 and ltype != "S":
        p["ln2"] = norm_init(cfg.d_model, dtype)
        if cfg.n_experts > 0 and is_decoder:
            p["moe"] = init_moe(ks[2], cfg.d_model, cfg.d_ff,
                                cfg.n_experts, dtype)
        elif cfg.glu_mlp:
            p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff, dtype)
        else:
            p["mlp"] = init_mlp_nonglu(ks[2], cfg.d_model, cfg.d_ff, dtype)
    return p


def init_layer_cache(cfg: ModelConfig, ltype: str, batch, max_seq,
                     dtype=jnp.bfloat16, cross_seq=0):
    if ltype in ("G", "L"):
        c = init_kv_cache(batch, max_seq, cfg.n_kv_heads,
                          cfg.resolved_head_dim, dtype)
    elif ltype == "R":
        c = init_rglru_cache(batch, cfg.lru_width or cfg.d_model)
    elif ltype == "S":
        c = init_ssd_cache(batch, cfg.d_model, expand=cfg.ssm_expand,
                           head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
                           n_groups=cfg.ssm_groups)
    else:
        raise ValueError(ltype)
    if cfg.cross_attention and cross_seq:
        c = dict(c)
        c["cross_k"] = jnp.zeros(
            (batch, cross_seq, cfg.n_kv_heads, cfg.resolved_head_dim), dtype)
        c["cross_v"] = jnp.zeros_like(c["cross_k"])
    return c


# ---------------------------------------------------------------------------
# full-sequence apply (train / prefill)
# ---------------------------------------------------------------------------

def _ffn(cfg, p, x, norm):
    if "moe" in p:
        h, aux = apply_moe(p["moe"], norm(p["ln2"], x),
                           cfg.experts_per_token, act=cfg.act,
                           capacity_factor=cfg.capacity_factor,
                           dispatch_groups=cfg.moe_dispatch_groups)
        return x + h, aux
    if "mlp" in p:
        h = norm(p["ln2"], x)
        h = (apply_mlp(p["mlp"], h, cfg.act) if cfg.glu_mlp
             else apply_mlp_nonglu(p["mlp"], h, cfg.act))
        return x + h, jnp.float32(0.0)
    return x, jnp.float32(0.0)


@jax.named_scope("model.attention")
def _attend_full(cfg, spec, p_attn, h, positions, ltype, prefix_len):
    from .hints import constrain
    S = h.shape[1]
    B = h.shape[0]
    window = cfg.sliding_window if ltype == "L" else None
    batch_shard = cfg.attn_batch_shard and B >= 16 and B % 16 == 0
    if batch_shard:
        h = constrain(h, "model", None, None)
    if S >= FLASH_MIN_SEQ and S % 512 == 0:
        def scan_path(p, x, pos):
            return attention_flash(
                p, spec, x, pos, window=window,
                prefix_len=(prefix_len if ltype == "G" or window is None
                            else None))

        def kernel_path(p, x, pos):
            return attention_flash_kernel(p, spec, x, pos, interpret=False)

        if (ltype != "E" and window is None and not prefix_len
                and spec.softcap is None
                and flash_kernel_supports(S, spec.head_dim)):
            # plain causal: the fused kernels where the step is lowered
            # for a TPU, the jnp scan everywhere else
            out = jax.lax.platform_dependent(p_attn, h, positions,
                                             tpu=kernel_path,
                                             default=scan_path)
        else:
            out = scan_path(p_attn, h, positions)
        if batch_shard:
            out = constrain(out, "model", None, None)
        return out
    qpos = positions[0] if positions.ndim == 2 else positions
    if ltype == "E":
        mask = jnp.ones((S, S), bool)          # encoder: bidirectional
    elif prefix_len:
        mask = prefix_mask(qpos, qpos, prefix_len)
    elif window is not None:
        mask = sliding_mask(qpos, qpos, window)
    else:
        mask = causal_mask(qpos, qpos)
    return attention_dense(p_attn, spec, h, positions, mask)


def apply_layer(cfg: ModelConfig, ltype: str, p, x, positions, *,
                enc_out=None, prefix_len=0, return_cache=False,
                cache_len=None):
    """Full-sequence layer. Returns (x, aux_loss, cache_or_None)."""
    from .hints import constrain
    _, norm = make_norm(cfg.norm_type)
    if cfg.seq_parallel:
        # sequence parallelism: elementwise/norm segments run with the S
        # axis sharded over `model`; XLA inserts all-gather/reduce-scatter
        # pairs at the matmul boundaries (§Perf)
        x = constrain(x, None, "model", None)
    h = norm(p["ln1"], x)
    cache = None
    if ltype in ("G", "L", "E"):
        spec = attn_spec(cfg)
        out = _attend_full(cfg, spec, p["attn"], h, positions, ltype,
                           prefix_len)
        if return_cache:
            # recompute K/V once for the cache (cheap vs attention itself)
            from .common import _project_qkv
            _, k, v = _project_qkv(p["attn"], spec, h, positions)
            S = x.shape[1]
            L = cache_len or S
            cache = init_kv_cache(x.shape[0], L, cfg.n_kv_heads,
                                  cfg.resolved_head_dim, jnp.bfloat16)
            cache["k"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(jnp.bfloat16), 0, axis=1)
            cache["v"] = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(jnp.bfloat16), 0, axis=1)
        x = x + out
    elif ltype == "R":
        if cfg.attn_batch_shard and h.shape[0] >= 16 and h.shape[0] % 16 == 0:
            # batch-sharded recurrent block: the RG-LRU gate matmuls
            # (Wl x Wl, contraction-sharded) otherwise all-reduce the f32
            # (B,S,Wl) activations every layer (§Perf recurrentgemma)
            from .hints import constrain
            h = constrain(h, "model", None, None)
        out, h_fin = apply_rglru(p["rglru"], h)
        if return_cache:
            cw = p["rglru"]["conv_w"].shape[0]
            cache = {"conv": jnp.zeros(
                (x.shape[0], cw - 1, h_fin.shape[-1]), x.dtype), "h": h_fin}
        x = x + out
    elif ltype == "S":
        out, h_fin = apply_ssd(
            p["ssm"], h, chunk=cfg.ssm_chunk, head_dim=cfg.ssm_head_dim,
            state=cfg.ssm_state, n_groups=cfg.ssm_groups)
        if return_cache:
            c0 = init_ssd_cache(
                x.shape[0], cfg.d_model, expand=cfg.ssm_expand,
                head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
                n_groups=cfg.ssm_groups)
            cache = {"conv": c0["conv"], "ssm": h_fin}
        x = x + out

    if "cross" in p and enc_out is not None:
        hc = norm(p["ln_cross"], x)
        out = _cross_full(cfg, p["cross"], hc, enc_out)
        x = x + out
        if return_cache and cache is not None:
            from .common import _project_qkv
            spec_c = cross_spec(cfg)
            epos = jnp.zeros(enc_out.shape[:2], jnp.int32)
            _, ck, cv = _project_qkv(p["cross"], spec_c, enc_out, epos)
            cache["cross_k"] = ck.astype(jnp.bfloat16)
            cache["cross_v"] = cv.astype(jnp.bfloat16)

    if cfg.seq_parallel:
        x = constrain(x, None, "model", None)
    x, aux = _ffn(cfg, p, x, norm)
    return x, aux, cache


def _cross_full(cfg, p_cross, x, enc_out):
    """Full-sequence cross-attention (decoder queries, encoder keys)."""
    spec = cross_spec(cfg)
    from .common import _gqa_expand, _project_qkv
    B, Sq, _ = x.shape
    qpos = jnp.zeros((B, Sq), jnp.int32)
    q, _, _ = _project_qkv(p_cross, spec, x, qpos)
    epos = jnp.zeros(enc_out.shape[:2], jnp.int32)
    _, k, v = _project_qkv(p_cross, spec, enc_out, epos)
    k = _gqa_expand(k, spec.n_heads)
    v = _gqa_expand(v, spec.n_heads)
    s = jnp.einsum("bqhk,bshk->bhqs", q * spec.head_dim ** -0.5, k)
    pterm = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqs,bshk->bqhk", pterm, v)
    return jnp.einsum("bqhk,hkd->bqd", out, p_cross["wo"])


# ---------------------------------------------------------------------------
# decode apply (1 token vs cache)
# ---------------------------------------------------------------------------

def apply_layer_decode(cfg: ModelConfig, ltype: str, p, x, pos, cache, *,
                       enc_out=None):
    """x: (B,1,D); pos: scalar int32. Returns (x, new_cache)."""
    _, norm = make_norm(cfg.norm_type)
    h = norm(p["ln1"], x)
    new_cache = dict(cache)
    if ltype in ("G", "L"):
        window = cfg.sliding_window if ltype == "L" else None
        kv = {"k": cache["k"], "v": cache["v"]}
        out, kv = attention_decode(p["attn"], attn_spec(cfg), h, pos, kv,
                                   window=window)
        new_cache.update(kv)
        x = x + out
    elif ltype == "R":
        rc = {"conv": cache["conv"], "h": cache["h"]}
        out, rc = apply_rglru_decode(p["rglru"], h, rc)
        new_cache.update(rc)
        x = x + out
    elif ltype == "S":
        sc = {"conv": cache["conv"], "ssm": cache["ssm"]}
        out, sc = apply_ssd_decode(
            p["ssm"], h, sc, head_dim=cfg.ssm_head_dim,
            state=cfg.ssm_state, n_groups=cfg.ssm_groups)
        new_cache.update(sc)
        x = x + out

    if "cross" in p and "cross_k" in cache:
        hc = norm(p["ln_cross"], x)
        out = _cross_decode(cfg, p["cross"], hc, cache)
        x = x + out

    if "moe" in p:
        h2 = norm(p["ln2"], x)
        out, _ = apply_moe_decode(p["moe"], h2, cfg.experts_per_token,
                                  act=cfg.act)
        x = x + out
    elif "mlp" in p:
        h2 = norm(p["ln2"], x)
        out = (apply_mlp(p["mlp"], h2, cfg.act) if cfg.glu_mlp
               else apply_mlp_nonglu(p["mlp"], h2, cfg.act))
        x = x + out
    return x, new_cache


def _cross_decode(cfg, p_cross, x, cache):
    spec = cross_spec(cfg)
    from .common import _gqa_expand, _project_qkv
    B = x.shape[0]
    qpos = jnp.zeros((B, 1), jnp.int32)
    q, _, _ = _project_qkv(p_cross, spec, x, qpos)
    k = _gqa_expand(cache["cross_k"].astype(x.dtype), spec.n_heads)
    v = _gqa_expand(cache["cross_v"].astype(x.dtype), spec.n_heads)
    s = jnp.einsum("bqhk,bshk->bhqs", q * spec.head_dim ** -0.5, k)
    pterm = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqs,bshk->bqhk", pterm, v)
    return jnp.einsum("bqhk,hkd->bqd", out, p_cross["wo"])

"""Plain reference of a llama-family dense decoder (SmolLM): token
embedding, pre-norm blocks of causal grouped-query attention with rotary
positions and a SiLU-gated MLP, a final norm, the tied output head, and the
mean next-token cross-entropy.

Written from the published architecture in straightforward jax.numpy.  It
imports nothing of the system under test.  Attention materializes the full
(S, S) scores; each block is rematerialized in the backward pass so the
whole model fits one chip at the published widths.

Departures from the published SmolLM, which the trainer makes and this
reference follows so that the two compute the same function:
- RMSNorm is ``x / rms(x) * (1 + scale)`` with the scale initialized to 0
  (the same function as the published ones-initialized scale) and eps 1e-6;
- the initial weights follow the trainer's documented scheme: LeCun-normal
  projections, N(0, 0.02) embeddings, drawn from the seed's key splits.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6


def _dims(cfg):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return (d, h, cfg["num_key_value_heads"], d // h,
            cfg["intermediate_size"], cfg["vocab_size"])


def program_sizes(cfg) -> dict:
    """The configuration's sizes under the names the trainer's model
    configuration gives them."""
    return {"n_layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"], "vocab": cfg["vocab_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "d_ff": cfg["intermediate_size"], "rope_theta": cfg["rope_theta"]}


def matmul_params(cfg) -> int:
    """Weights that enter a matmul once per token (the tied output head
    included, the embedding lookup not)."""
    d, h, kv, dh, f, v = _dims(cfg)
    layer = d * dh * (h + 2 * kv) + h * dh * d + 3 * d * f
    return cfg["num_hidden_layers"] * layer + v * d


def flops_per_token(cfg, seq: int) -> int:
    """Forward plus backward FLOPs per trained token: 6 N plus attention's
    12 L S d_attn (scores and values, forward and backward, over the full
    S x S square)."""
    return (6 * matmul_params(cfg)
            + 12 * cfg["num_hidden_layers"] * seq * cfg["hidden_size"])


def padded_vocab(cfg) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def _lecun(key, shape, fan_in):
    return jax.random.normal(key, shape) / math.sqrt(fan_in)


def init(cfg, key):
    """Initial weights from the seed's key, in float32, as a tree keyed
    like the trainer's parameters (layers stacked on a leading axis)."""
    d, h, kv, dh, f, _ = _dims(cfg)
    n = cfg["num_hidden_layers"]
    ks = jax.random.split(key, 8)
    layers = []
    for i in range(n):
        lk = jax.random.split(jax.random.fold_in(ks[2], i), 6)
        ak = jax.random.split(lk[0], 6)
        mk = jax.random.split(lk[2], 3)
        layers.append({
            "ln1": {"scale": jnp.zeros((d,), jnp.float32)},
            "attn": {"wq": _lecun(ak[0], (d, h, dh), d),
                     "wk": _lecun(ak[1], (d, kv, dh), d),
                     "wv": _lecun(ak[2], (d, kv, dh), d),
                     "wo": _lecun(ak[3], (h, dh, d), dh)},
            "ln2": {"scale": jnp.zeros((d,), jnp.float32)},
            "mlp": {"gate": _lecun(mk[0], (d, f), d),
                    "up": _lecun(mk[1], (d, f), d),
                    "down": _lecun(mk[2], (f, d), f)},
        })
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {"embed": jax.random.normal(ks[0], (padded_vocab(cfg), d)) * 0.02,
            "final_norm": {"scale": jnp.zeros((d,), jnp.float32)},
            "scan": {"pos0": stacked}, "tail": {}}


def rmsnorm(scale, x):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + NORM_EPS) * (1 + scale)


def rope(x, theta):
    """Rotary positions, rotate-half form.  x: (B, S, heads, Dh)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None] * inv)[None, :, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(cfg, prec, x, p):
    d, h, kv, dh, _, _ = _dims(cfg)
    dt = x.dtype
    a = p["attn"]
    y = rmsnorm(p["ln1"]["scale"], x)
    q = jnp.einsum("bsd,dhk->bshk", y, a["wq"], precision=prec)
    k = jnp.einsum("bsd,dhk->bshk", y, a["wk"], precision=prec)
    v = jnp.einsum("bsd,dhk->bshk", y, a["wv"], precision=prec)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    s = jnp.einsum("bqhk,bthk->bhqt", q, k, precision=prec) * (dh ** -0.5)
    n = x.shape[1]
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(causal, s, jnp.asarray(-1e30, s.dtype))
    pr = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("bhqt,bthk->bqhk", pr, v, precision=prec)
    x = x + jnp.einsum("bqhk,hkd->bqd", o, a["wo"], precision=prec)
    m = p["mlp"]
    y = rmsnorm(p["ln2"]["scale"], x)
    g = jnp.einsum("bsd,df->bsf", y, m["gate"], precision=prec)
    u = jnp.einsum("bsd,df->bsf", y, m["up"], precision=prec)
    return x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, m["down"],
                          precision=prec)


def loss(cfg, params, tokens, *, prec=jax.lax.Precision.HIGHEST):
    """Mean next-token cross-entropy of ``tokens`` (B, S), computed in the
    dtype of ``params``."""
    x = params["embed"][tokens]

    def body(x, p):
        return jax.checkpoint(lambda x, p: _block(cfg, prec, x, p))(x, p), None

    x, _ = jax.lax.scan(body, x, params["scan"]["pos0"])
    x = rmsnorm(params["final_norm"]["scale"], x)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"], precision=prec)
    col = jnp.arange(logits.shape[-1])
    logits = jnp.where(col < cfg["vocab_size"], logits,
                       jnp.asarray(-1e30, logits.dtype))
    lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll.astype(jnp.float32))

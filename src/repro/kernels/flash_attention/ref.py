"""Pure-jnp oracle for the causal flash-attention kernels: the (S, S)
scores in f32, masked, softmaxed, at the highest matmul precision."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v):
    """q, k, v: (B, S, H, Dh) -> (B, S, H, Dh) f32, causal by index."""
    hi = jax.lax.Precision.HIGHEST
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    S = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision=hi)

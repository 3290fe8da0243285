"""Shared model layers: RMSNorm, RoPE, embeddings, gated MLP.

Convention: every layer is an (init, apply) pair of pure functions over
plain dict pytrees.  Parameter leaf names are stable and pattern-matched by
sharding/rules.py to assign logical axes — keep names in sync with that
table when adding parameters.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def he_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in or shape[0]
    return (jax.random.normal(key, shape) * (2.0 / fan_in) ** 0.5).astype(dtype)


# ---------------------------------------------------------------- RMSNorm
def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dtype)


# ------------------------------------------------------------------- RoPE
def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1 * mscale * ln(factor) + 1``
    (1 at factor <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim: int, theta: float, factor: float,
                     original_max_position: int, beta_fast: float,
                     beta_slow: float) -> jnp.ndarray:
    """YaRN inverse frequencies (DeepSeek-V2): the plain ``theta^(-2i/D)``
    where a pair turns more than ``beta_fast`` times over the original
    context, the same divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, and a linear ramp between."""
    def correction_dim(rotations):
        return (head_dim * math.log(original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = rope_frequencies(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                       # 1 = the unscaled frequency
    return extra / factor * (1.0 - keep) + extra * keep


def rope_freqs_for(cfg, head_dim: int) -> jnp.ndarray:
    """The inverse frequencies ``cfg`` rotates a ``head_dim`` slice by."""
    if cfg.yarn_factor:
        return yarn_frequencies(head_dim, cfg.rope_theta, cfg.yarn_factor,
                                cfg.yarn_original_max_position,
                                cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    return rope_frequencies(head_dim, cfg.rope_theta)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float, freqs: jnp.ndarray | None = None) -> jnp.ndarray:
    """x: [..., S, H, D]; positions: broadcastable to [..., S]; ``freqs``
    overrides the plain ``theta`` frequencies (YaRN)."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(d, theta)                   # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :]                      # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------- Embeddings
def embedding_init(key, vocab: int, d: int, dtype) -> dict:
    return {"embedding": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def embed(params: dict, tokens: jnp.ndarray, scale: bool = False) -> jnp.ndarray:
    x = jnp.take(params["embedding"], tokens, axis=0)
    if scale:
        x = x * jnp.asarray(x.shape[-1] ** 0.5, x.dtype)
    return x


def unembed(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Logits in the model dtype; the loss upcasts to f32 *inside* its
    reductions so no f32 [B,S,V] tensor is ever materialized."""
    return jnp.einsum("...d,vd->...v", x, params["embedding"])


def lm_head_init(key, d: int, vocab: int, dtype) -> dict:
    return {"unembedding": (jax.random.normal(key, (d, vocab)) * 0.02).astype(dtype)}


def lm_head(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("...d,dv->...v", x, params["unembedding"])


# ------------------------------------------------------ Gated MLP (dense)
def mlp_init(key, d: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"wi_gate": he_init(k1, (d, d_ff), dtype),
            "wi_up": he_init(k2, (d, d_ff), dtype),
            "wo": he_init(k3, (d_ff, d), dtype, fan_in=d_ff)}


def mlp_apply(params: dict, x: jnp.ndarray, act: str) -> jnp.ndarray:
    gate = jnp.einsum("...d,df->...f", x, params["wi_gate"])
    up = jnp.einsum("...d,df->...f", x, params["wi_up"])
    g = jax.nn.silu(gate) if act == "silu" else jax.nn.gelu(gate)
    return jnp.einsum("...f,fd->...d", g * up, params["wo"])

"""Attention: GQA (with qk-norm, sliding window) and MLA (compressed-latent
KV cache), each with full-sequence (train/prefill) and single-token decode
paths.

KV cache layouts:
  * GQA  : k/v  [B, S_cache, KV, D]  (cache_mode 'full') or [B, W, KV, D]
           ring buffer (cache_mode 'ring', SWA only — §Perf lever: the ring
           cache bounds decode memory traffic by the window instead of the
           full context).
  * MLA  : c_kv [B, S_cache, kv_lora_rank], k_rope [B, S_cache, rope_dim]
           — the compressed latents are cached, not per-head K/V; decode
           uses the absorbed-projection form so per-step FLOPs and cache
           bytes scale with the latent rank.
RoPE is applied at write time with absolute positions (relative-consistent
under the dot product), which is what makes the ring buffer sound.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import (apply_rope, he_init, rmsnorm, rmsnorm_init,
                                 rope_freqs_for, yarn_mscale)


class KVCache(NamedTuple):
    k: jnp.ndarray            # GQA: [B, S, KV, D] / MLA: c_kv [B, S, R]
    v: jnp.ndarray            # GQA: [B, S, KV, D] / MLA: k_rope [B, S, Dr]


class QuantKVCache(NamedTuple):
    """int8 KV cache (kv_quant=true): per-(token, head) absmax scales.

    Halves decode HBM capacity and (with a fused dequant kernel on TPU)
    cache read traffic; the XLA dry-run path dequantizes explicitly, so the
    bytes-accessed metric does not credit the read saving — see
    EXPERIMENTS.md §Perf H3 it2 for the honest accounting.
    """
    k: jnp.ndarray            # int8 [B, S, KV, D]
    v: jnp.ndarray            # int8 [B, S, KV, D]
    k_scale: jnp.ndarray      # f32 [B, S, KV]
    v_scale: jnp.ndarray      # f32 [B, S, KV]


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[..., D] -> (int8 values, f32 absmax scale over D)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    q = jnp.round(x.astype(jnp.float32)
                  / jnp.maximum(scale[..., None], 1e-8)).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# =================================================================== GQA
def gqa_init(key, cfg: ArchConfig, dtype) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    params = {
        "wq": he_init(ks[0], (d, h * hd), dtype),
        "wk": he_init(ks[1], (d, kv * hd), dtype),
        "wv": he_init(ks[2], (d, kv * hd), dtype),
        "wo": he_init(ks[3], (h * hd, d), dtype, fan_in=h * hd),
    }
    if cfg.qk_norm:
        params["q_norm"] = rmsnorm_init(hd, dtype)
        params["k_norm"] = rmsnorm_init(hd, dtype)
    return params


def _project_qkv(params, x, cfg: ArchConfig, positions):
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, params["wq"]).reshape(b, s, h, hd)
    k = jnp.einsum("bsd,de->bse", x, params["wk"]).reshape(b, s, kv, hd)
    v = jnp.einsum("bsd,de->bse", x, params["wv"]).reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, softcap=None):
    """q [B,S,H,D] x k/v [B,T,KV,D] grouped-query attention core."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    q = q.reshape(b, s, kv, g, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)


def causal_mask(s: int, t: int, q_offset, window: int | None) -> jnp.ndarray:
    """[1,1,1,s,t] boolean mask; q_offset = absolute position of query 0."""
    q_pos = q_offset + jnp.arange(s)[:, None]
    k_pos = jnp.arange(t)[None, :]
    m = k_pos <= q_pos
    if window is not None:
        m &= k_pos > q_pos - window
    return m[None, None, None]


def _sdpa_q_chunked(q, k, v, cfg: ArchConfig, chunk: int, softcap=None):
    """Query-chunked attention (§Perf lever, attn_impl='chunked'):
    processes Q in blocks of `chunk` rows via lax.scan so the score matrix
    materialized at any instant is [chunk, S] instead of [S, S] — the
    XLA-level analogue of the Pallas flash kernel for the dry-run path."""
    b, s, h, d = q.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    qc = jnp.moveaxis(q.reshape(b, nc, chunk, h, d), 1, 0)   # [nc,b,c,h,d]

    def body(_, inp):
        qi, idx = inp
        mask = causal_mask(chunk, s, idx * chunk, cfg.window)
        return None, _sdpa(qi, k, v, mask, softcap)

    _, out = jax.lax.scan(body, None, (qc, jnp.arange(nc)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def gqa_forward(params, x, cfg: ArchConfig, positions) -> tuple[jnp.ndarray, KVCache]:
    """Full-sequence path (train/prefill). Returns output and fresh cache."""
    s = x.shape[1]
    q, k, v = _project_qkv(params, x, cfg, positions)
    if cfg.attn_impl == "chunked" and s > cfg.attn_chunk:
        out = _sdpa_q_chunked(q, k, v, cfg, cfg.attn_chunk, cfg.logit_softcap)
    else:
        mask = causal_mask(s, s, 0, cfg.window)
        out = _sdpa(q, k, v, mask, cfg.logit_softcap)
    out = jnp.einsum("bse,ed->bsd", out.reshape(*out.shape[:2], -1),
                     params["wo"])
    return out, KVCache(k=k, v=v)


def gqa_decode(params, x, cache, pos, cfg: ArchConfig,
               cache_mode: str = "full"):
    """Single-token decode. x: [B,1,d]; pos: scalar absolute position.
    cache: KVCache or QuantKVCache (int8)."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, cfg, positions)
    s_cache = cache.k.shape[1]
    if cache_mode == "ring":
        slot = pos % s_cache
    else:
        slot = pos
    quant = isinstance(cache, QuantKVCache)
    if quant:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        new_cache = QuantKVCache(
            k=jax.lax.dynamic_update_slice_in_dim(cache.k, kq, slot, axis=1),
            v=jax.lax.dynamic_update_slice_in_dim(cache.v, vq, slot, axis=1),
            k_scale=jax.lax.dynamic_update_slice_in_dim(
                cache.k_scale, ks, slot, axis=1),
            v_scale=jax.lax.dynamic_update_slice_in_dim(
                cache.v_scale, vs, slot, axis=1))
        k = dequantize_kv(new_cache.k, new_cache.k_scale, k_new.dtype)
        v = dequantize_kv(new_cache.v, new_cache.v_scale, v_new.dtype)
    else:
        k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, slot, axis=1)
    idx = jnp.arange(s_cache)
    if cache_mode == "ring":
        # entry at slot i holds absolute position: reconstructible but we
        # only need validity: entries written so far and within the window.
        age = (slot - idx) % s_cache          # 0 = just written
        valid = (age <= jnp.minimum(pos, s_cache - 1))
        if cfg.window is not None:
            valid &= age < cfg.window
        mask = valid[None, None, None, None, :]
    else:
        valid = idx <= pos
        if cfg.window is not None:
            valid &= idx > pos - cfg.window
        mask = valid[None, None, None, None, :]
    out = _sdpa(q, k, v, mask, cfg.logit_softcap)
    out = jnp.einsum("bse,ed->bsd", out.reshape(b, 1, -1), params["wo"])
    return out, (new_cache if quant else KVCache(k=k, v=v))


# =================================================================== MLA
def mla_init(key, cfg: ArchConfig, dtype) -> dict:
    d, h = cfg.d_model, cfg.num_heads
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    d_nope, d_rope, d_v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    if r_q:
        q = {"wq_a": he_init(ks[0], (d, r_q), dtype),
             "q_a_norm": rmsnorm_init(r_q, dtype),
             "wq_b": he_init(ks[1], (r_q, h * (d_nope + d_rope)), dtype,
                             fan_in=r_q)}
    else:                   # no query latent: one d -> H*(nope+rope) map
        q = {"wq": he_init(ks[0], (d, h * (d_nope + d_rope)), dtype)}
    return {
        **q,
        "wkv_a": he_init(ks[2], (d, r_kv + d_rope), dtype),
        "kv_a_norm": rmsnorm_init(r_kv, dtype),
        "wk_b": he_init(ks[3], (r_kv, h * d_nope), dtype, fan_in=r_kv),
        "wv_b": he_init(ks[4], (r_kv, h * d_v), dtype, fan_in=r_kv),
        "wo": he_init(ks[5], (h * d_v, d), dtype, fan_in=h * d_v),
    }


def _mla_q(params, x, cfg: ArchConfig, positions):
    b, s, _ = x.shape
    h = cfg.num_heads
    d_nope, d_rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if "wq" in params:
        q = jnp.einsum("bsd,de->bse", x, params["wq"])
    else:
        q = rmsnorm(params["q_a_norm"],
                    jnp.einsum("bsd,dr->bsr", x, params["wq_a"]), cfg.norm_eps)
        q = jnp.einsum("bsr,re->bse", q, params["wq_b"])
    q = q.reshape(b, s, h, d_nope + d_rope)
    q_nope, q_rope = q[..., :d_nope], q[..., d_nope:]
    q_rope = _mla_rope(q_rope, positions, cfg)
    return q_nope, q_rope


def _mla_rope(x, positions, cfg: ArchConfig):
    """Rope on the decoupled rope slice, with YaRN's frequencies and its
    cos/sin factor ``mscale(f, mscale) / mscale(f, mscale_all_dim)`` where
    the config scales (1 for DeepSeek-V2-Lite's equal pair)."""
    out = apply_rope(x, positions, cfg.rope_theta,
                     rope_freqs_for(cfg, x.shape[-1]))
    if cfg.yarn_factor:
        f = cfg.yarn_factor
        m = (yarn_mscale(f, cfg.yarn_mscale)
             / yarn_mscale(f, cfg.yarn_mscale_all_dim))
        if m != 1.0:
            out = (out.astype(jnp.float32) * m).astype(x.dtype)
    return out


def mla_temperature(cfg: ArchConfig) -> float:
    """YaRN's ``mscale(f, mscale_all_dim)^2`` on the softmax scale
    ``(nope + rope)^-1/2`` where the config scales rope, else 1."""
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        return yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return 1.0


def _mla_latents(params, x, cfg: ArchConfig, positions):
    r_kv, d_rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    c_kv = rmsnorm(params["kv_a_norm"], kv[..., :r_kv], cfg.norm_eps)
    k_rope = _mla_rope(kv[..., r_kv:][..., None, :], positions,
                       cfg)[..., 0, :]                       # shared head
    return c_kv, k_rope


def mla_forward(params, x, cfg: ArchConfig, positions) -> tuple[jnp.ndarray, KVCache]:
    """Full-sequence MLA (expanded form). Caches latents only."""
    b, s, _ = x.shape
    h, d_nope, d_v = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(params, x, cfg, positions)
    c_kv, k_rope = _mla_latents(params, x, cfg, positions)
    k_nope = jnp.einsum("btr,re->bte", c_kv, params["wk_b"]).reshape(
        b, s, h, d_nope)
    v = jnp.einsum("btr,re->bte", c_kv, params["wv_b"]).reshape(b, s, h, d_v)
    scale = 1.0 / jnp.sqrt(d_nope + cfg.qk_rope_head_dim)
    if mla_temperature(cfg) != 1.0:
        scale = scale * mla_temperature(cfg)

    def block(qn, qr, q_offset, c):
        scores = (jnp.einsum("bshd,bthd->bhst", qn, k_nope)
                  + jnp.einsum("bshd,btd->bhst", qr, k_rope)
                  ).astype(jnp.float32) * scale
        mask = causal_mask(c, s, q_offset, cfg.window)[:, :, 0]  # [1,1,c,t]
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhst,bthd->bshd", probs, v)

    if cfg.attn_impl == "chunked" and s > cfg.attn_chunk:
        # query-chunked (§Perf lever): [chunk, S] scores instead of [S, S]
        c = cfg.attn_chunk
        nc = s // c
        qn = jnp.moveaxis(q_nope.reshape(b, nc, c, h, d_nope), 1, 0)
        qr = jnp.moveaxis(q_rope.reshape(b, nc, c, h, cfg.qk_rope_head_dim),
                          1, 0)

        def body(_, inp):
            qn_i, qr_i, idx = inp
            return None, block(qn_i, qr_i, idx * c, c)

        _, out = jax.lax.scan(body, None, (qn, qr, jnp.arange(nc)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, -1)
    else:
        out = block(q_nope, q_rope, 0, s).reshape(b, s, -1)
    out = jnp.einsum("bse,ed->bsd", out, params["wo"])
    return out, KVCache(k=c_kv, v=k_rope)


def mla_decode(params, x, cache: KVCache, pos, cfg: ArchConfig,
               cache_mode: str = "full") -> tuple[jnp.ndarray, KVCache]:
    """Absorbed-projection decode: score via latents, never materializing
    per-head K/V for the whole cache."""
    b = x.shape[0]
    h, d_nope, d_v = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    r_kv = cfg.kv_lora_rank
    positions = jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(params, x, cfg, positions)        # [b,1,h,*]
    c_new, kr_new = _mla_latents(params, x, cfg, positions)
    s_cache = cache.k.shape[1]
    slot = pos % s_cache if cache_mode == "ring" else pos
    c_kv = jax.lax.dynamic_update_slice_in_dim(cache.k, c_new, slot, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(cache.v, kr_new, slot, axis=1)
    # absorb W_uk into the query: q_abs [b,h,r_kv]
    wk_b = params["wk_b"].reshape(r_kv, h, d_nope)
    q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, wk_b)[:, 0]  # [b,h,r]
    scores = (jnp.einsum("bhr,btr->bht", q_abs, c_kv)
              + jnp.einsum("bshd,btd->bht", q_rope, k_rope)).astype(jnp.float32)
    scores = scores / jnp.sqrt(d_nope + cfg.qk_rope_head_dim)
    if mla_temperature(cfg) != 1.0:
        scores = scores * mla_temperature(cfg)
    idx = jnp.arange(s_cache)
    if cache_mode == "ring":
        age = (slot - idx) % s_cache
        valid = age <= jnp.minimum(pos, s_cache - 1)
        if cfg.window is not None:
            valid &= age < cfg.window
    else:
        valid = idx <= pos
        if cfg.window is not None:
            valid &= idx > pos - cfg.window
    scores = jnp.where(valid[None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(c_kv.dtype)
    out_latent = jnp.einsum("bht,btr->bhr", probs, c_kv)      # [b,h,r]
    wv_b = params["wv_b"].reshape(r_kv, h, d_v)
    out = jnp.einsum("bhr,rhd->bhd", out_latent, wv_b).reshape(b, 1, -1)
    out = jnp.einsum("bse,ed->bsd", out, params["wo"])
    return out, KVCache(k=c_kv, v=k_rope)


# ========================================================== Cross-attention
def cross_attn_init(key, cfg: ArchConfig, dtype) -> dict:
    return gqa_init(key, cfg, dtype)


def cross_attn(params, x, enc_kv: KVCache, cfg: ArchConfig) -> jnp.ndarray:
    """Decoder-to-encoder attention (whisper backbone). enc_kv holds the
    encoder's projected K/V (computed once at prefill)."""
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, params["wq"]).reshape(b, s, h, hd)
    t = enc_kv.k.shape[1]
    mask = jnp.ones((1, 1, 1, s, t), bool)
    out = _sdpa(q, enc_kv.k, enc_kv.v, mask, None)
    return jnp.einsum("bse,ed->bsd", out.reshape(b, s, -1), params["wo"])


def encode_kv(params, enc_out: jnp.ndarray, cfg: ArchConfig) -> KVCache:
    b, t, _ = enc_out.shape
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    k = jnp.einsum("btd,de->bte", enc_out, params["wk"]).reshape(b, t, kv, hd)
    v = jnp.einsum("btd,de->bte", enc_out, params["wv"]).reshape(b, t, kv, hd)
    return KVCache(k=k, v=v)

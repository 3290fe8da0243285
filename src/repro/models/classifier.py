"""Sequence classifier head over any backbone: mean-pooled final hidden
states -> K-class logits.  This is what turns an assigned architecture into
an ASCII agent's model class F_0^(m) (DESIGN.md §2).  A classifier never
reads the LM output head, so none is allocated."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer
from repro.models.layers import he_init, rmsnorm


def init_params(key, cfg: ArchConfig, num_classes: int):
    k1, k2 = jax.random.split(key)
    params = transformer.init_params(k1, cfg, with_head=False)
    params["cls_head"] = {"w": he_init(k2, (cfg.d_model, num_classes),
                                       jnp.dtype(cfg.dtype))}
    return params


def apply_counted(params, batch: dict, cfg: ArchConfig):
    """batch {"tokens": [B,S]} (or embeddings) -> (class logits [B,K],
    expert_tokens: the (token, choice) pairs routed to held experts)."""
    x = transformer.embed_inputs(params, batch, cfg)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x, _, _, count = transformer.layer_stack(params, x, cfg, positions)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    pooled = jnp.mean(x, axis=1)
    logits = jnp.einsum("bd,dk->bk", pooled.astype(jnp.float32),
                        params["cls_head"]["w"].astype(jnp.float32))
    return logits, count


def apply(params, batch: dict, cfg: ArchConfig) -> jnp.ndarray:
    """batch {"tokens": [B,S]} (or embeddings) -> class logits [B,K]."""
    return apply_counted(params, batch, cfg)[0]

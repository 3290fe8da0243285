"""Mixture-of-Experts: top-k router + expert FFNs.

Implementations (cfg.moe_impl):
  * ``dense`` — every expert computes every token, mask-combined.  Exact
    oracle used by smoke tests and as the numerical reference; FLOPs are
    E/k-fold inflated, so never used for roofline numbers.
  * ``gmm``   — grouped matmul: tokens are sorted by expert and processed
    with ``jax.lax.ragged_dot`` against stacked expert weights (the
    megablocks/MaxText formulation; on TPU this lowers to the grouped MXU
    matmul).  Default for training and the dry-run: HLO FLOPs reflect only
    *activated* experts.
  * ``ep_a2a`` — expert-parallel shard_map with fixed-capacity all_to_all
    (see sharding/ep.py); a §Perf lever wired in by the launcher.

Router: softmax over experts, top-k, renormalized among the chosen k
(Qwen3/Mixtral convention; ``cfg.norm_topk_prob`` False keeps the raw
softmax weights, as DeepSeek-V2 does), plus the standard load-balance
auxiliary loss (Switch: E * sum_e f_e * P_e) surfaced to the trainer.

Expert-parallel share (``cfg.experts_held``): the layer holds the weights
of experts ``[0, held)`` of ``num_experts``, routes every token
over all of them, and computes only its own experts' part of the result,
dropless.  ``cfg.shared_experts`` adds an always-on SwiGLU of width
``shared_experts * moe_d_ff`` that every token passes through.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import he_init, mlp_apply, mlp_init


def moe_init(key, cfg: ArchConfig, dtype) -> dict:
    d, f = cfg.d_model, cfg.moe_d_ff
    e, h = cfg.num_experts, cfg.held_experts
    ks = jax.random.split(key, 4)
    params = {
        "router": he_init(ks[0], (d, e), dtype),
        "wi_gate": (jax.random.normal(ks[1], (h, d, f)) * (2.0 / d) ** 0.5).astype(dtype),
        "wi_up": (jax.random.normal(ks[2], (h, d, f)) * (2.0 / d) ** 0.5).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h, f, d)) * (2.0 / f) ** 0.5).astype(dtype),
    }
    if cfg.shared_experts:
        params["shared_mlp"] = mlp_init(jax.random.fold_in(key, 1), d,
                                        cfg.shared_experts * f, dtype)
    return params


def router_topk(params, x_flat: jnp.ndarray, cfg: ArchConfig):
    """x_flat [T, d] -> (probs [T, k], idx [T, k], aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs_full = jax.nn.softmax(logits, axis=-1)
    probs, idx = jax.lax.top_k(probs_full, cfg.top_k)
    if cfg.norm_topk_prob:
        probs = probs / jnp.maximum(jnp.sum(probs, -1, keepdims=True), 1e-9)
    # Switch-style load-balance loss.
    e = cfg.num_experts
    frac_tokens = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e), axis=1), axis=0)       # f_e
    frac_probs = jnp.mean(probs_full, axis=0)                  # P_e
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return probs.astype(x_flat.dtype), idx, aux


def _expert_ffn_dense(params, x_flat, probs, idx, cfg: ArchConfig):
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    gate = jnp.einsum("td,edf->tef", x_flat, params["wi_gate"])
    up = jnp.einsum("td,edf->tef", x_flat, params["wi_up"])
    h = act(gate) * up
    y_all = jnp.einsum("tef,efd->ted", h, params["wo"])        # [T, H, d]
    held = idx < cfg.held_experts
    combine = jnp.zeros((x_flat.shape[0], cfg.held_experts), x_flat.dtype)
    combine = jax.vmap(lambda c, p, i: c.at[i].add(p))(
        combine, jnp.where(held, probs, 0), jnp.where(held, idx, 0))
    return jnp.einsum("te,ted->td", combine, y_all)


def _expert_ffn_gmm(params, x_flat, probs, idx, cfg: ArchConfig):
    """Held experts' part by grouped matmul: the (token, choice) pairs are
    sorted by held expert, the pairs of experts held elsewhere last and
    outside every group, so the grouped matmul skips them (dropless)."""
    t, d = x_flat.shape
    k, h_e = cfg.top_k, cfg.held_experts
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    flat_expert = idx.reshape(-1)                              # [T*k]
    held = flat_expert < h_e
    group = jnp.minimum(flat_expert, h_e)
    order = jnp.argsort(group)                                 # stable
    token_of = order // k
    held_sorted = held[order][:, None]
    # rows past the last group are never written by the grouped matmul:
    # zero them on the way in and out, so neither value nor gradient of an
    # unwritten row reaches a token
    x_sorted = jnp.where(held_sorted, x_flat[token_of], 0)     # [T*k, d]
    group_sizes = jnp.bincount(group, length=h_e + 1)[:h_e].astype(jnp.int32)
    gate = jax.lax.ragged_dot(x_sorted, params["wi_gate"], group_sizes)
    up = jax.lax.ragged_dot(x_sorted, params["wi_up"], group_sizes)
    h = act(gate) * up
    y = jax.lax.ragged_dot(h, params["wo"], group_sizes)       # [T*k, d]
    y = jnp.where(held_sorted, y, 0)
    p_sorted = probs.reshape(-1)[order][:, None].astype(y.dtype)
    out = jnp.zeros((t, d), y.dtype).at[token_of].add(y * p_sorted)
    return out.astype(x_flat.dtype)


def moe_layer(params, x: jnp.ndarray, cfg: ArchConfig,
              impl: str | None = None):
    """x [B, S, d] -> (y [B, S, d], aux_loss, expert_tokens): the held
    experts' part plus the shared experts; ``expert_tokens`` counts the
    (token, choice) pairs routed to held experts."""
    impl = impl or cfg.moe_impl
    b, s, d = x.shape
    if impl == "ep_a2a":
        # routing happens inside the shard_map block (per data shard)
        from repro.sharding.ep import moe_apply_ep_a2a
        routed = {k: v for k, v in params.items() if k != "shared_mlp"}
        y, aux = moe_apply_ep_a2a(routed, x, cfg)
        count = jnp.asarray(b * s * cfg.top_k, jnp.int32)
    else:
        x_flat = x.reshape(-1, d)
        with jax.named_scope("backbone_router"):
            probs, idx, aux = router_topk(params, x_flat, cfg)
            count = jnp.sum(idx < cfg.held_experts, dtype=jnp.int32)
        with jax.named_scope("backbone_experts"):
            if impl == "dense":
                y = _expert_ffn_dense(params, x_flat, probs, idx, cfg)
            elif impl == "gmm":
                y = _expert_ffn_gmm(params, x_flat, probs, idx, cfg)
            else:
                raise ValueError(f"unknown moe_impl {impl!r}")
        y = y.reshape(b, s, d)
    if "shared_mlp" in params:
        with jax.named_scope("backbone_shared"):
            y = y + mlp_apply(params["shared_mlp"], x, cfg.act)
    return y, aux, count


def moe_apply(params, x: jnp.ndarray, cfg: ArchConfig,
              impl: str | None = None):
    """x [B, S, d] -> (y [B, S, d], aux_loss); see :func:`moe_layer`."""
    y, aux, _ = moe_layer(params, x, cfg, impl)
    return y, aux

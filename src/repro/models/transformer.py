"""Decoder-only model assembly (dense / MoE / SSM / hybrid / VLM), built as
``jax.lax.scan`` over stacked per-layer parameters so the lowered HLO is
layer-count independent (94-layer qwen3-moe compiles as fast as 2 layers).

Three entry points per model, all pure:
  * ``forward(params, tokens_or_embeds, cfg)``            -> logits, caches
  * ``decode_step(params, caches, token, pos, cfg)``      -> logits, caches
  * ``init_params(key, cfg)`` / ``init_cache(cfg, batch, s_cache)``

Hybrid (Jamba) stacks scan over *pattern units* (8 heterogeneous sub-layers
unrolled inside, 4 scanned repeats).  Leading dense layers
(``cfg.first_k_dense``, DeepSeek-V2) are a second stack, ``params["lead"]``,
scanned before the units; their FFN is the dense SwiGLU of width ``d_ff``.

The mixer, router, routed experts, shared experts and dense FFN run under
the ``backbone_attn``/``backbone_router``/``backbone_experts``/
``backbone_shared``/``backbone_ffn`` name scopes (metadata only).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (embed, embedding_init, lm_head, lm_head_init,
                                 mlp_apply, mlp_init, rmsnorm, rmsnorm_init,
                                 unembed)

PyTree = Any


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# ------------------------------------------------------------ block defs
def _block_kinds(cfg: ArchConfig) -> tuple[str, ...]:
    """Sub-layer kinds of one scanned unit."""
    if cfg.layer_pattern:
        return cfg.layer_pattern
    if cfg.arch_type == "ssm":
        return ("ssm",)
    return ("attn",)


def _num_units(cfg: ArchConfig) -> int:
    return (cfg.num_layers - cfg.first_k_dense) // len(_block_kinds(cfg))


def _ffn_kind(cfg: ArchConfig, sub_idx: int, lead: bool = False) -> str:
    """What follows the mixer in this sub-layer: moe | mlp | none (a
    leading dense layer always has the dense mlp)."""
    if lead:
        return "mlp"
    if cfg.arch_type == "ssm":
        return "none"                       # pure mamba2: no FFN
    if cfg.is_moe:
        if cfg.moe_every <= 1 or (sub_idx % cfg.moe_every == 1):
            return "moe"
        return "mlp"
    return "mlp"


def _init_sub_block(key, cfg: ArchConfig, kind: str, sub_idx: int, dtype,
                    lead: bool = False):
    ks = jax.random.split(key, 4)
    p: dict = {"ln1": rmsnorm_init(cfg.d_model, dtype)}
    if kind == "attn":
        if cfg.attention == "mla":
            p["attn"] = attn.mla_init(ks[0], cfg, dtype)
        else:
            p["attn"] = attn.gqa_init(ks[0], cfg, dtype)
    else:
        p["ssm"] = ssm_lib.ssm_init(ks[0], cfg, dtype)
    ffn = _ffn_kind(cfg, sub_idx, lead)
    if ffn != "none":
        p["ln2"] = rmsnorm_init(cfg.d_model, dtype)
        if ffn == "moe":
            p["moe"] = moe_lib.moe_init(ks[1], cfg, dtype)
        else:
            p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype)
    return p


def _seq_shard(x, cfg: ArchConfig):
    """Megatron sequence parallelism (cfg.seq_parallel): constrain the
    residual stream to S-sharded over ``model`` so XLA converts the TP
    activation all-reduces into reduce-scatter + all-gather pairs and the
    norm/residual math runs on S/|model| rows per chip."""
    if not cfg.seq_parallel:
        return x
    from repro.sharding.context import current_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return x
    if x.shape[1] % mesh.shape["model"] != 0:
        return x
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(dp, "model", None)))


def _sub_block_forward(p, x, cfg: ArchConfig, kind: str, sub_idx: int,
                       positions, lead: bool = False):
    """Full-seq sub-layer. Returns (x, cache_leaf, aux, expert_tokens)."""
    aux = jnp.zeros((), jnp.float32)
    count = jnp.zeros((), jnp.int32)
    x = _seq_shard(x, cfg)
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind == "attn":
        with jax.named_scope("backbone_attn"):
            if cfg.attention == "mla":
                out, cache = attn.mla_forward(p["attn"], h, cfg, positions)
            else:
                out, cache = attn.gqa_forward(p["attn"], h, cfg, positions)
    else:
        out, cache = ssm_lib.ssm_forward(p["ssm"], h, cfg)
    x = x + out
    x = _seq_shard(x, cfg)
    ffn = _ffn_kind(cfg, sub_idx, lead)
    if ffn == "moe":
        y, aux, count = moe_lib.moe_layer(
            p["moe"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
        x = x + y
    elif ffn == "mlp":
        with jax.named_scope("backbone_ffn"):
            x = x + mlp_apply(p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps),
                              cfg.act)
    return x, cache, aux, count


def _sub_block_decode(p, x, cache_leaf, pos, cfg: ArchConfig, kind: str,
                      sub_idx: int, cache_mode: str, lead: bool = False):
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind == "attn":
        if cfg.attention == "mla":
            out, cache = attn.mla_decode(p["attn"], h, cache_leaf, pos, cfg,
                                         cache_mode)
        else:
            out, cache = attn.gqa_decode(p["attn"], h, cache_leaf, pos, cfg,
                                         cache_mode)
    else:
        out, cache = ssm_lib.ssm_decode(p["ssm"], h, cache_leaf, cfg)
    x = x + out
    ffn = _ffn_kind(cfg, sub_idx, lead)
    if ffn == "moe":
        y, _ = moe_lib.moe_apply(p["moe"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
        x = x + y
    elif ffn == "mlp":
        x = x + mlp_apply(p["mlp"], rmsnorm(p["ln2"], x, cfg.norm_eps), cfg.act)
    return x, cache


# ------------------------------------------------------------- unit defs
def _unit_kinds(cfg: ArchConfig, lead: bool) -> tuple[str, ...]:
    """Sub-layer kinds of a scanned unit (a leading dense layer is one
    attention sub-layer)."""
    return ("attn",) if lead else _block_kinds(cfg)


def _init_unit(key, cfg: ArchConfig, dtype, lead: bool = False):
    kinds = _unit_kinds(cfg, lead)
    ks = jax.random.split(key, len(kinds))
    return {f"sub{i}": _init_sub_block(ks[i], cfg, kinds[i], i, dtype, lead)
            for i in range(len(kinds))}


def _unit_forward(unit_params, x, cfg: ArchConfig, positions,
                  lead: bool = False):
    """One scanned unit: (x, caches, aux, expert_tokens)."""
    kinds = _unit_kinds(cfg, lead)
    caches, aux_total = {}, jnp.zeros((), jnp.float32)
    count_total = jnp.zeros((), jnp.int32)
    for i, kind in enumerate(kinds):
        x, cache, aux, count = _sub_block_forward(
            unit_params[f"sub{i}"], x, cfg, kind, i, positions, lead)
        caches[f"sub{i}"] = cache
        aux_total = aux_total + aux
        count_total = count_total + count
    return x, caches, aux_total, count_total


def _unit_decode(unit_params, x, unit_cache, pos, cfg: ArchConfig,
                 cache_mode: str, lead: bool = False):
    kinds = _unit_kinds(cfg, lead)
    new_caches = {}
    for i, kind in enumerate(kinds):
        x, cache = _sub_block_decode(unit_params[f"sub{i}"], x,
                                     unit_cache[f"sub{i}"], pos, cfg, kind, i,
                                     cache_mode, lead)
        new_caches[f"sub{i}"] = cache
    return x, new_caches


# --------------------------------------------------------------- model
def init_params(key, cfg: ArchConfig, with_head: bool = True) -> PyTree:
    """Embedding, leading dense layers, scanned units, final norm and (with
    ``with_head``, untied) the output head."""
    dtype = _dtype(cfg)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    units = _num_units(cfg)
    unit_keys = jax.random.split(k_layers, units)
    layers = jax.vmap(lambda k: _init_unit(k, cfg, dtype))(unit_keys)
    params = {
        "embed": embedding_init(k_embed, cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if cfg.first_k_dense:
        lead_keys = jax.random.split(jax.random.fold_in(k_layers, 1),
                                     cfg.first_k_dense)
        params["lead"] = jax.vmap(
            lambda k: _init_unit(k, cfg, dtype, lead=True))(lead_keys)
    if with_head and not cfg.tie_embeddings:
        params["lm_head"] = lm_head_init(k_head, cfg.d_model, cfg.vocab_size,
                                         dtype)
    return params


def _logits(params, x, cfg: ArchConfig):
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        return unembed(params["embed"], x)
    return lm_head(params["lm_head"], x)


def embed_inputs(params, batch: dict, cfg: ArchConfig) -> jnp.ndarray:
    """Token embeddings, with modality-frontend stub embeddings prepended
    for VLM/audio archs (the one sanctioned stub — DESIGN.md §2)."""
    x = embed(params["embed"], batch["tokens"], cfg.embed_scale)
    if cfg.frontend == "vision" and "patch_emb" in batch:
        x = jnp.concatenate([batch["patch_emb"].astype(x.dtype), x], axis=1)
    return x


def layer_stack(params, x, cfg: ArchConfig, positions):
    """Run the leading dense layers, then the scanned units, over the
    embedded sequence ``x``.  Returns (x, caches, aux, expert_tokens);
    ``caches`` holds the leading layers' under ``"lead"``."""
    def stack(x, layers, lead):
        def body(carry, unit_params):
            x, aux, count = carry
            x, caches, aux_u, count_u = _unit_forward(unit_params, x, cfg,
                                                      positions, lead)
            return (x, aux + aux_u, count + count_u), caches

        body_fn = jax.checkpoint(body) if cfg.remat == "block" else body
        carry = (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
        if cfg.scan_layers:
            return jax.lax.scan(body_fn, carry, layers)
        # Unrolled path: identical math/params, used by the dry-run cost
        # extraction (XLA cost_analysis counts a scan body only once).
        cache_list = []
        for i in range(jax.tree.leaves(layers)[0].shape[0]):
            unit = jax.tree.map(lambda a: a[i], layers)
            carry, c = body_fn(carry, unit)
            cache_list.append(c)
        return carry, jax.tree.map(lambda *xs: jnp.stack(xs), *cache_list)

    aux = jnp.zeros((), jnp.float32)
    count = jnp.zeros((), jnp.int32)
    lead_caches = None
    if "lead" in params:
        (x, aux, count), lead_caches = stack(x, params["lead"], True)
    (x, aux_u, count_u), caches = stack(x, params["layers"], False)
    if lead_caches is not None:
        caches = dict(caches, lead=lead_caches)
    return x, caches, aux + aux_u, count + count_u


def forward(params, batch: dict, cfg: ArchConfig):
    """Full-sequence forward (train / prefill).

    batch: {"tokens": [B,S]} (+ "patch_emb" [B,Timg,d] for VLM).
    Returns (logits [B,S_total,V], caches, aux_loss).
    """
    x = embed_inputs(params, batch, cfg)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x, caches, aux, _ = layer_stack(params, x, cfg, positions)
    return _logits(params, x, cfg), caches, aux


def decode_step(params, caches, tokens: jnp.ndarray, pos, cfg: ArchConfig,
                cache_mode: str = "full"):
    """One-token decode. tokens [B,1]; pos scalar int32 (absolute position,
    frontend tokens included for VLM). Returns (logits [B,1,V], caches)."""
    x = embed(params["embed"], tokens, cfg.embed_scale)

    def stack(x, layers, stack_caches, lead):
        def body(x, inp):
            unit_params, unit_cache = inp
            x, new_cache = _unit_decode(unit_params, x, unit_cache, pos, cfg,
                                        cache_mode, lead)
            return x, new_cache

        if cfg.scan_layers:
            return jax.lax.scan(body, x, (layers, stack_caches))
        cache_list = []
        for i in range(jax.tree.leaves(layers)[0].shape[0]):
            unit = jax.tree.map(lambda a: a[i], layers)
            cache_u = jax.tree.map(lambda a: a[i], stack_caches)
            x, c = body(x, (unit, cache_u))
            cache_list.append(c)
        return x, jax.tree.map(lambda *xs: jnp.stack(xs), *cache_list)

    unit_caches = {k: v for k, v in caches.items() if k != "lead"}
    if "lead" in params:
        x, lead_caches = stack(x, params["lead"], caches["lead"], True)
    x, new_caches = stack(x, params["layers"], unit_caches, False)
    if "lead" in params:
        new_caches = dict(new_caches, lead=lead_caches)
    return _logits(params, x, cfg), new_caches


def init_cache(cfg: ArchConfig, batch: int, s_cache: int,
               dtype=None) -> PyTree:
    """Zero-initialized decode cache matching the scan layout [U, ...]."""
    dtype = dtype or _dtype(cfg)
    units = _num_units(cfg)
    kinds = _block_kinds(cfg)

    def leaf(kind, units=units):
        if kind == "attn":
            if cfg.attention == "mla":
                # (MLA latents are already rank-compressed; int8 not applied)
                return attn.KVCache(
                    k=jnp.zeros((units, batch, s_cache, cfg.kv_lora_rank), dtype),
                    v=jnp.zeros((units, batch, s_cache, cfg.qk_rope_head_dim),
                                dtype))
            kv_shape = (units, batch, s_cache, cfg.num_kv_heads, cfg.head_dim)
            if cfg.kv_quant:
                return attn.QuantKVCache(
                    k=jnp.zeros(kv_shape, jnp.int8),
                    v=jnp.zeros(kv_shape, jnp.int8),
                    k_scale=jnp.zeros(kv_shape[:-1], jnp.float32),
                    v_scale=jnp.zeros(kv_shape[:-1], jnp.float32))
            return attn.KVCache(k=jnp.zeros(kv_shape, dtype),
                                v=jnp.zeros(kv_shape, dtype))
        return ssm_lib.SSMState(
            conv_x=jnp.zeros((units, batch, cfg.ssm_conv - 1, cfg.d_inner),
                             dtype),
            conv_B=jnp.zeros((units, batch, cfg.ssm_conv - 1, cfg.ssm_state),
                             dtype),
            conv_C=jnp.zeros((units, batch, cfg.ssm_conv - 1, cfg.ssm_state),
                             dtype),
            ssm=jnp.zeros((units, batch, cfg.ssm_heads, cfg.ssm_state,
                           cfg.ssm_head_dim), jnp.float32))

    caches = {f"sub{i}": leaf(kind) for i, kind in enumerate(kinds)}
    if cfg.first_k_dense:
        caches["lead"] = {"sub0": leaf("attn", cfg.first_k_dense)}
    return caches


def cache_length(cfg: ArchConfig, seq_len: int) -> int:
    """Decode-cache length: ring buffer when SWA is active (§Perf lever —
    bounds both memory and per-step attention traffic by the window)."""
    if cfg.window is not None and cfg.window < seq_len:
        return cfg.window
    return seq_len


def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))

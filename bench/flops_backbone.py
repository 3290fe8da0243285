"""Model FLOPs of a backbone hop, computed from the configuration's shapes
and the tokens the hop processed.

A hop is the minibatched weighted fit (each step a forward and a backward
pass over ``batch x length`` tokens) and the forward pass over every row
that scores its reward.  Matrix products count 2 FLOPs per multiply-add;
the backward pass counts twice the forward (weight and input gradients;
the embedding's gradient is a scatter, counted as nothing); rematerialised
forward passes are not model FLOPs and do not count.  Attention counts the
causal (query, key) pairs, ``L (L + 1) / 2`` a sequence.  The routed
experts count only the (token, choice) pairs the router sent to experts
held here, as the program counted them (``expert_tokens_fit`` and
``expert_tokens_predict``).
"""
from __future__ import annotations

from bench import flops


def layer_flops_per_token(cfg: dict, dense: bool) -> int:
    """One token's forward through one layer, without the attention
    products and without the routed experts."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv, r = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    proj = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + dv) \
        + h * dv * d
    if dense:
        ffn = 3 * d * int(cfg["intermediate_size"])
    else:
        ffn = (d * int(cfg["router_experts"])
               + 3 * d * int(cfg["n_shared_experts"])
               * int(cfg["moe_intermediate_size"]))
    return 2 * (proj + ffn)


def attention_flops_per_sequence(cfg: dict, length: int) -> int:
    """Scores and the weighted values of one layer over one causal
    sequence."""
    h = int(cfg["num_attention_heads"])
    qk = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    pairs = length * (length + 1) // 2
    return 2 * pairs * h * (qk + int(cfg["v_head_dim"]))


def routed_flops_per_pair(cfg: dict) -> int:
    """One (token, expert) pair through a SwiGLU expert."""
    return 2 * 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def forward_flops(cfg: dict, tokens: int, routed_pairs: int,
                  length: int) -> int:
    """A forward pass over ``tokens`` tokens in sequences of ``length``,
    with ``routed_pairs`` pairs sent to held experts, through the classifier
    head."""
    layers = int(cfg["num_hidden_layers"])
    dense = int(cfg["first_k_dense_replace"])
    sequences = tokens // length
    per_token = (dense * layer_flops_per_token(cfg, True)
                 + (layers - dense) * layer_flops_per_token(cfg, False))
    return (tokens * per_token
            + layers * sequences * attention_flops_per_sequence(cfg, length)
            + routed_pairs * routed_flops_per_pair(cfg)
            + sequences * 2 * int(cfg["hidden_size"]) * int(cfg["num_classes"]))


def backbone_flops(cfg: dict, counts: dict) -> int:
    """Fit and predict FLOPs of the backbone hops behind ``counts`` (the
    ``session`` span's sums: tokens and routed pairs of fits and
    predicts)."""
    length = int(cfg["dataset"]["length"])
    fit = forward_flops(cfg, int(counts["tokens_fit"]),
                        int(counts["expert_tokens_fit"]), length)
    predict = forward_flops(cfg, int(counts["tokens_predict"]),
                            int(counts["expert_tokens_predict"]), length)
    return 3 * fit + predict


def mlp_hop_flops(cfg: dict, agent: dict, width: int) -> int:
    """A tabular agent's hop, as ``bench/flops.py`` counts it."""
    return flops.hop_flops(dict(agent), int(cfg["dataset"]["n"]), width,
                           int(cfg["num_classes"]))

"""A queue of ASCII training sessions with a neural-backbone agent, run
back to back.

Each session is one ``Protocol.fit(backend="compiled")`` on the same
cohort with a fresh session key from the seed (``session_key``, as in
``session_queue``), over the agents the configuration lists: a backbone
reading each subject's note, a tabular learner reading the chart.  The
window counts the sessions completed over the time they took, the host's
ledger replay and the building of the fitted ensemble included.

A session's fitted ensemble holds gigabytes of backbone parameters, so
before the window the seed picks which of the first two sessions is
checked; of that session the generator keeps the first hop's parameters
(on the device), the components' (agent, round, alpha) and the wire
ledger, and every other session's parameters are dropped as soon as it
ends.  After the window, with the program's state freed, the kept session
is checked against the plain reference (``bench/ref_backbone.py``) run on
the same key (``reference_numbers``: round 0, and the next backbone hop's
fit for a few steps at ``HIGHEST``), on the numbers the configuration's
``limits`` name.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from bench import data_backbone, program, ref_backbone
from bench.traffic.session_queue import (annotate, ledger, session_key,
                                         with_limits)

def backbone_arch(config: dict, agent: dict):
    """The program's ``ArchConfig``: the registry's architecture with every
    number of the configuration file, float32, and the agent's remat."""
    from repro.configs.registry import get_arch
    rs = config["rope_scaling"]
    return get_arch(agent["arch"]).with_overrides(
        num_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]),
        q_lora_rank=int(config["q_lora_rank"] or 0),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        rope_theta=float(config["rope_theta"]),
        yarn_factor=float(rs["factor"]), yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        yarn_original_max_position=int(
            rs["original_max_position_embeddings"]),
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        num_experts=int(config["router_experts"]),
        experts_held=int(config["n_routed_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        moe_d_ff=int(config["moe_intermediate_size"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        shared_experts=int(config["n_shared_experts"]),
        first_k_dense=int(config["first_k_dense_replace"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype="float32", remat=agent.get("remat", "none"))


def learners(config: dict) -> list:
    """One learner per entry of the configuration's ``agents``."""
    out = []
    for agent in config["agents"]:
        if agent["kind"] == "backbone":
            from repro.learners.neural import NeuralBackbone
            out.append(NeuralBackbone(
                cfg=backbone_arch(config, agent), steps=int(agent["steps"]),
                lr=float(agent["lr"]), batch_size=int(agent["batch"]),
                predict_block=int(agent["predict_block"])))
        elif agent["kind"] == "mlp":
            from repro.learners.mlp import MLP
            out.append(MLP(hidden=tuple(agent["hidden"]),
                           steps=int(agent["steps"]), lr=float(agent["lr"])))
        else:
            raise ValueError(f"unknown agent kind {agent['kind']!r}")
    return out


class Traffic:
    def __init__(self, cell):
        self.cell = cell
        self.config = cell.config
        self.trace = cell.trace
        self.key = cell.key

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        # the learners first: a program without the backbone fails here,
        # before any data is made
        self.learners = learners(self.config)
        self.blocks, self.classes = data_backbone.make(
            self.config, jax.random.fold_in(self.key, 0))
        self.checked = int(np.random.default_rng(self.cell.seed).integers(2))
        # one session on a key the window never uses compiles and loads
        # every program the window runs, the check's kept slice included;
        # its parameters go at once
        key = jax.random.fold_in(self.key, 2)
        kept_session(key, *self._fit(key, None))

    def _fit(self, key, tele):
        from repro.core.engine import endpoints_for
        proto = program.protocol(self.config, telemetry=tele)
        with annotate("bench.session", self.trace):
            fitted = proto.fit(key, endpoints_for(self.learners,
                                                  list(self.blocks)),
                               self.classes)
        return proto, fitted

    # ------------------------------------------------------------- window
    def window(self, seconds: float, tick=lambda: None) -> dict:
        self.tele = program.telemetry() if self.trace else None
        self.done, self.kept = [], None
        t0 = time.perf_counter()
        end = t0
        while end - t0 < seconds or len(self.done) <= self.checked:
            key = session_key(self.key, len(self.done))
            s = time.perf_counter()
            proto, fitted = self._fit(key, self.tele)
            end = time.perf_counter()
            self.done.append({
                "wall_s": end - s,
                "hops": [len(rec["alphas"]) for rec in fitted.history],
                **self._counts()})
            if len(self.done) - 1 == self.checked:
                self.kept = kept_session(key, proto, fitted)
            del proto, fitted
            tick()
        self.elapsed = end - t0
        return {"sessions_per_s": len(self.done) / self.elapsed}

    def _counts(self) -> dict:
        """The last ``session`` span's work counts (traced runs only)."""
        if self.tele is None:
            return {}
        spans = [sp for sp in self.tele.tracer.spans if sp.name == "session"]
        return {k: v for k, v in spans[-1].attrs.items()
                if k.startswith(("tokens_", "expert_tokens"))}

    def attempted(self) -> tuple[int, int]:
        return len(self.done), 0

    def record(self) -> dict:
        spans = self.tele.tracer.spans if self.tele is not None else []
        return {
            "n_train": int(self.classes.shape[0]),
            "widths": [int(b.shape[1]) for b in self.blocks],
            "sessions": self.done,
            "spans": [(sp.name, sp.duration_s) for sp in spans],
        }

    # -------------------------------------------------------------- check
    def release(self) -> None:
        self.tele = None

    def check(self) -> list:
        got, self.kept = self.kept, None
        return with_limits(
            reference_numbers(got, self.blocks, self.classes, self.config),
            self.config["limits"]["session"])


def kept_session(key, proto, fitted) -> dict:
    """What the check needs of a session: its key, the components' (agent,
    round, alpha), round 0's alphas (the components', then the stopping
    hop's where the round stopped), the wire ledger and the first hop's
    parameters (from the session's result, so also where that hop stopped
    the round)."""
    comps = fitted.components
    first_round = [c.alpha for c in comps if c.round == 0]
    return {"key": key,
            "components": [(c.agent, c.round, c.alpha) for c in comps],
            "alphas": (first_round
                       + fitted.history[0]["alphas"][len(first_round):]),
            "ledger": ledger(proto),
            "first": first_hop(proto.compiled_result.params[0])}


@jax.jit
def first_hop(stacked):
    """Round 0's parameters of a slot's per-round stack, in one program."""
    return jax.tree.map(lambda x: x[0], stacked)


#: AdamW steps of the fits ``fit_gap`` compares: few enough that two fits
#: at float32 ``HIGHEST`` products still take the same steps
FIT_GAP_STEPS = 2


def reference_numbers(got: dict, blocks, classes, config: dict,
                      control: str | None = None) -> dict:
    """A kept session (``got``) against the reference on the same key.

    The first hop's fit is compared by ``scale_update_gap`` with the
    reference's own fit from the same initial weights and draws.  Its
    trajectory is not reproducible below float32 ``HIGHEST`` products, so
    the rest of round 0 is the reference's from the program's first-hop
    parameters: its forward over every subject (``HIGHEST``), eq. (13), the
    stop rule, the reweight, the wire and the tabular agent's fit, against
    the program's alphas, components and ledger.  The backbone's fit step
    by step is ``fit_gap``: the program's learner and the reference each
    take ``FIT_GAP_STEPS`` steps at ``HIGHEST`` from the key and the
    (reweighted, so not uniform) weights of the hop after round 0, the
    next round's backbone hop.  ``control`` names a reference precision
    put in the program's place (``got`` then needs only its key)."""
    f32 = ref_backbone.backbone_from(config, config["agents"][0])
    k = int(config["num_classes"])
    n = int(classes.shape[0])
    onehot = jax.nn.one_hot(classes, k)
    sub = ref_backbone.first_sub(got["key"])
    if control is not None:
        r = ref_backbone.round_zero(got["key"], blocks, classes, config,
                                    control)
        got = {"key": got["key"], "ledger": r.ledger, "alphas": r.alphas,
               "components": [c[:3] for c in r.components],
               "first": r.first}
        del r
    first = got.pop("first")
    want = f32.fit(sub, blocks[0], onehot,
                   jax.numpy.full((n,), 1.0 / n, jax.numpy.float32))
    gap = float(ref_backbone.scale_update_gap(first, want, f32.init(sub)))
    del want
    cond = ref_backbone.round_zero(got["key"], blocks, classes, config,
                                   first=first)
    del first
    numbers = round_numbers(got, cond, gap)
    nxt = ref_backbone.first_sub(cond.key)
    w = cond.w.astype(jax.numpy.float32)
    del cond
    if control is None:
        fit = program_fit(config, nxt, blocks[0], classes, w)
    else:
        fit = ref_backbone.backbone_from(
            config, config["agents"][0], control).fit(
                nxt, blocks[0], onehot, w, FIT_GAP_STEPS)
    want = f32.fit(nxt, blocks[0], onehot, w, FIT_GAP_STEPS)
    numbers["fit_gap"] = float(ref_backbone.update_gap(fit, want,
                                                       f32.init(nxt)))
    return numbers


def program_fit(config: dict, sub, tokens, classes, w):
    """The program's backbone learner's fit of ``FIT_GAP_STEPS`` steps at
    float32 ``HIGHEST`` products, from the hop key ``sub``."""
    learner = dataclasses.replace(learners(config)[0], steps=FIT_GAP_STEPS)
    with jax.default_matmul_precision("highest"):
        return learner.fit(sub, tokens, classes, w,
                           int(config["num_classes"]))


def round_numbers(got: dict, want, scale_update_gap: float) -> dict:
    """Round 0 of the program (``got``: components as (agent, round,
    alpha), every executed hop's alpha, the whole wire ledger) against the
    reference's round 0.  Each alpha is compared relative to max(1,
    |alpha|), the stopping hop's too."""
    g_c = [c for c in got["components"] if c[1] == 0]
    w_c = [c[:3] for c in want.components]
    common = 0
    while (common < min(len(g_c), len(w_c))
           and g_c[common][:2] == w_c[common][:2]):
        common += 1
    gaps = [abs(g - w) / max(1.0, abs(w))
            for g, w in zip(got["alphas"], want.alphas)]
    setup = sum(1 for kind, _ in want.ledger
                if kind in ("labels", "sample_ids"))
    g_l = got["ledger"][:setup + 2 * len(g_c)]
    w_l = want.ledger
    return {
        "components_differ": max(len(g_c), len(w_c)) - common,
        "alpha_gap_head": max(gaps, default=0.0),
        "scale_update_gap": scale_update_gap,
        "wire_mismatch": (sum(a != b for a, b in zip(g_l, w_l))
                          + abs(len(g_l) - len(w_l))),
    }

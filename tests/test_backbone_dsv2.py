"""The DeepSeek-V2-Lite text agent at a size the CPU holds, against the
plain reference (``bench/ref_backbone.py``) on seeded random weights:
logits of the block (MLA with YaRN, a leading dense layer, held-share MoE
with shared experts), the expert-parallel shares against the uncut layer,
the minibatched weighted fit step for step, eager against compiled, and
name scopes as metadata only."""
import sys
from contextlib import nullcontext
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import ref_backbone  # noqa: E402
from program_tolerance import (assert_floats_close,  # noqa: E402
                               assert_history_close)
from repro.configs.registry import get_arch  # noqa: E402
from repro.core import compiled  # noqa: E402
from repro.core.engine import (MeteredTransport, Protocol,  # noqa: E402
                               SessionConfig, endpoints_for)
from repro.comm import make_codec  # noqa: E402
from repro.data.synthetic import mimic_notes  # noqa: E402
from repro.learners.mlp import MLP  # noqa: E402
from repro.learners.neural import NeuralBackbone, NeuralCore  # noqa: E402
from repro.models import classifier, layers, moe  # noqa: E402

K = 2
# every width cut; the published structure kept: MLA without a query
# latent and with YaRN, a leading dense layer, more routed experts than
# are held, top-k over all of them unnormalized, two shared experts
TINY = dict(num_layers=3, d_model=64, num_heads=2, num_kv_heads=2,
            d_ff=96, moe_d_ff=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, vocab_size=128, num_experts=8,
            experts_held=4, top_k=3, dtype="float32")


def tiny(**kw):
    return get_arch("deepseek-v2-lite").with_overrides(**{**TINY, **kw})


def ref_config(cfg) -> dict:
    """The configuration file's keys for ``cfg``, as the reference reads
    them."""
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": cfg.first_k_dense,
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "intermediate_size": cfg.d_ff, "moe_intermediate_size": cfg.moe_d_ff,
        "router_experts": cfg.num_experts,
        "n_routed_experts": cfg.held_experts,
        "num_experts_per_tok": cfg.top_k,
        "n_shared_experts": cfg.shared_experts, "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": {"factor": cfg.yarn_factor, "mscale": cfg.yarn_mscale,
                         "mscale_all_dim": cfg.yarn_mscale_all_dim,
                         "original_max_position_embeddings":
                             cfg.yarn_original_max_position,
                         "beta_fast": cfg.yarn_beta_fast,
                         "beta_slow": cfg.yarn_beta_slow},
        "num_classes": K,
    }


def notes(n=32, length=24, vocab=128, seed=1):
    return mimic_notes(jax.random.key(seed), n, length=length, vocab=vocab,
                       noise=4.0)


def test_yarn_frequencies_and_temperature_as_published():
    s = ref_backbone.shape_from(dict(ref_config(get_arch(
        "deepseek-v2-lite").with_overrides(num_layers=27)), num_classes=K))
    np.testing.assert_allclose(
        np.asarray(layers.yarn_frequencies(64, 10_000.0, 40.0, 4096, 32.0,
                                           1.0)),
        np.asarray(ref_backbone.yarn_inv_freq(s)), rtol=1e-6)
    from repro.models.attention import mla_temperature
    cfg = get_arch("deepseek-v2-lite")
    assert mla_temperature(cfg) == pytest.approx(
        (0.1 * 0.707 * np.log(40.0) + 1.0) ** 2)


@pytest.mark.parametrize("num_layers,dense", [(2, 1), (4, 2)],
                         ids=["dense+moe", "2dense+2moe"])
def test_backbone_logits_match_the_reference(num_layers, dense):
    cfg = tiny(num_layers=num_layers, first_k_dense=dense)
    core = NeuralCore(K, cfg, predict_block=4)
    key = jax.random.key(5)
    params = core.init(key, (24,))
    shape = ref_backbone.shape_from(ref_config(cfg))
    want_init = ref_backbone.init(key, shape, jnp.float32)
    assert jax.tree.structure(params) == jax.tree.structure(want_init)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want_init)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "lm_head" not in params
    tokens = notes()[0][:8]
    with jax.default_matmul_precision("highest"):
        got = core.logits(params, tokens)
        want = ref_backbone.logits(params, tokens, shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("impl", ["gmm", "dense"])
def test_held_shares_add_up_to_the_uncut_layer(impl):
    """Four chips of two experts each: their routed parts, with the shared
    experts every chip computes alike counted once, are the 8-expert
    layer; their held-token counts add up to every (token, choice).  A
    chip holds experts 0 and 1 of its own router's numbering, so the chip
    holding experts ``first, first + 1`` sees the router's columns
    rotated by ``first``."""
    whole = tiny(experts_held=0, num_experts=8, top_k=3)
    share = whole.with_overrides(experts_held=2)
    params = moe.moe_init(jax.random.key(2), whole, jnp.float32)
    x = jax.random.normal(jax.random.key(3), (2, 12, whole.d_model))
    with jax.default_matmul_precision("highest"):
        y, _, count = moe.moe_layer(params, x, whole, impl)
        shared = layers.mlp_apply(params["shared_mlp"], x, whole.act)
        parts, counts = [], []
        for first in range(0, 8, 2):
            held = dict(params,
                        router=jnp.roll(params["router"], -first, axis=1),
                        **{name: params[name][first:first + 2]
                           for name in ("wi_gate", "wi_up", "wo")})
            y_s, _, c_s = moe.moe_layer(held, x, share, impl)
            parts.append(y_s - shared)
            counts.append(int(c_s))
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(y), rtol=1e-5, atol=1e-6)
    assert sum(counts) == int(count) == 2 * 12 * 3


@pytest.mark.parametrize("steps", [1, 2, 5])
def test_minibatched_fit_matches_the_reference(steps):
    """Rows drawn in proportion to a skewed w from the same keys, and the
    same AdamW steps: the program's parameters after ``steps`` steps are
    the reference's.  An AdamW step is about lr * sign(g), so a coordinate
    whose gradient is at rounding level may step the other way: the
    relative difference of the moves stays a few 1e-4."""
    cfg = tiny()
    tokens, _, classes = notes()
    onehot = jax.nn.one_hot(classes, K)
    w = jax.random.dirichlet(jax.random.key(4), jnp.ones(classes.shape[0]))
    key = jax.random.key(6)
    core = NeuralCore(K, cfg, steps=steps, lr=1e-3, batch_size=8)
    ref = ref_backbone.Backbone(ref_backbone.shape_from(ref_config(cfg)),
                                steps, 8, 1e-3)
    with jax.default_matmul_precision("highest"):
        got, counts = jax.jit(core.fit_counted)(core.init(key, (24,)), key,
                                                tokens, onehot, w)
    want = ref.fit(key, tokens, onehot, w)
    start = ref.init(key)
    moved = [np.asarray(b) - np.asarray(s) for b, s in
             zip(jax.tree.leaves(want), jax.tree.leaves(start))]
    diff = [np.asarray(a) - np.asarray(b) for a, b in
            zip(jax.tree.leaves(got), jax.tree.leaves(want))]
    gap = (np.sqrt(sum(np.sum(d * d) for d in diff))
           / np.sqrt(sum(np.sum(m * m) for m in moved)))
    assert gap < 2e-3
    assert float(ref_backbone.scale_update_gap(got, want, start)) < 2e-3
    assert int(counts["tokens_fit"]) == steps * 8 * 24
    assert 0 < int(counts["expert_tokens_fit"]) <= steps * 8 * 24 * 3


def _session(backend, key=11, telemetry=None):
    tokens, chart, classes = notes(n=48, length=16)
    learners = [NeuralBackbone(cfg=tiny(), steps=4, batch_size=8,
                               predict_block=8),
                MLP(hidden=(16, 8), steps=20)]
    transport = MeteredTransport(codec=make_codec("int8"))
    proto = Protocol(SessionConfig(num_classes=K, max_rounds=2),
                     transport=transport, backend=backend,
                     telemetry=telemetry)
    fitted = proto.fit(jax.random.key(key),
                       endpoints_for(learners, [tokens, chart]), classes)
    return fitted, transport, [tokens, chart]


def test_backbone_session_compiled_matches_eager():
    eager, log_e, Xs = _session("eager")
    comp, log_c, _ = _session("compiled")
    assert [(c.agent, c.round) for c in eager.components] == \
           [(c.agent, c.round) for c in comp.components]
    assert len(comp.components) == 4
    assert_floats_close([c.alpha for c in eager.components],
                        [c.alpha for c in comp.components])
    for ce, cc in zip(eager.components, comp.components):
        for le, lc in zip(jax.tree.leaves(ce.params),
                          jax.tree.leaves(cc.params)):
            assert_floats_close(le, lc)
    assert_history_close(eager.history, comp.history)
    assert log_e.log.entries == log_c.log.entries
    np.testing.assert_array_equal(np.asarray(eager.predict(Xs)),
                                  np.asarray(comp.predict(Xs)))


def test_backbone_session_span_counts_its_work():
    from repro.telemetry import Telemetry
    tele = Telemetry()
    compiled._session_program.cache_clear()
    compiled.TRACE_COUNTS.clear()
    fitted, _, _ = _session("compiled", telemetry=tele)
    assert compiled.TRACE_COUNTS["backbone_fit"] == 1
    assert compiled.TRACE_COUNTS["backbone_predict"] == 1
    tele.sync_gauges()
    assert tele.registry.gauge("program_traces",
                               program="backbone_fit") == 1
    span = [sp for sp in tele.tracer.spans if sp.name == "session"][-1]
    hops = sum(1 for c in fitted.components if c.agent == 0)
    assert span.attrs["tokens_fit"] == hops * 4 * 8 * 16
    assert span.attrs["tokens_predict"] == hops * 48 * 16
    assert span.attrs["expert_tokens"] == (span.attrs["expert_tokens_fit"]
                                           + span.attrs[
                                               "expert_tokens_predict"])
    assert 0 < span.attrs["expert_tokens_predict"] <= hops * 48 * 16 * 3


def test_backbone_scopes_keep_values(monkeypatch):
    """Name scopes are metadata: the session program traced without any
    of them gives bit for bit what the scoped program gives."""
    tokens, chart, classes = notes(n=48, length=16)
    plan = compiled.plan_for([NeuralBackbone(cfg=tiny(), steps=3,
                                             batch_size=8, predict_block=8),
                              MLP(hidden=(16, 8), steps=10)], K,
                             max_rounds=2, codec=make_codec("int8"))
    shapes = ((16,), (16,))
    args = (jax.random.key(3), (tokens, chart), classes)
    scoped_fn = compiled.make_session_fn(plan, shapes)
    text = jax.jit(scoped_fn).lower(*args).as_text(debug_info=True)
    for scope in ("backbone_attn", "backbone_router", "backbone_experts",
                  "backbone_shared", "backbone_ffn", "backbone_batch",
                  "backbone_predict"):
        assert scope in text, scope
    scoped = jax.jit(scoped_fn)(*args)
    monkeypatch.setattr(jax, "named_scope", lambda name: nullcontext())
    bare = jax.jit(compiled.make_session_fn(plan, shapes))(*args)
    for a, b in zip(jax.tree.leaves(scoped), jax.tree.leaves(bare)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_classifier_allocates_no_lm_head():
    cfg = tiny()
    params = jax.eval_shape(lambda: classifier.init_params(
        jax.random.key(0), cfg, K))
    assert "lm_head" not in params and "cls_head" in params
    from repro.models import transformer
    full = jax.eval_shape(lambda: transformer.init_params(jax.random.key(0),
                                                          cfg))
    assert "lm_head" in full          # the LM keeps its untied output head

"""Serve-path channel: prediction-time ScoreBlockMsg traffic through the
wire subsystem.  Pins eager vs compiled ``predict_distributed`` bit-for-bit
per codec (predictions, transport entries, bits_by_kind, accountant state),
the budget degrade -> head-only fallback with no free bits, serve-traffic
checkpoint/resume, the serve-axis codec sweep, and the fig4 frontier JSON
schema."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from program_tolerance import assert_floats_close

from repro.comm import (BudgetSpec, BudgetedTransport, GaussianMechanism,
                        make_codec)
from repro.core.compiled import plan_for, quant_sweep_run, serve_session
from repro.core.engine import (MeteredTransport, Protocol, SessionConfig,
                               endpoints_for)
from repro.data.partition import train_test_split, vertical_split
from repro.data.synthetic import blob_fig3
from repro.learners.logistic import LogisticRegression
from repro.learners.tree import DecisionTree

CODECS = ["fp32", "fp16", "int8", "int4", "topk"]


@pytest.fixture(scope="module")
def blob():
    key = jax.random.key(0)
    ds = blob_fig3(key, n=240)
    tr, te = train_test_split(0, 240)
    Xs = vertical_split(ds.X, ds.splits)
    return ([x[tr] for x in Xs], ds.classes[tr],
            [x[te] for x in Xs], ds.classes[te], ds.num_classes)


def _engines(blob, make_transport, rounds=3, steps=40, **cfg_kw):
    """Two identically-configured engines (eager, compiled), fitted."""
    Xtr, ctr, _, _, k = blob
    out = []
    for backend in ("eager", "compiled"):
        transport = make_transport()
        engine = Protocol(
            SessionConfig(num_classes=k, max_rounds=rounds, **cfg_kw),
            transport=transport, backend=backend)
        engine.fit(jax.random.key(11),
                   endpoints_for([LogisticRegression(steps=steps)
                                  for _ in Xtr], Xtr), ctr)
        out.append((engine, transport))
    return out


# ============================================== eager == compiled, per codec
@pytest.mark.parametrize("name", CODECS)
def test_serve_compiled_matches_eager_per_codec(blob, name):
    """The serve-path acceptance pin: identical distributed predictions AND
    identical encoded-bit ledgers, entry for entry, for every codec rung."""
    Xtr, _, Xte, _, k = blob
    (pe, te_), (pc, tc) = _engines(
        blob, lambda: MeteredTransport(codec=make_codec(name)))
    p_e = pe.predict_distributed(Xte)
    p_c = pc.predict_distributed(Xte)
    np.testing.assert_array_equal(np.asarray(p_e), np.asarray(p_c))
    assert te_.log.entries == tc.log.entries
    assert te_.bits_by_kind() == tc.bits_by_kind()
    blocks = [e for e in te_.log.entries if e["kind"] == "score_block"]
    assert len(blocks) == len(Xtr) - 1          # head ships nothing
    shape = (Xte[0].shape[0], k)
    assert all(e["bits"] == make_codec(name).wire_bits(shape)
               for e in blocks)
    if name != "fp32":
        # the serve ledger books *encoded* bits, strictly below raw fp32
        assert all(e["bits"] < 32 * shape[0] * shape[1] for e in blocks)


def test_serve_max_round_parity(blob):
    """max_round masking (partial-ensemble serving) stays pinned across
    backends too."""
    Xtr, _, Xte, _, _ = blob
    (pe, te_), (pc, tc) = _engines(
        blob, lambda: MeteredTransport(codec=make_codec("int8")))
    np.testing.assert_array_equal(
        np.asarray(pe.predict_distributed(Xte, max_round=0)),
        np.asarray(pc.predict_distributed(Xte, max_round=0)))
    assert te_.log.entries == tc.log.entries


def test_serve_compiled_matches_eager_with_privacy(blob):
    """DP serve blocks: same noise draws, same ledger, and the accountant
    composes one release per shipped block per agent on both backends."""
    Xtr, _, Xte, _, _ = blob
    mech = GaussianMechanism(epsilon=2.0, clip=0.1)
    (pe, te_), (pc, tc) = _engines(
        blob, lambda: MeteredTransport(codec=make_codec("int8"),
                                       privacy=mech))
    before = dict(te_.accountant.releases)
    p_e = pe.predict_distributed(Xte)
    p_c = pc.predict_distributed(Xte)
    np.testing.assert_array_equal(np.asarray(p_e), np.asarray(p_c))
    assert te_.log.entries == tc.log.entries
    assert te_.accountant.releases == tc.accountant.releases
    assert te_.accountant.report(mech) == tc.accountant.report(mech)
    # every non-head agent released exactly one noised block; the head's
    # own block never crosses the wire, so it spends no epsilon
    delta = {a: te_.accountant.releases[a] - before.get(a, 0)
             for a in te_.accountant.releases}
    assert delta == {f"agent{m}": (1 if m else 0) for m in range(len(Xtr))}


def test_serve_codec_override(blob):
    """serve_codec channels only the prediction traffic: training hops stay
    raw fp32 (bit-identical to a channel-less run), serve blocks encode —
    on both backends, identically."""
    Xtr, _, Xte, _, k = blob
    (pe, te_), (pc, tc) = _engines(
        blob, lambda: MeteredTransport(serve_codec=make_codec("int8")))
    (pr, tr_), _ = _engines(blob, MeteredTransport)
    p_e = pe.predict_distributed(Xte)
    p_c = pc.predict_distributed(Xte)
    np.testing.assert_array_equal(np.asarray(p_e), np.asarray(p_c))
    assert te_.log.entries == tc.log.entries
    ign = [e for e in te_.log.entries if e["kind"] == "ignorance"]
    n = Xtr[0].shape[0]
    assert all(e["bits"] == 32 * n for e in ign)        # training stays raw
    shape = (Xte[0].shape[0], k)
    blocks = [e for e in te_.log.entries if e["kind"] == "score_block"]
    assert all(e["bits"] == make_codec("int8").wire_bits(shape)
               for e in blocks)
    # training trajectory unaffected by the serve-only channel
    train_e = [e for e in te_.log.entries if e["kind"] != "score_block"]
    train_r = [e for e in tr_.log.entries if e["kind"] != "score_block"]
    assert train_e == train_r


def test_serve_default_key_identical_across_backends(blob):
    """Both backends derive the *same* default serve key — the session's
    evolved post-run ``state.key`` (the only anchor a resumed session can
    reproduce) — pinned directly on the key data, so a divergence cannot
    hide behind argmax-stable predictions.  Covers the full run and the
    alpha<=0 early stop (where the compiled scan keeps splitting masked
    slots the eager loop never reaches)."""
    from dataclasses import dataclass

    from repro.learners.base import Learner, LearnerCore

    @dataclass(frozen=True)
    class _ConstCore(LearnerCore):
        num_classes: int

        def init(self, key, shapes):
            return {"z": jnp.zeros(())}

        def fit(self, params, key, X, onehot, w):
            return params

        def logits(self, params, X):
            return (jnp.zeros((X.shape[0], self.num_classes))
                    .at[:, 0].set(1.0) + params["z"])

    @dataclass(frozen=True)
    class _ConstLearner(Learner):
        num_classes: int
        functional = True

        def core(self, num_classes):
            return _ConstCore(num_classes)

        def fit(self, key, X, classes, w, num_classes):
            core = self.core(num_classes)
            return core.fit(core.init(key, X.shape[1:]), key, X,
                            jax.nn.one_hot(classes, num_classes), w)

        def predict(self, params, X):
            return jnp.argmax(
                _ConstCore(self.num_classes).logits(params, X), axis=-1)

    Xtr, ctr, _, _, k = blob

    def keys_for(learners):
        out = []
        for backend in ("eager", "compiled"):
            engine = Protocol(
                SessionConfig(num_classes=k, max_rounds=3),
                transport=MeteredTransport(codec=make_codec("int8")),
                backend=backend)
            engine.fit(jax.random.key(11),
                       endpoints_for(learners(), Xtr[:len(learners())]),
                       ctr)
            if backend == "eager":
                out.append(engine._session.state.key)
            else:
                _, _, result = engine._compiled_ctx
                out.append(engine._evolved_key(result))
        return out

    full = keys_for(lambda: [LogisticRegression(steps=40) for _ in Xtr])
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(full[0])),
        np.asarray(jax.random.key_data(full[1])))

    stopped = keys_for(lambda: [LogisticRegression(steps=40),
                                _ConstLearner(k),
                                LogisticRegression(steps=40)])
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(stopped[0])),
        np.asarray(jax.random.key_data(stopped[1])))


# =========================================== budget: degrade -> head-only
def _squeeze_serve_budget(transport, spec, shape, leave_rungs):
    """Shrink the remaining session budget (via the resume carryover
    mechanism) so the next predict can afford exactly the cheapest
    ``leave_rungs`` serve blocks."""
    costs = spec.payload_costs(shape)
    transport.carryover_bits = (spec.session_bits - transport.log.total_bits
                                - costs[-1] * leave_rungs)


def test_serve_budget_exhaustion_head_only(blob):
    """Budget-exhaustion mid-predict: the first block degrades down the
    ladder, later blocks skip (head-only fallback), the transport flags
    exhausted, and not one bit is booked for a skipped block — identically
    on both backends."""
    Xtr, _, Xte, cte, k = blob
    spec = BudgetSpec(session_bits=10 ** 8)
    shape = (Xte[0].shape[0], k)
    (pe, te_), (pc, tc) = _engines(blob, lambda: BudgetedTransport(spec))
    for t in (te_, tc):
        _squeeze_serve_budget(t, spec, shape, leave_rungs=1)
    total_before = {id(t): t.log.total_bits for t in (te_, tc)}
    p_e = pe.predict_distributed(Xte)
    p_c = pc.predict_distributed(Xte)
    np.testing.assert_array_equal(np.asarray(p_e), np.asarray(p_c))
    assert te_.log.entries == tc.log.entries
    assert te_.link_spent == tc.link_spent
    assert sorted(te_.skipped) == sorted(tc.skipped)
    assert te_.exhausted and tc.exhausted
    blocks = [e for e in te_.log.entries if e["kind"] == "score_block"]
    # exactly one block shipped, degraded to the cheapest rung (int4)
    assert len(blocks) == 1
    assert blocks[0]["bits"] == spec.ladder[-1].wire_bits(shape)
    # the other agents' blocks were dropped, not priced: no free bits
    assert len(te_.skipped) == len(Xtr) - 2
    spent = te_.log.total_bits - total_before[id(te_)]
    assert spent == blocks[0]["bits"]
    assert te_.log.total_bits + te_.carryover_bits <= spec.session_bits


def test_serve_budget_full_skip_is_head_only_prediction(blob):
    """With no serve budget at all, every remote block skips and the answer
    equals the head agent predicting from its own components alone."""
    Xtr, _, Xte, _, k = blob
    spec = BudgetSpec(session_bits=10 ** 8)
    shape = (Xte[0].shape[0], k)
    (pe, te_), _ = _engines(blob, lambda: BudgetedTransport(spec))
    _squeeze_serve_budget(te_, spec, shape, leave_rungs=0)
    preds = pe.predict_distributed(Xte)
    assert len(te_.skipped) == len(Xtr) - 1
    assert not any(e["kind"] == "score_block" for e in te_.log.entries)
    session = pe._session
    head_block = session.endpoints[0].score_block(
        session.state.components, k, X=Xte[0])
    np.testing.assert_array_equal(
        np.asarray(preds), np.asarray(jnp.argmax(head_block, axis=-1)))


# ======================================================= checkpoint / resume
def test_serve_traffic_survives_resume(blob, tmp_path):
    """Serve-path DP releases and budget spend cross the pause/resume
    boundary (extends test_budget_and_privacy_survive_resume to
    ScoreBlockMsg traffic): a mid-session predict books bits and epsilon
    that the resumed run keeps counting against the same caps."""
    Xtr, ctr, Xte, _, k = blob
    spec = BudgetSpec(session_bits=60_000)
    mech = GaussianMechanism(epsilon=2.0, clip=0.1)
    cfg = SessionConfig(num_classes=k, max_rounds=5,
                        stop_on_negative_alpha=False)

    def make():
        t = BudgetedTransport(spec, privacy=mech)
        return Protocol(cfg, transport=t), t

    def eps():
        return endpoints_for([DecisionTree(depth=3, num_thresholds=8)
                              for _ in Xtr], Xtr)

    def serve_then_continue(session):
        preds = session.predict_distributed(Xte)
        session.run()
        return preds

    eng, t_full = make()
    full = eng.start(jax.random.key(9), eps(), ctr)
    full.step()
    p_full = serve_then_continue(full)
    assert any(e["kind"] == "score_block" for e in t_full.log.entries)

    eng, t_part = make()
    part = eng.start(jax.random.key(9), eps(), ctr)
    part.step()
    p_part = part.predict_distributed(Xte)
    np.testing.assert_array_equal(np.asarray(p_part), np.asarray(p_full))
    ckpt = str(tmp_path / "serve")
    part.checkpoint(ckpt)
    eng2, t_res = make()
    resumed = eng2.resume(ckpt, eps(), ctr)
    # the paused run's serve traffic counts against the resumed session cap
    assert t_res.carryover_bits == t_part.log.total_bits
    assert any(e["kind"] == "score_block" for e in t_part.log.entries)
    # ... and its DP releases keep composing
    assert t_res.accountant.releases == t_part.accountant.releases
    resumed.run()

    assert resumed.state.history == full.state.history
    assert (t_part.log.total_bits + t_res.log.total_bits
            == t_full.log.total_bits)
    assert t_res.link_spent == t_full.link_spent
    assert t_res.exhausted == t_full.exhausted
    assert t_res.accountant.releases == t_full.accountant.releases


# ================================================================ codec sweep
def test_quant_sweep_serve_axis(blob):
    """quant_sweep_run's serve axis: the vmapped (session + serve) program
    matches per-config compiled runs followed by serve_session — identical
    distributed predictions and wire metadata (sent / codec rung), blocks
    equal to the quantization-scale ulp.  (Exact block equality is not
    claimed across the static- and traced-qmax programs: XLA folds a
    compile-time qmax into the absmax/qmax scale division differently than
    a runtime one, one ulp in the scale.  The acceptance pin — eager ==
    compiled predict_distributed, both static-qmax — is exact; see
    test_serve_compiled_matches_eager_per_codec.)"""
    Xtr, ctr, Xte, _, k = blob
    learners = [LogisticRegression(steps=30) for _ in Xtr]
    plan8 = plan_for(learners, k, max_rounds=2, codec=make_codec("int8"))
    plan4 = plan_for(learners, k, max_rounds=2, codec=make_codec("int4"))
    key = jax.random.key(0)
    from repro.comm.codecs import SERVE_FOLD
    from repro.core.compiled import compiled_session
    res, serve = quant_sweep_run(plan8, jnp.stack([key, key]), Xtr, ctr,
                                 jnp.asarray([127.0, 7.0]), serve_Xs=Xte)
    for row, plan in ((0, plan8), (1, plan4)):
        single = compiled_session(plan, key, Xtr, ctr)
        assert_floats_close(res.alphas[row], single.alphas)
        single_serve = serve_session(
            plan, single, jax.random.fold_in(key, SERVE_FOLD), Xte)
        np.testing.assert_array_equal(np.asarray(serve.preds[row]),
                                      np.asarray(single_serve.preds))
        np.testing.assert_array_equal(np.asarray(serve.sent[row]),
                                      np.asarray(single_serve.sent))
        np.testing.assert_array_equal(np.asarray(serve.codec_idx[row]),
                                      np.asarray(single_serve.codec_idx))
        np.testing.assert_allclose(np.asarray(serve.blocks[row]),
                                   np.asarray(single_serve.blocks),
                                   rtol=1e-6, atol=1e-6)


# ========================================================== frontier schema
def test_fig4_frontier_json_schema(tmp_path):
    """Smoke the emitted BENCH_comm.json schema at toy sizes: every row
    carries the train AND serve axes, and the quantized-oracle serve
    baselines are present and ordered."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    try:
        from benchmarks.fig4_transmission import frontier
    finally:
        sys.path.pop(0)
    out = str(tmp_path / "comm.json")
    res = frontier(out=out, sizes=(160, 2, 15))
    with open(out) as f:
        assert json.load(f) == res
    points = [r["point"] for r in res["rows"]]
    assert points[:5] == ["fp32", "fp16", "int8", "int4", "topk"]
    for r in res["rows"]:
        for field in ("acc", "interchange_bits", "serve_acc", "serve_bits",
                      "total_bits", "bits_by_kind", "rounds",
                      "bits_ratio_vs_fp32", "acc_drop_vs_fp32",
                      "serve_bits_ratio_vs_fp32", "serve_acc_drop_vs_fp32"):
            assert field in r, (r["point"], field)
        if not r["point"].startswith("budget50pct"):
            assert r["serve_bits"] == r["bits_by_kind"].get("score_block", 0)
            assert r["serve_bits"] > 0
        # a fully-skipped serve (head-only fallback, zero bits) reports a
        # null ratio, never a huge bogus compression number
        if r["serve_bits"] == 0:
            assert r["serve_bits_ratio_vs_fp32"] is None
        else:
            assert r["serve_bits_ratio_vs_fp32"] > 0
    base = res["rows"][0]
    assert base["serve_bits_ratio_vs_fp32"] == 1.0
    oracle = res["oracle_serve_bits"]
    assert oracle["fp32"] > oracle["fp16"] > oracle["int8"] > oracle["int4"]
    budget = next(r for r in res["rows"] if r["point"] == "budget50pct")
    assert "skipped_hops" in budget and "exhausted" in budget
    # control-plane points: the adaptive controller and the RDP-accounted
    # DP trace ride the same schema
    assert "adaptive" in points
    rdp = next(r for r in res["rows"] if r["point"] == "int8+dp1+rdp")
    for agent, entry in rdp["dp"].items():
        assert entry["epsilon"] <= entry["epsilon_additive"] + 1e-9
    # scheduler demo: same link caps, both round orders, full schema
    demo = res["scheduler_demo"]
    assert demo["agents"] >= 3          # 2 agents cannot distinguish orders
    for order in ("sequential", "budget_aware"):
        for field in ("acc", "skipped_hops", "interchange_bits"):
            assert field in demo[order], (order, field)

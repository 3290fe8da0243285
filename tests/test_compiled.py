"""Compiled-backend pin: `backend="compiled"` (one lax.scan program per
session, core/compiled.py) must reproduce the eager engine under
sequential scheduling — the same components, predictions and metered
message ledger exactly, alphas, params and history to the float tolerance
of two separately compiled programs (tests/program_tolerance.py) — and
the vmapped fleet must match per-session compiled runs exactly."""
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from program_tolerance import assert_floats_close, assert_history_close

from repro.control.scheduler import BudgetAwarePlan
from repro.core.compiled import (AsyncStalePlan, SessionPlan, async_session,
                                 compiled_session, fitted_from_async_result,
                                 fitted_from_result, fleet_run, plan_for)
from repro.core.engine import (MeteredTransport, Protocol, RandomScheduler,
                               SessionConfig, endpoints_for)
from repro.data.partition import train_test_split, vertical_split
from repro.data.synthetic import blob_fig3
from repro.learners.base import Learner, LearnerCore
from repro.learners.logistic import LogisticRegression
from repro.learners.mlp import MLP
from repro.learners.tree import DecisionTree


@pytest.fixture(scope="module")
def blob():
    key = jax.random.key(0)
    ds = blob_fig3(key, n=240)
    tr, te = train_test_split(0, 240)
    Xs = vertical_split(ds.X, ds.splits)
    return ([x[tr] for x in Xs], ds.classes[tr],
            [x[te] for x in Xs], ds.classes[te], ds.num_classes)


LEARNERS = {
    "logistic": lambda: LogisticRegression(steps=60),
    "mlp": lambda: MLP(hidden=(16,), steps=40),
}


def _run_both(blob, learner_fn, **cfg_kw):
    Xtr, ctr, Xte, cte, k = blob
    learners = [learner_fn() for _ in Xtr]
    cfg = SessionConfig(num_classes=k, max_rounds=3, **cfg_kw)
    log_e, log_c = MeteredTransport(), MeteredTransport()
    eager = Protocol(cfg, transport=log_e).fit(
        jax.random.key(11), endpoints_for(learners, Xtr), ctr)
    comp = Protocol(cfg, transport=log_c, backend="compiled").fit(
        jax.random.key(11), endpoints_for(learners, Xtr), ctr)
    return eager, comp, log_e, log_c, Xte


def _assert_identical(eager, comp, Xte):
    assert [(c.agent, c.round) for c in eager.components] == \
           [(c.agent, c.round) for c in comp.components]
    assert_floats_close([c.alpha for c in eager.components],
                        [c.alpha for c in comp.components])
    for ce, cc in zip(eager.components, comp.components):
        for le, lc in zip(jax.tree.leaves(ce.params),
                          jax.tree.leaves(cc.params)):
            assert_floats_close(le, lc)
    assert_history_close(eager.history, comp.history)
    np.testing.assert_array_equal(np.asarray(eager.predict(Xte)),
                                  np.asarray(comp.predict(Xte)))


@pytest.mark.parametrize("name", list(LEARNERS))
def test_compiled_matches_eager(blob, name):
    eager, comp, log_e, log_c, Xte = _run_both(blob, LEARNERS[name])
    _assert_identical(eager, comp, Xte)
    # byte-identical Fig.-4 accounting, entry for entry
    assert log_e.log.entries == log_c.log.entries


def test_compiled_matches_eager_simple_variant(blob):
    """upstream=False (ASCII-Simple alphas) pins too."""
    eager, comp, _, _, Xte = _run_both(blob, LEARNERS["logistic"],
                                       upstream=False)
    _assert_identical(eager, comp, Xte)


def test_compiled_matches_eager_exact_reweight(blob):
    eager, comp, _, _, Xte = _run_both(blob, LEARNERS["logistic"],
                                       exact_reweight=True)
    _assert_identical(eager, comp, Xte)


# --------------------------------------------------- early-stop (line 8) pin
@dataclass(frozen=True)
class _ConstCore(LearnerCore):
    """Always predicts the last class, which the early-stop fixture's
    labels never hold: its weighted accuracy is 0, so its alpha is
    -alpha_cap and trips Algorithm 1's line-8 stop on its first hop."""
    num_classes: int

    def init(self, key, shapes):
        return {"z": jnp.zeros(())}

    def fit(self, params, key, X, onehot, w):
        return params

    def logits(self, params, X):
        base = jnp.zeros((X.shape[0], self.num_classes)).at[:, -1].set(1.0)
        return base + params["z"]


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
        return jnp.argmax(_ConstCore(self.num_classes).logits(params, X),
                          axis=-1)


def test_compiled_matches_eager_early_stop(blob):
    """The alpha<=0 stop (and the masked tail after it) pins on both
    backends."""
    Xtr, ctr, Xte, cte, k = blob
    ctr = jnp.where(ctr == k - 1, 0, ctr)      # no sample of the last class
    learners = [LogisticRegression(steps=60), _ConstLearner(k),
                LogisticRegression(steps=60)]
    cfg = SessionConfig(num_classes=k, max_rounds=3)
    eager = Protocol(cfg).fit(jax.random.key(5),
                              endpoints_for(learners, Xtr[:3]), ctr)
    comp = Protocol(cfg, backend="compiled").fit(
        jax.random.key(5), endpoints_for(learners, Xtr[:3]), ctr)
    # the constant agent must actually have tripped the stop mid-round
    assert eager.num_rounds == 1
    assert len(eager.history[0]["alphas"]) == 2   # head + triggering agent
    _assert_identical(eager, comp, Xte[:3])


# ---------------------------------------------------------- result extraction
def _per_leaf_components(plan, result, stale):
    """The reference the extraction program must equal: one eager slice per
    parameter leaf of each valid slot, in the order ``FittedASCII`` lists
    its components."""
    alphas = np.asarray(result.alphas)
    executed = np.asarray(result.executed)
    valid = np.asarray(result.valid)
    order = getattr(result, "order", None)
    order = None if order is None else np.asarray(order)
    out = []
    for t in range(plan.max_rounds):
        if not executed[t].any():
            break
        for j in range(plan.num_agents):
            if not stale and not executed[t, j]:
                break
            if valid[t, j]:
                agent = j if order is None else int(order[t, j])
                out.append((agent, t, float(alphas[t, j]),
                            jax.tree.map(lambda x, _t=t: x[_t],
                                         result.params[j])))
    return out


def _extraction_run(blob, case):
    """(plan, compiled result, learners) of one extraction case."""
    Xtr, ctr, _, _, k = blob
    kw = {}
    if case == "early_stop":
        ctr = jnp.where(ctr == k - 1, 0, ctr)
        learners = [LogisticRegression(steps=30), _ConstLearner(k),
                    LogisticRegression(steps=30)]
        Xtr = Xtr[:3]
    elif case == "sequential":
        learners = [MLP(hidden=(8,), steps=10) for _ in Xtr]
    else:
        learners = [LogisticRegression(steps=30) for _ in Xtr]
        kw["scheduler"] = (AsyncStalePlan() if case == "async_stale"
                           else BudgetAwarePlan(spend_signal="none"))
    plan = plan_for(learners, k, max_rounds=3, **kw)
    run = async_session if case == "async_stale" else compiled_session
    return plan, run(plan, jax.random.key(5), Xtr, ctr), learners


@pytest.mark.parametrize("case", ["sequential", "async_stale", "early_stop",
                                  "permuted"])
def test_extraction_matches_per_leaf_slices(blob, case):
    """One extraction program gives every component the agent, round,
    alpha and parameters, leaf for leaf and bit for bit, that slicing each
    leaf on its own gives."""
    plan, result, learners = _extraction_run(blob, case)
    stale = case == "async_stale"
    build = fitted_from_async_result if stale else fitted_from_result
    fitted = build(plan, result, learners)
    want = _per_leaf_components(plan, result, stale)
    assert want
    assert [(c.agent, c.round, c.alpha) for c in fitted.components] == \
           [w[:3] for w in want]
    for c, (*_, params) in zip(fitted.components, want):
        assert jax.tree.structure(c.params) == jax.tree.structure(params)
        for got, ref in zip(jax.tree.leaves(c.params),
                            jax.tree.leaves(params)):
            assert isinstance(got, jax.Array)
            assert got.dtype == ref.dtype
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    if case == "sequential":       # later rounds slice other rows
        assert len({c.round for c in fitted.components}) > 1
    if case == "early_stop":       # the stop tripped mid-round
        assert [len(r["alphas"]) for r in fitted.history] == [2]
    if case == "permuted":         # the scheduler really reordered a round
        order = np.asarray(result.order)
        assert any(list(row) != sorted(row) for row in order)


# ------------------------------------------------------------------ the fleet
def test_fleet_matches_single_sessions(blob):
    Xtr, ctr, _, _, k = blob
    plan = plan_for([LogisticRegression(steps=40) for _ in Xtr], k,
                    max_rounds=3)
    keys = jax.random.split(jax.random.key(0), 4)
    fleet = fleet_run(plan, keys, Xtr, ctr)
    assert fleet.alphas.shape == (4, 3, len(Xtr))
    for s in (0, 3):
        single = compiled_session(plan, keys[s], Xtr, ctr)
        np.testing.assert_array_equal(np.asarray(fleet.alphas[s]),
                                      np.asarray(single.alphas))
        np.testing.assert_array_equal(np.asarray(fleet.w[s]),
                                      np.asarray(single.w))


def test_fleet_data_batched(blob):
    """Per-cohort fleets: each session gets its own (Xs, classes)."""
    Xtr, ctr, _, _, k = blob
    S = 3
    Xs_b = [jnp.stack([x + 0.01 * s for s in range(S)]) for x in Xtr]
    classes_b = jnp.stack([ctr] * S)
    plan = plan_for([LogisticRegression(steps=30) for _ in Xtr], k,
                    max_rounds=2)
    fleet = fleet_run(plan, jax.random.split(jax.random.key(1), S),
                      Xs_b, classes_b, data_batched=True)
    assert fleet.alphas.shape == (S, 2, len(Xtr))
    assert bool(jnp.all(jnp.isfinite(fleet.alphas)))


# ------------------------------------------------------------------ contracts
@pytest.mark.parametrize("name", list(LEARNERS))
def test_core_composition_equals_eager_fit(blob, name):
    """The LearnerCore contract: fit(init(key), key, ...) == Learner.fit."""
    Xtr, ctr, _, _, k = blob
    learner = LEARNERS[name]()
    key = jax.random.key(9)
    w = jnp.full((ctr.shape[0],), 1.0 / ctr.shape[0])
    params_eager = learner.fit(key, Xtr[0], ctr, w, k)
    core = learner.core(k)
    onehot = jax.nn.one_hot(ctr, k)
    shapes = Xtr[0].shape[1:]
    # jit the composition like both engine backends do (op-by-op dispatch
    # fuses differently at the last ulp)
    fresh = jax.jit(lambda kk, X, oh, ww:
                    core.fit(core.init(kk, shapes), kk, X, oh, ww))
    params_core = fresh(key, Xtr[0], onehot, w)
    for le, lc in zip(jax.tree.leaves(params_eager),
                      jax.tree.leaves(params_core)):
        np.testing.assert_array_equal(np.asarray(le), np.asarray(lc))
    np.testing.assert_array_equal(
        np.asarray(learner.predict(params_eager, Xtr[0])),
        np.asarray(core.predict(params_core, Xtr[0])))


def test_compiled_rejects_eager_only_learners(blob):
    Xtr, ctr, _, _, k = blob
    cfg = SessionConfig(num_classes=k, max_rounds=2)
    eng = Protocol(cfg, backend="compiled")
    eps = endpoints_for([DecisionTree(depth=2) for _ in Xtr], Xtr)
    with pytest.raises(ValueError, match="LearnerCore"):
        eng.fit(jax.random.key(0), eps, ctr)


def test_compiled_rejects_nonsequential_scheduler(blob):
    Xtr, ctr, _, _, k = blob
    cfg = SessionConfig(num_classes=k, max_rounds=2)
    eng = Protocol(cfg, scheduler=RandomScheduler(0), backend="compiled")
    eps = endpoints_for([LogisticRegression(steps=10) for _ in Xtr], Xtr)
    with pytest.raises(ValueError, match="sequential"):
        eng.fit(jax.random.key(0), eps, ctr)


def test_unknown_backend_rejected(blob):
    _, _, _, _, k = blob
    with pytest.raises(ValueError, match="backend"):
        Protocol(SessionConfig(num_classes=k), backend="turbo")


# ============================================================= control sweeps
def test_control_sweep_controller_matches_static(blob):
    """PR 9: controller thresholds/beta become traced operands — one vmapped
    program sweeps N (cuts, beta) configs, each row bit-equal to a static
    per-config compile, and the whole sweep traces exactly once."""
    from repro.comm.codecs import Fp16Codec, QuantCodec
    from repro.control import AdaptiveController
    from repro.core import compiled
    Xtr, ctr, _, _, k = blob
    learners = [LogisticRegression(steps=30) for _ in Xtr]
    ladder = (Fp16Codec(), QuantCodec(bits=4))
    configs = [((0.5,), 0.0), ((0.1,), 0.0), ((0.9,), 0.5), ((0.3,), 0.9)]
    mk = lambda cut, beta: plan_for(
        learners, k, max_rounds=2,
        controller=AdaptiveController(ladder=ladder, thresholds=cut,
                                      beta=beta))
    plan = mk(*configs[0])
    key = jax.random.key(0)
    compiled.TRACE_COUNTS.clear()
    sweep = compiled.control_sweep_run(
        plan, jnp.stack([key] * len(configs)), Xtr, ctr,
        cuts=[c for c, _ in configs], betas=[b for _, b in configs])
    assert compiled.TRACE_COUNTS == {"control_sweep": 1}
    for row, (cut, beta) in enumerate(configs):
        single = compiled_session(mk(cut, beta), key, Xtr, ctr)
        np.testing.assert_array_equal(np.asarray(sweep.alphas[row]),
                                      np.asarray(single.alphas))
        np.testing.assert_array_equal(np.asarray(sweep.w[row]),
                                      np.asarray(single.w))
        np.testing.assert_array_equal(np.asarray(sweep.codec_idx[row]),
                                      np.asarray(single.codec_idx))


def test_control_sweep_budget_caps_match_static(blob):
    """Budget caps sweep as traced operands too — including a ``None``
    (uncapped) entry, lowered as the int32 sentinel — each row bit-equal to
    the statically-capped compile, one trace for the lot."""
    from repro.comm import BudgetSpec
    from repro.comm.codecs import QuantCodec
    from repro.core import compiled
    Xtr, ctr, _, _, k = blob
    learners = [LogisticRegression(steps=30) for _ in Xtr]
    ladder = (QuantCodec(bits=8), QuantCodec(bits=4))
    caps = [40_000, 20_000, 12_000, None]
    mk = lambda cap: plan_for(learners, k, max_rounds=3,
                              budget=BudgetSpec(session_bits=cap,
                                                ladder=ladder))
    plan = mk(caps[0])
    key = jax.random.key(0)
    compiled.TRACE_COUNTS.clear()
    sweep = compiled.control_sweep_run(plan, jnp.stack([key] * len(caps)),
                                       Xtr, ctr, session_bits=caps)
    assert compiled.TRACE_COUNTS == {"control_sweep": 1}
    for row, cap in enumerate(caps):
        single = compiled_session(mk(cap), key, Xtr, ctr)
        for field in ("alphas", "w", "sent", "codec_idx", "exhausted"):
            np.testing.assert_array_equal(
                np.asarray(getattr(sweep, field)[row]),
                np.asarray(getattr(single, field)))


def test_control_sweep_needs_a_control_plane(blob):
    Xtr, ctr, _, _, k = blob
    from repro.core import compiled
    plan = plan_for([LogisticRegression(steps=10) for _ in Xtr], k,
                    max_rounds=2)
    with pytest.raises(ValueError, match="neither"):
        compiled.control_sweep_run(plan, jnp.stack([jax.random.key(0)]),
                                   Xtr, ctr)

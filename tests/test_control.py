"""Control-plane subsystem: the adaptive codec controller must agree
across engine backends per codec ladder (ledgers and rung choices exactly,
float trajectories to the tolerance of two separately compiled programs,
tests/program_tolerance.py), compose with budgets as a floor on the ladder walk, and
checkpoint/resume exactly; the budget-aware scheduler must order rounds by
remaining link budget deterministically (and replay that order across
resume); the RDP accountant must never report more epsilon than additive
composition, and accountant reads must be monotone-safe."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from program_tolerance import assert_floats_close, assert_history_close

from repro.comm import (BudgetSpec, BudgetedTransport, GaussianMechanism,
                        PrivacyAccountant, make_codec)
from repro.comm.codecs import Fp16Codec, Fp32Codec, QuantCodec
from repro.control import (AdaptiveController, BudgetAwareScheduler,
                           RDPAccountant, make_accountant)
from repro.control.accounting import rdp_epsilon
from repro.control.adaptive import DEFAULT_LADDER
from repro.core.engine import (MeteredTransport, Protocol, SessionConfig,
                               endpoints_for)
from repro.data.partition import train_test_split, vertical_split
from repro.data.synthetic import blob_fig3
from repro.learners.logistic import LogisticRegression
from repro.learners.tree import DecisionTree

LADDERS = {
    "default": DEFAULT_LADDER,
    "two-rung": (Fp16Codec(), QuantCodec(bits=4)),
}


@pytest.fixture(scope="module")
def blob():
    key = jax.random.key(0)
    ds = blob_fig3(key, n=240)
    tr, te = train_test_split(0, 240)
    Xs = vertical_split(ds.X, ds.splits)
    return ([x[tr] for x in Xs], ds.classes[tr],
            [x[te] for x in Xs], ds.classes[te], ds.num_classes)


def _fit(blob, transport, backend, rounds=3, steps=40, scheduler=None,
         **cfg_kw):
    Xtr, ctr, _, _, k = blob
    cfg = SessionConfig(num_classes=k, max_rounds=rounds, **cfg_kw)
    learners = [LogisticRegression(steps=steps) for _ in Xtr]
    engine = Protocol(cfg, transport=transport, backend=backend,
                      scheduler=scheduler)
    return engine.fit(jax.random.key(11), endpoints_for(learners, Xtr), ctr)


def _assert_identical(eager, comp, Xte):
    assert [(c.agent, c.round) for c in eager.components] == \
           [(c.agent, c.round) for c in comp.components]
    assert_floats_close([c.alpha for c in eager.components],
                        [c.alpha for c in comp.components])
    assert_history_close(eager.history, comp.history)
    np.testing.assert_array_equal(np.asarray(eager.predict(Xte)),
                                  np.asarray(comp.predict(Xte)))


# ============================================================ controller unit
def test_controller_validation():
    with pytest.raises(ValueError, match="at least one"):
        AdaptiveController(ladder=())
    with pytest.raises(ValueError, match="stateless"):
        AdaptiveController(ladder=(make_codec("topk"),))
    with pytest.raises(ValueError, match="thresholds"):
        AdaptiveController(thresholds=(0.5,))
    with pytest.raises(ValueError, match="descend"):
        AdaptiveController(thresholds=(0.1, 0.5, 0.9))
    with pytest.raises(ValueError, match="beta"):
        AdaptiveController(beta=1.0)
    with pytest.raises(ValueError, match="stat"):
        AdaptiveController(stat="kurtosis")


def test_controller_rung_policy_branchless():
    """The rung is sum(ema < thresholds): a quiet channel decays down the
    ladder, a loud one snaps back up — and the computation is pure/jittable
    (it must ride the session scan)."""
    c = AdaptiveController(thresholds=(0.75, 0.3, 0.03), beta=0.0)
    n = 64
    uniform = jnp.full((n,), 1.0 / n)
    spike = jnp.zeros((n,)).at[0].set(1.0)
    ema = c.init_state()
    # no innovation: statistic 0 -> coarsest rung
    rung, ema2 = jax.jit(c.step)(uniform, uniform, ema)
    assert int(rung) == 3 and float(ema2) == 0.0
    # maximal innovation (uniform -> delta): TV ~ 1 -> finest rung
    rung, ema3 = jax.jit(c.step)(uniform, spike, ema2)
    assert int(rung) == 0
    # mid innovation lands on a middle rung
    mid = (uniform + spike) / 2.0
    rung, _ = jax.jit(c.step)(uniform, mid, ema2)
    assert int(rung) in (1, 2)


def test_controller_entropy_stat_monotone():
    c = AdaptiveController(stat="entropy", beta=0.0)
    n = 256
    uniform = jnp.full((n,), 1.0 / n)
    conc = jnp.zeros((n,)).at[:4].set(0.25)
    s_u = float(c.observe(uniform, uniform))
    s_c = float(c.observe(uniform, conc))
    assert s_u == pytest.approx(1.0, abs=1e-6)
    assert s_c < 0.3
    # l2 participation ratio agrees on the ordering
    c2 = AdaptiveController(stat="l2", beta=0.0)
    assert float(c2.observe(uniform, uniform)) == pytest.approx(1.0, 1e-6)
    assert float(c2.observe(uniform, conc)) < 0.1


# ================================================= eager == compiled, per ladder
@pytest.mark.parametrize("ladder", sorted(LADDERS))
def test_compiled_matches_eager_adaptive(blob, ladder):
    """The tentpole pin: the same trajectories, identical encoded-bit
    ledgers, and identical per-hop rung choices on both backends, per codec
    ladder."""
    mk = lambda: AdaptiveController(ladder=LADDERS[ladder])  # noqa: E731
    te_, tc = (MeteredTransport(controller=mk()) for _ in range(2))
    eager = _fit(blob, te_, "eager")
    comp = _fit(blob, tc, "compiled")
    _assert_identical(eager, comp, blob[2])
    assert te_.log.entries == tc.log.entries
    # rung choice is observable through the encoded ignorance sizes
    n = blob[0][0].shape[0]
    sizes = {e["bits"] for e in te_.log.entries if e["kind"] == "ignorance"}
    allowed = {c.wire_bits(n) for c in LADDERS[ladder]}
    assert sizes <= allowed and sizes


def test_compiled_matches_eager_adaptive_entropy_stat(blob):
    """The entropy statistic decays hop over hop on this cohort, so several
    distinct rungs ship — still the same across backends."""
    mk = lambda: AdaptiveController(stat="entropy")  # noqa: E731
    te_, tc = (MeteredTransport(controller=mk()) for _ in range(2))
    eager = _fit(blob, te_, "eager", rounds=4)
    comp = _fit(blob, tc, "compiled", rounds=4)
    _assert_identical(eager, comp, blob[2])
    assert te_.log.entries == tc.log.entries
    n = blob[0][0].shape[0]
    sizes = {e["bits"] for e in te_.log.entries if e["kind"] == "ignorance"}
    assert len(sizes) >= 2          # the controller actually adapted


def test_compiled_matches_eager_adaptive_with_privacy(blob):
    mech = GaussianMechanism(epsilon=2.0, clip=0.1)
    mk = lambda: MeteredTransport(controller=AdaptiveController(),  # noqa: E731
                                  privacy=mech)
    te_, tc = mk(), mk()
    eager = _fit(blob, te_, "eager")
    comp = _fit(blob, tc, "compiled")
    _assert_identical(eager, comp, blob[2])
    assert te_.log.entries == tc.log.entries
    assert te_.accountant.releases == tc.accountant.releases


def test_compiled_matches_eager_adaptive_under_budget(blob):
    """Controller + budget compose: the controller rung floors the ladder
    walk, the budget degrades past it when bits run low — identical rungs,
    ledgers, link spend, and exhaustion on both backends."""
    spec = BudgetSpec(session_bits=48_000)
    mk = lambda: BudgetedTransport(spec,  # noqa: E731
                                   controller=AdaptiveController())
    te_, tc = mk(), mk()
    eager = _fit(blob, te_, "eager", rounds=5, stop_on_negative_alpha=False)
    comp = _fit(blob, tc, "compiled", rounds=5, stop_on_negative_alpha=False)
    _assert_identical(eager, comp, blob[2])
    assert te_.log.entries == tc.log.entries
    assert te_.link_spent == tc.link_spent
    assert sorted(te_.skipped) == sorted(tc.skipped)
    assert te_.exhausted == tc.exhausted


def test_serve_parity_budget_with_controller(blob):
    """Regression: a budgeted transport with a controller must serve score
    blocks through the budget ladder (encoded, priced at the shipped rung)
    on BOTH backends — the controller's raw-serve bypass applies only to
    unbudgeted transports."""
    Xtr, ctr, Xte, cte, k = blob
    # cap sized so training finishes undegraded (~119k bits) but the serve
    # walk must degrade below fp32 blocks and skip the tail
    spec = BudgetSpec(session_bits=124_000)
    mk = lambda: BudgetedTransport(spec,  # noqa: E731
                                   controller=AdaptiveController())
    te_, tc = mk(), mk()
    preds = {}
    for backend, t in (("eager", te_), ("compiled", tc)):
        eng = Protocol(SessionConfig(num_classes=k, max_rounds=3),
                       transport=t, backend=backend)
        eng.fit(jax.random.key(11),
                endpoints_for([LogisticRegression(steps=40) for _ in Xtr],
                              Xtr), ctr)
        preds[backend] = np.asarray(eng.predict_distributed(Xte))
    np.testing.assert_array_equal(preds["eager"], preds["compiled"])
    assert te_.log.entries == tc.log.entries
    assert te_.link_spent == tc.link_spent
    assert te_.exhausted == tc.exhausted
    # the serve walk actually degraded (distinct rung sizes shipped) and
    # the session cap held — no raw blocks booked at encoded prices
    blocks = [e["bits"] for e in te_.log.entries
              if e["kind"] == "score_block"]
    assert len(blocks) >= 2 and min(blocks) < max(blocks)
    assert te_.skipped and te_.exhausted
    assert te_.total_bits <= spec.session_bits


def test_budgeted_controller_ladder_mismatch_rejected():
    spec = BudgetSpec(session_bits=10 ** 6)
    with pytest.raises(ValueError, match="share the budget's ladder"):
        BudgetedTransport(spec, controller=AdaptiveController(
            ladder=(Fp16Codec(), QuantCodec(bits=4))))


def test_controller_with_explicit_codec_rejected():
    with pytest.raises(ValueError, match="drives codec choice"):
        MeteredTransport(codec=make_codec("int8"),
                         controller=AdaptiveController())


def test_controller_floor_respected_under_budget(blob):
    """With an uncapped budget the walk starts at the controller's rung:
    the shipped sizes match a plain controlled transport hop for hop."""
    spec = BudgetSpec(session_bits=10 ** 8)
    tb = BudgetedTransport(spec, controller=AdaptiveController())
    tm = MeteredTransport(controller=AdaptiveController())
    _fit(blob, tb, "eager")
    _fit(blob, tm, "eager")
    ign_b = [e["bits"] for e in tb.log.entries if e["kind"] == "ignorance"]
    ign_m = [e["bits"] for e in tm.log.entries if e["kind"] == "ignorance"]
    assert ign_b == ign_m and ign_b


# ======================================================== checkpoint / resume
def test_controller_and_rdp_state_survive_resume(blob, tmp_path):
    """Satellite pin: adaptive-controller EMA state and RDP accountant
    state cross the pause/resume boundary — the resumed run picks identical
    rungs (no free bits) and keeps composing epsilon (no resets), matching
    the uninterrupted run exactly."""
    Xtr, ctr, Xte, cte, k = blob
    spec = BudgetSpec(session_bits=60_000)
    mech = GaussianMechanism(epsilon=2.0, clip=0.1)
    cfg = SessionConfig(num_classes=k, max_rounds=4,
                        stop_on_negative_alpha=False)

    def make():
        t = BudgetedTransport(spec, privacy=mech,
                              controller=AdaptiveController(),
                              accountant=RDPAccountant())
        return Protocol(cfg, transport=t), t

    def eps():
        return endpoints_for([DecisionTree(depth=3, num_thresholds=8)
                              for _ in Xtr], Xtr)

    eng, t_full = make()
    full = eng.start(jax.random.key(9), eps(), ctr)
    full.run()

    eng, t_part = make()
    part = eng.start(jax.random.key(9), eps(), ctr)
    part.step()
    ckpt = str(tmp_path / "ctrl")
    part.checkpoint(ckpt)
    assert part.state.comm.get("ctrl_state") is not None
    eng2, t_res = make()
    resumed = eng2.resume(ckpt, eps(), ctr)
    # the EMA crossed the boundary bit for bit
    np.testing.assert_array_equal(np.asarray(t_res.ctrl_state),
                                  np.asarray(t_part.ctrl_state))
    resumed.run()

    assert resumed.state.history == full.state.history
    np.testing.assert_array_equal(np.asarray(resumed.state.w),
                                  np.asarray(full.state.w))
    # no free bits: the split ledgers sum to the uninterrupted ledger
    assert (t_part.log.total_bits + t_res.log.total_bits
            == t_full.log.total_bits)
    assert t_res.link_spent == t_full.link_spent
    np.testing.assert_array_equal(np.asarray(t_res.ctrl_state),
                                  np.asarray(t_full.ctrl_state))
    # no epsilon resets: release counts and the RDP report compose across
    # the boundary
    assert t_res.accountant.releases == t_full.accountant.releases
    assert t_res.accountant.report(mech) == t_full.accountant.report(mech)


def test_accountant_reads_are_monotone_safe(blob, tmp_path):
    """Satellite regression: reading epsilon mid-session (spent/report),
    checkpointing, and resuming must neither double-count nor drop the last
    release — the final ledger equals a run with no reads at all."""
    Xtr, ctr, _, _, k = blob
    mech = GaussianMechanism(epsilon=1.0, clip=0.1)
    cfg = SessionConfig(num_classes=k, max_rounds=3,
                        stop_on_negative_alpha=False)

    def make(acct):
        t = MeteredTransport(privacy=mech, accountant=acct)
        return Protocol(cfg, transport=t), t

    def eps():
        return endpoints_for([DecisionTree(depth=3, num_thresholds=8)
                              for _ in Xtr], Xtr)

    for acct_name in ("basic", "rdp"):
        eng, t_quiet = make(make_accountant(acct_name))
        quiet = eng.start(jax.random.key(3), eps(), ctr)
        quiet.run()

        eng, t_read = make(make_accountant(acct_name))
        sess = eng.start(jax.random.key(3), eps(), ctr)
        sess.step()
        before = t_read.accountant.spent("agent0", mech)
        assert t_read.accountant.spent("agent0", mech) == before  # pure
        t_read.accountant.report(mech)
        ckpt = str(tmp_path / f"acct-{acct_name}")
        sess.checkpoint(ckpt)
        t_read.accountant.report(mech)                 # read after snapshot
        eng2, t_res = make(make_accountant(acct_name))
        resumed = eng2.resume(ckpt, eps(), ctr)
        t_res.accountant.report(mech)                  # read after restore
        resumed.run()
        assert t_res.accountant.releases == t_quiet.accountant.releases
        assert t_res.accountant.report(mech) == t_quiet.accountant.report(mech)


# ============================================================= RDP accounting
def test_rdp_never_looser_than_additive():
    mech = GaussianMechanism(epsilon=1.0, delta=1e-5)
    for k in (1, 2, 5, 20, 100):
        eps, _, _ = rdp_epsilon(k, mech)
        assert eps <= k * mech.epsilon + 1e-12, (k, eps)
    # and strictly tighter once composition bites
    eps5, delta5, _ = rdp_epsilon(5, mech)
    assert eps5 < 5 * mech.epsilon * 0.75
    assert delta5 == mech.delta               # the RDP bound's own delta
    # sublinear growth: 4x the releases far less than 4x the epsilon
    eps20, _, _ = rdp_epsilon(20, mech)
    assert eps20 < 4 * eps5
    # monotone in k
    last = 0.0
    for k in range(1, 30):
        e, _, _ = rdp_epsilon(k, mech)
        assert e >= last - 1e-12
        last = e


def test_rdp_additive_cap_reports_proven_delta():
    """When the additive bound is the tighter epsilon (large per-release
    epsilon), the report must be the pair basic composition actually
    proves: (k*eps, k*delta) — not k*eps at the smaller per-release
    delta."""
    mech = GaussianMechanism(epsilon=20.0, delta=1e-5)
    eps, delta, order = rdp_epsilon(2, mech)
    assert eps == pytest.approx(40.0)         # cap binds
    assert delta == pytest.approx(2e-5)       # proven additive delta
    assert order == 0.0                       # marks the additive bound
    acct = RDPAccountant()
    acct.record("a"), acct.record("a")
    assert acct.spent("a", mech) == (eps, delta)
    assert acct.report(mech)["a"]["delta"] == pytest.approx(2e-5)


def test_rdp_accountant_interface_and_report():
    mech = GaussianMechanism(epsilon=0.5, delta=1e-6)
    acct = RDPAccountant()
    assert isinstance(acct, PrivacyAccountant)   # drop-in behind the engine
    assert acct.spent("agent0", mech) == (0.0, 0.0)
    for _ in range(8):
        acct.record("agent0")
    acct.record("agent1")
    eps, delta = acct.spent("agent0", mech)
    assert 0 < eps <= 8 * 0.5 and delta == mech.delta
    rep = acct.report(mech)
    assert list(rep) == ["agent0", "agent1"]
    assert rep["agent0"]["releases"] == 8
    assert rep["agent0"]["epsilon"] <= rep["agent0"]["epsilon_additive"]
    assert rep["agent1"]["epsilon_additive"] == pytest.approx(0.5)


def test_make_accountant_registry():
    assert isinstance(make_accountant("rdp"), RDPAccountant)
    assert type(make_accountant("basic")) is PrivacyAccountant
    with pytest.raises(ValueError, match="unknown accountant"):
        make_accountant("zcdp")


def test_compiled_replay_tallies_rdp_accountant(blob):
    """The compiled backend's post-run ledger replay feeds the same
    accountant interface: an RDP accountant on a compiled run reports
    exactly what the eager run reports."""
    mech = GaussianMechanism(epsilon=2.0, clip=0.1)
    mk = lambda: MeteredTransport(codec=make_codec("int8"),  # noqa: E731
                                  privacy=mech,
                                  accountant=RDPAccountant())
    te_, tc = mk(), mk()
    _fit(blob, te_, "eager")
    _fit(blob, tc, "compiled")
    assert te_.accountant.releases == tc.accountant.releases
    assert te_.accountant.report(mech) == tc.accountant.report(mech)
    rep = te_.accountant.report(mech)
    for agent in rep:
        assert rep[agent]["epsilon"] <= rep[agent]["epsilon_additive"] + 1e-12


def test_accountant_without_privacy_rejected():
    with pytest.raises(ValueError, match="accountant"):
        MeteredTransport(accountant=RDPAccountant())


# ====================================================== budget-aware scheduler
def test_scheduler_orders_by_remaining_link_budget(blob):
    """Agents that spent less as senders go first; reward EMA breaks ties;
    agent id keeps it deterministic."""
    Xtr, ctr, _, _, k = blob
    spec = BudgetSpec(session_bits=10 ** 8, link_bits=10 ** 7)
    t = BudgetedTransport(spec)
    t.bind(endpoints_for([DecisionTree(depth=2) for _ in Xtr], Xtr))
    sched = BudgetAwareScheduler()
    sched.bind_transport(t)
    active = [0, 1, 2, 3]
    # fresh transport: no spend anywhere -> id order
    assert sched.round_order(0, active) == [0, 1, 2, 3]
    # agent0 spent the most, agent2 a little, others nothing
    t.link_spent = {("agent0", "agent1"): 5000, ("agent2", "agent3"): 100}
    assert sched.round_order(1, active) == [1, 3, 2, 0]
    # reward EMA breaks the tie between the two zero-spend agents
    sched.observe(3, 0.9)
    sched.observe(1, 0.2)
    assert sched.round_order(2, active) == [3, 1, 2, 0]
    # state_dict round-trips through the comm snapshot format
    s2 = BudgetAwareScheduler()
    s2.load_state_dict(sched.state_dict())
    s2.bind_transport(t)
    assert s2.round_order(2, active) == [3, 1, 2, 0]


def test_scheduler_run_deterministic_and_resumable(blob, tmp_path):
    """A budget-aware run is deterministic, and pause/resume replays the
    identical round orders (scheduler state + link spend both cross the
    boundary)."""
    Xtr, ctr, _, _, k = blob
    spec = BudgetSpec(session_bits=48_000)
    cfg = SessionConfig(num_classes=k, max_rounds=5,
                        stop_on_negative_alpha=False)

    def run_full():
        t = BudgetedTransport(spec)
        eng = Protocol(cfg, scheduler=BudgetAwareScheduler(), transport=t)
        s = eng.start(jax.random.key(9), endpoints_for(
            [DecisionTree(depth=3, num_thresholds=8) for _ in Xtr], Xtr),
            ctr)
        s.run()
        return s, t

    full_a, _ = run_full()
    full_b, t_b = run_full()
    assert full_a.state.history == full_b.state.history
    # the scheduler genuinely reordered at least one budget-starved round
    orders = [[c.agent for c in full_a.state.components if c.round == t]
              for t in range(full_a.state.round)]
    assert any(o != sorted(o) for o in orders if o), orders

    t = BudgetedTransport(spec)
    eng = Protocol(cfg, scheduler=BudgetAwareScheduler(), transport=t)
    part = eng.start(jax.random.key(9), endpoints_for(
        [DecisionTree(depth=3, num_thresholds=8) for _ in Xtr], Xtr), ctr)
    part.step()
    part.step()
    ckpt = str(tmp_path / "sched")
    part.checkpoint(ckpt)
    t2 = BudgetedTransport(spec)
    eng2 = Protocol(cfg, scheduler=BudgetAwareScheduler(), transport=t2)
    resumed = eng2.resume(ckpt, endpoints_for(
        [DecisionTree(depth=3, num_thresholds=8) for _ in Xtr], Xtr), ctr)
    resumed.run()
    assert resumed.state.history == full_a.state.history
    np.testing.assert_array_equal(np.asarray(resumed.state.w),
                                  np.asarray(full_a.state.w))


def test_scheduler_resume_on_plain_metered_transport(blob, tmp_path):
    """Regression: the scheduler's metered-ledger ordering signal is
    process-local, so it must cross the checkpoint through scheduler state
    — with unequal per-sender spend (dropout cohort), a resumed session
    must replay the uninterrupted run's round orders exactly."""
    Xtr, ctr, _, _, k = blob
    cfg = SessionConfig(num_classes=k, max_rounds=5,
                        stop_on_negative_alpha=False)

    def eps():
        return endpoints_for([DecisionTree(depth=3, num_thresholds=8)
                              for _ in Xtr], Xtr)

    def start(key=9):
        t = MeteredTransport()
        eng = Protocol(cfg, scheduler=BudgetAwareScheduler(), transport=t)
        return eng, eng.start(jax.random.key(key), eps(), ctr)

    # uninterrupted run with a dropout: sender spends diverge
    _, full = start()
    full.step()
    full.endpoints[1].active = False
    full.step()
    full.endpoints[1].active = True
    full.run()

    _, part = start()
    part.step()
    part.endpoints[1].active = False
    part.step()
    part.endpoints[1].active = True
    ckpt = str(tmp_path / "metered-sched")
    part.checkpoint(ckpt)
    assert part.state.comm["scheduler"].get("spent_by_src")  # signal saved
    t2 = MeteredTransport()
    eng2 = Protocol(cfg, scheduler=BudgetAwareScheduler(), transport=t2)
    resumed = eng2.resume(ckpt, eps(), ctr)
    resumed.run()
    assert resumed.state.history == full.state.history
    np.testing.assert_array_equal(np.asarray(resumed.state.w),
                                  np.asarray(full.state.w))


def test_scheduler_compiled_matches_eager_metered(blob):
    """PR 9: the budget-aware permutation lowers into the scan for
    homogeneous fleets — the compiled backend runs it bit-identically
    instead of rejecting (wire-bit spend signal, EMA tie-break).  The
    remaining RandomScheduler rejection pin lives in test_compiled."""
    Xtr, ctr, Xte, _, k = blob
    te_, tc = MeteredTransport(), MeteredTransport()
    eager = _fit(blob, te_, "eager", rounds=4,
                 scheduler=BudgetAwareScheduler())
    comp = _fit(blob, tc, "compiled", rounds=4,
                scheduler=BudgetAwareScheduler())
    _assert_identical(eager, comp, Xte)
    assert te_.log.entries == tc.log.entries


def test_scheduler_compiled_matches_eager_budgeted(blob):
    """The full acceptance pin: budget-aware + budgeted transport compiled
    == eager — components, params, history, predictions, ledger entries
    (rung stamps included), link spend, skips, exhaustion, and the serve
    round-trip; and budget pressure genuinely permutes the round order."""
    Xtr, ctr, Xte, cte, k = blob
    spec = lambda: BudgetSpec(session_bits=40_000, link_bits=9_000,
                              ladder=(QuantCodec(bits=8),
                                      QuantCodec(bits=4)))
    te_, tc = BudgetedTransport(spec()), BudgetedTransport(spec())
    cfg = SessionConfig(num_classes=k, max_rounds=4)
    mk = lambda: [LogisticRegression(steps=40) for _ in Xtr]
    pe = Protocol(cfg, scheduler=BudgetAwareScheduler(), transport=te_)
    pc = Protocol(cfg, scheduler=BudgetAwareScheduler(), transport=tc,
                  backend="compiled")
    fe = pe.fit(jax.random.key(11), endpoints_for(mk(), Xtr), ctr)
    fc = pc.fit(jax.random.key(11), endpoints_for(mk(), Xtr), ctr)
    _assert_identical(fe, fc, Xte)
    for ce, cc in zip(fe.components, fc.components):
        for le, lc in zip(jax.tree.leaves(ce.params),
                          jax.tree.leaves(cc.params)):
            np.testing.assert_array_equal(np.asarray(le), np.asarray(lc))
    assert te_.log.entries == tc.log.entries
    assert te_.link_spent == tc.link_spent
    assert te_.skipped == tc.skipped
    assert te_.exhausted == tc.exhausted
    # the chosen rung rides the ledger entries on both backends
    assert any("rung" in e for e in te_.log.entries)
    # budget pressure reordered at least one round away from id order
    per_round: dict[int, list[int]] = {}
    for c in fe.components:
        per_round.setdefault(c.round, []).append(c.agent)
    assert any(agents != sorted(agents) for agents in per_round.values())
    np.testing.assert_array_equal(np.asarray(pe.predict_distributed(Xte)),
                                  np.asarray(pc.predict_distributed(Xte)))


def test_scheduler_validation():
    with pytest.raises(ValueError, match="reward_smoothing"):
        BudgetAwareScheduler(reward_smoothing=1.0)

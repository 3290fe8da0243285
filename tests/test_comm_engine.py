"""Wire channel through both engine backends: eager and compiled must
produce the same trajectories (floats to the tolerance of two separately
compiled programs, tests/program_tolerance.py) AND identical encoded-bit
ledgers for every codec, the budget must degrade/defer identically, byte accounting must
stay consistent under agent dropout and late joins, and codec state must
checkpoint/resume exactly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from program_tolerance import assert_floats_close, assert_history_close

from repro.comm import (BudgetSpec, BudgetedTransport, GaussianMechanism,
                        make_codec)
from repro.comm.codecs import Fp16Codec, QuantCodec
from repro.control import AdaptiveController
from repro.core.compiled import compiled_session, plan_for, quant_sweep_run
from repro.core.engine import (AsyncStaleScheduler, MeteredTransport,
                               Protocol, SessionConfig, endpoints_for)
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


def _fit(blob, transport, backend, rounds=3, steps=40, **cfg_kw):
    Xtr, ctr, _, _, k = blob
    cfg = SessionConfig(num_classes=k, max_rounds=rounds, **cfg_kw)
    learners = [LogisticRegression(steps=steps) for _ in Xtr]
    fitted = Protocol(cfg, transport=transport, backend=backend).fit(
        jax.random.key(11), endpoints_for(learners, Xtr), ctr)
    return fitted


def _assert_identical(eager, comp, Xte):
    assert [(c.agent, c.round) for c in eager.components] == \
           [(c.agent, c.round) for c in comp.components]
    assert_floats_close([c.alpha for c in eager.components],
                        [c.alpha for c in comp.components])
    assert_history_close(eager.history, comp.history)
    np.testing.assert_array_equal(np.asarray(eager.predict(Xte)),
                                  np.asarray(comp.predict(Xte)))


# ================================================ eager == compiled, per codec
@pytest.mark.parametrize("name", CODECS)
def test_compiled_matches_eager_per_codec(blob, name):
    """The acceptance pin: identical trajectories AND identical encoded-bit
    ledgers, entry for entry, for every codec."""
    te_, tc = (MeteredTransport(codec=make_codec(name)) for _ in range(2))
    eager = _fit(blob, te_, "eager")
    comp = _fit(blob, tc, "compiled")
    _assert_identical(eager, comp, blob[2])
    assert te_.log.entries == tc.log.entries
    if name != "fp32":
        # the ledger books *encoded* bits, strictly below raw fp32
        n = blob[0][0].shape[0]
        ign = [e for e in te_.log.entries if e["kind"] == "ignorance"]
        assert ign and all(e["bits"] < 32 * n for e in ign)
        assert all(e["bits"] == make_codec(name).wire_bits(n) for e in ign)


def test_compiled_matches_eager_with_privacy(blob):
    mech = GaussianMechanism(epsilon=2.0, clip=0.1)
    te_, tc = (MeteredTransport(privacy=mech) for _ in range(2))
    eager = _fit(blob, te_, "eager")
    comp = _fit(blob, tc, "compiled")
    _assert_identical(eager, comp, blob[2])
    assert te_.log.entries == tc.log.entries
    assert te_.accountant.releases == tc.accountant.releases
    assert te_.accountant.report(mech) == tc.accountant.report(mech)


def test_compiled_matches_eager_with_privacy_and_codec(blob):
    mech = GaussianMechanism(epsilon=3.0, clip=0.1)
    te_, tc = (MeteredTransport(codec=make_codec("int8"), privacy=mech)
               for _ in range(2))
    eager = _fit(blob, te_, "eager")
    comp = _fit(blob, tc, "compiled")
    _assert_identical(eager, comp, blob[2])
    assert te_.log.entries == tc.log.entries


def test_compiled_matches_eager_under_budget(blob):
    """The degrade-then-skip ladder walk picks identical rungs hop for hop
    on both backends: same ledger, same per-link spend, same skip set, same
    exhaustion — and exhaustion stops the session early."""
    # n=168: setup books 32256 bits, then the greedy ladder walk ships
    # fp32, fp32, fp16, int8, int4, skip -> every rung exercised
    spec = BudgetSpec(session_bits=48_000)
    te_, tc = (BudgetedTransport(spec) for _ in range(2))
    eager = _fit(blob, te_, "eager", rounds=5,
                 stop_on_negative_alpha=False)
    comp = _fit(blob, tc, "compiled", rounds=5,
                stop_on_negative_alpha=False)
    _assert_identical(eager, comp, blob[2])
    assert te_.log.entries == tc.log.entries
    assert te_.link_spent == tc.link_spent
    assert sorted(te_.skipped) == sorted(tc.skipped)
    assert te_.exhausted and tc.exhausted
    assert eager.num_rounds < 5                    # budget ended the session
    # the ladder actually degraded: several distinct ignorance wire sizes
    ign_sizes = {e["bits"] for e in te_.log.entries
                 if e["kind"] == "ignorance"}
    assert len(ign_sizes) >= 2
    if spec.session_bits is not None:
        assert te_.total_bits <= spec.session_bits  # the cap held


def test_compiled_matches_eager_budget_plus_privacy(blob):
    """Budget and DP compose: the scan factors the (rung-independent) noise
    out of the ladder walk — still bit-identical to the eager fused
    channel."""
    spec = BudgetSpec(session_bits=48_000)
    mech = GaussianMechanism(epsilon=3.0, clip=0.1)
    te_, tc = (BudgetedTransport(spec, privacy=mech) for _ in range(2))
    eager = _fit(blob, te_, "eager", rounds=5, stop_on_negative_alpha=False)
    comp = _fit(blob, tc, "compiled", rounds=5,
                stop_on_negative_alpha=False)
    _assert_identical(eager, comp, blob[2])
    assert te_.log.entries == tc.log.entries
    assert te_.accountant.releases == tc.accountant.releases
    assert te_.link_spent == tc.link_spent
    assert te_.exhausted == tc.exhausted


REPLAY_RUNS = {
    # each hop priced at its own rung of the adaptive ladder
    "adaptive": (lambda: MeteredTransport(controller=AdaptiveController(
        ladder=(Fp16Codec(), QuantCodec(bits=4)), thresholds=(0.5,),
        beta=0.0)), dict(max_rounds=3)),
    # codec-less: priced through num_elements, so the payload's size counts
    "plain": (MeteredTransport, dict(max_rounds=3)),
    # the budget degrades the ladder, then skips hops
    "budget": (lambda: BudgetedTransport(BudgetSpec(session_bits=48_000)),
               dict(max_rounds=5, stop_on_negative_alpha=False)),
}


@pytest.mark.parametrize("name", sorted(REPLAY_RUNS))
def test_replay_books_the_scan_rows(blob, name):
    """The compiled replay books the eager ledger, and each endpoint's
    freshest IgnoranceMsg carries, bit for bit, row ``w_trace[t, j]`` of
    the last hop delivered to it, as its own device array."""
    Xtr, ctr, _, _, k = blob
    make, cfg_kw = REPLAY_RUNS[name]
    cfg = SessionConfig(num_classes=k, **cfg_kw)
    runs = {}
    for backend in ("eager", "compiled"):
        transport = make()
        eps = endpoints_for([LogisticRegression(steps=40) for _ in Xtr], Xtr)
        proto = Protocol(cfg, transport=transport, backend=backend)
        proto.fit(jax.random.key(11), eps, ctr)
        runs[backend] = proto, transport, eps
    proto, tc, eps = runs["compiled"]
    assert tc.log.entries == runs["eager"][1].log.entries
    res = proto.compiled_result
    booked = np.asarray(res.valid) & np.asarray(res.sent)
    last = {}
    for t, j in zip(*np.nonzero(booked)):     # row-major: chain order
        last[(j + 1) % len(eps)] = t, j
    assert len(last) == len(eps)
    for dst, (t, j) in last.items():
        w = eps[dst].latest("ignorance").w
        assert isinstance(w, jax.Array)
        assert np.asarray(w).tobytes() == \
            np.asarray(res.w_trace[t, j]).tobytes()
    if name == "budget":
        assert tc.skipped


ASYNC_REPLAY_RUNS = {
    # channel-less: the per-agent mid-merge pairs, raw
    "plain": MeteredTransport,
    # an int8 barrier: raw alphas, then one encoded release per round
    "int8": lambda: MeteredTransport(codec=make_codec("int8")),
}


@pytest.mark.parametrize("name", sorted(ASYNC_REPLAY_RUNS))
def test_async_replay_books_the_barrier_rows(blob, name):
    """The folded replay books the eager async-stale ledger, and the head's
    freshest IgnoranceMsg carries, bit for bit, the barrier release
    ``w_bar[t]`` (channel-less: the last agent's mid-merge snapshot
    ``w_trace[t, m]``) of the last round that shipped one, as its own
    device array."""
    Xtr, ctr, _, _, k = blob
    cfg = SessionConfig(num_classes=k, max_rounds=3)
    runs = {}
    for backend in ("eager", "compiled"):
        transport = ASYNC_REPLAY_RUNS[name]()
        eps = endpoints_for([LogisticRegression(steps=40) for _ in Xtr], Xtr)
        proto = Protocol(cfg, scheduler=AsyncStaleScheduler(),
                         transport=transport, backend=backend)
        proto.fit(jax.random.key(11), eps, ctr)
        runs[backend] = proto, transport, eps
    proto, tc, eps = runs["compiled"]
    assert tc.log.entries == runs["eager"][1].log.entries
    res = proto.compiled_result
    if name == "plain":
        m = len(eps) - 1                   # the agent whose hop ends at 0
        t = int(np.flatnonzero(np.asarray(res.valid)[:, m])[-1])
        want = res.w_trace[t, m]
    else:
        t = int(np.flatnonzero(np.asarray(res.sent))[-1])
        want = res.w_bar[t]
    msg = eps[0].latest("ignorance")
    assert msg.src == ("barrier" if name == "int8" else eps[m].name)
    assert isinstance(msg.w, jax.Array)
    assert np.asarray(msg.w).tobytes() == np.asarray(want).tobytes()


def test_budget_per_link_cap(blob):
    """A per-link cap starves each link independently of the session cap."""
    n = blob[0][0].shape[0]
    link_cap = Fp16Codec().wire_bits(n) + 32 + QuantCodec(bits=4
                                                          ).wire_bits(n) + 32
    spec = BudgetSpec(link_bits=link_cap,
                      ladder=(Fp16Codec(), QuantCodec(bits=4)))
    t = BudgetedTransport(spec)
    _fit(blob, t, "eager", rounds=4, stop_on_negative_alpha=False)
    assert not t.exhausted            # link caps never exhaust the session
    assert t.skipped                  # but every link eventually starves
    for spent in t.link_spent.values():
        assert spent <= link_cap


# =============================================== dropout / late-join accounting
def test_byte_accounting_under_dropout_and_late_join(blob):
    """Satellite pin: with churn mid-session and a codec active, the ledger
    stays internally consistent (per-entry sum == total_bits == by-kind sum)
    and every booked hop carries the codec's encoded size."""
    Xtr, ctr, _, _, k = blob
    codec = make_codec("int8")
    transport = MeteredTransport(codec=codec)
    cfg = SessionConfig(num_classes=k, max_rounds=4,
                        stop_on_negative_alpha=False)
    session = Protocol(cfg, transport=transport).start(
        jax.random.key(8),
        endpoints_for([DecisionTree(depth=3, num_thresholds=8)
                       for _ in Xtr[:2]], Xtr[:2]), ctr)
    session.step()
    session.endpoints[1].active = False                      # dropout
    session.step()
    session.add_endpoint(DecisionTree(depth=3, num_thresholds=8), Xtr[2])
    session.run()
    log = transport.log
    assert sum(e["bits"] for e in log.entries) == log.total_bits
    assert sum(transport.bits_by_kind().values()) == log.total_bits
    n = int(ctr.shape[0])
    hops = len(session.state.components)
    kinds = transport.bits_by_kind()
    assert kinds["ignorance"] == hops * codec.wire_bits(n)
    assert kinds["model_weight"] == hops * 32
    # collation setup: one (labels + sample_ids) pair per non-head agent,
    # including the late joiner
    assert kinds["labels"] == 2 * n * 32
    assert kinds["sample_ids"] == 2 * n * 32


# ================================================== checkpoint / stale / sweep
def test_checkpoint_resume_with_stateful_codec(blob, tmp_path):
    """Top-k error-feedback residuals ride SessionState: resuming mid-run
    reproduces the uninterrupted lossy-channel trajectory exactly."""
    Xtr, ctr, Xte, cte, k = blob
    cfg = SessionConfig(num_classes=k, max_rounds=4,
                        stop_on_negative_alpha=False)

    def make():
        return (Protocol(cfg, transport=MeteredTransport(
                    codec=make_codec("topk"))),
                endpoints_for([DecisionTree(depth=3, num_thresholds=8)
                               for _ in Xtr], Xtr))

    eng, eps = make()
    full = eng.start(jax.random.key(9), eps, ctr)
    full.run()
    assert full.state.codec_state                   # residuals accumulated

    eng, eps = make()
    part = eng.start(jax.random.key(9), eps, ctr)
    part.step()
    part.step()
    ckpt = str(tmp_path / "comm")
    part.checkpoint(ckpt)
    eng2, eps2 = make()
    resumed = eng2.resume(ckpt, eps2, ctr)
    assert resumed.state.codec_state.keys() == \
        part.state.codec_state.keys()
    resumed.run()
    assert resumed.state.history == full.state.history
    np.testing.assert_array_equal(np.asarray(resumed.state.w),
                                  np.asarray(full.state.w))
    np.testing.assert_array_equal(np.asarray(resumed.fitted().predict(Xte)),
                                  np.asarray(full.fitted().predict(Xte)))


def test_budget_and_privacy_survive_resume(blob, tmp_path):
    """Budget spend and DP release counts cross the pause/resume boundary:
    the resumed run continues under the same session cap (carryover bits)
    and the accountant keeps composing — identical trajectory, ledger
    split across the two processes, same final channel state as the
    uninterrupted run."""
    Xtr, ctr, _, _, k = blob
    spec = BudgetSpec(session_bits=48_000)
    mech = GaussianMechanism(epsilon=2.0, clip=0.1)
    cfg = SessionConfig(num_classes=k, max_rounds=5,
                        stop_on_negative_alpha=False)

    def make():
        t = BudgetedTransport(spec, privacy=mech)
        return Protocol(cfg, transport=t), t

    def eps():
        return endpoints_for([DecisionTree(depth=3, num_thresholds=8)
                              for _ in Xtr], Xtr)

    eng, t_full = make()
    full = eng.start(jax.random.key(9), eps(), ctr)
    full.run()
    assert t_full.exhausted                       # the cap actually bound

    eng, t_part = make()
    part = eng.start(jax.random.key(9), eps(), ctr)
    part.step()
    ckpt = str(tmp_path / "budget")
    part.checkpoint(ckpt)
    eng2, t_res = make()
    resumed = eng2.resume(ckpt, eps(), ctr)
    assert t_res.carryover_bits == t_part.log.total_bits
    resumed.run()

    assert resumed.state.history == full.state.history
    assert [(c.agent, c.round, c.alpha) for c in resumed.state.components] \
        == [(c.agent, c.round, c.alpha) for c in full.state.components]
    # the session cap held across both processes, not per process
    assert (t_part.log.total_bits + t_res.log.total_bits
            == t_full.log.total_bits)
    assert t_res.link_spent == t_full.link_spent
    assert t_res.exhausted == t_full.exhausted
    # epsilon composed across the boundary
    assert t_res.accountant.releases == t_full.accountant.releases


def test_stale_scheduler_rejects_controller(blob):
    """Per-barrier release narrowed the async rejection (PR 9): codec/DP/
    budget channels are legal on the stale path now — only adaptive
    controllers (a per-hop rung policy with no barrier analogue) stay
    rejected."""
    from repro.control import AdaptiveController
    Xtr, ctr, _, _, k = blob
    eng = Protocol(SessionConfig(num_classes=k, max_rounds=2),
                   scheduler=AsyncStaleScheduler(),
                   transport=MeteredTransport(controller=AdaptiveController()))
    with pytest.raises(ValueError, match="stale"):
        eng.start(jax.random.key(0),
                  endpoints_for([DecisionTree(depth=2) for _ in Xtr], Xtr),
                  ctr)
    # the previously-rejected codec channel now runs: one encoded barrier
    # release per executed round, booked from the synthetic "barrier" sender
    t = MeteredTransport(codec=make_codec("int8"))
    eng = Protocol(SessionConfig(num_classes=k, max_rounds=2),
                   scheduler=AsyncStaleScheduler(), transport=t)
    sess = eng.start(jax.random.key(0),
                     endpoints_for([DecisionTree(depth=2) for _ in Xtr],
                                   Xtr), ctr)
    sess.run()
    assert any(e["src"] == "barrier" and e["kind"] == "ignorance"
               for e in t.log.entries)


ASYNC_CHANNELS = {
    "plain": lambda: MeteredTransport(),
    "codec": lambda: MeteredTransport(codec=make_codec("int8")),
    "dp": lambda: MeteredTransport(
        privacy=GaussianMechanism(epsilon=2.0, clip=0.1)),
    "budget": lambda: BudgetedTransport(
        BudgetSpec(session_bits=40_000,
                   ladder=(QuantCodec(bits=8), QuantCodec(bits=4)))),
    # tight cap: the barrier walk runs out mid-session, skipping releases
    # (published score stays stale) and flipping exhausted
    "budget-tight": lambda: BudgetedTransport(
        BudgetSpec(session_bits=12_000,
                   ladder=(QuantCodec(bits=8), QuantCodec(bits=4)))),
}


@pytest.mark.parametrize("name", sorted(ASYNC_CHANNELS))
def test_async_compiled_matches_eager(blob, name):
    """PR 9 acceptance pin: channelized async fleets run on both backends
    with one ledger — per-barrier DP/codec/budget releases bit-identical to
    eager, including the skip path and the serve round-trip."""
    Xtr, ctr, Xte, _, k = blob
    te_, tc = ASYNC_CHANNELS[name](), ASYNC_CHANNELS[name]()
    cfg = SessionConfig(num_classes=k, max_rounds=4)
    learners = [LogisticRegression(steps=40) for _ in Xtr]
    pe = Protocol(cfg, scheduler=AsyncStaleScheduler(), transport=te_)
    pc = Protocol(cfg, scheduler=AsyncStaleScheduler(), transport=tc,
                  backend="compiled")
    fe = pe.fit(jax.random.key(11), endpoints_for(learners, Xtr), ctr)
    fc = pc.fit(jax.random.key(11), endpoints_for(learners, Xtr), ctr)
    _assert_identical(fe, fc, Xte)
    assert te_.log.entries == tc.log.entries
    if hasattr(te_, "link_spent"):
        assert te_.link_spent == tc.link_spent
        assert te_.skipped == tc.skipped
        assert te_.exhausted == tc.exhausted
    if te_.accountant is not None:
        assert te_.accountant.releases == tc.accountant.releases
    np.testing.assert_array_equal(np.asarray(pe.predict_distributed(Xte)),
                                  np.asarray(pc.predict_distributed(Xte)))


def test_quant_sweep_matches_per_config_runs(blob):
    """One vmapped program sweeping qmax == separate compiled runs with the
    statically-configured codecs — codec configs sweep inside one XLA
    program."""
    Xtr, ctr, _, _, k = blob
    learners = [LogisticRegression(steps=30) for _ in Xtr]
    plan8 = plan_for(learners, k, max_rounds=2, codec=make_codec("int8"))
    plan4 = plan_for(learners, k, max_rounds=2, codec=make_codec("int4"))
    key = jax.random.key(0)
    sweep = quant_sweep_run(plan8, jnp.stack([key, key]), Xtr, ctr,
                            jnp.asarray([127.0, 7.0]))
    for row, plan in ((0, plan8), (1, plan4)):
        single = compiled_session(plan, key, Xtr, ctr)
        assert_floats_close(sweep.alphas[row], single.alphas)
        assert_floats_close(sweep.w[row], single.w)

"""FedAvg and Assisted-Learning protocol variants on the ASCII wire.

The paper's baselines are usually reported from separate codebases with
separate (often absent) communication accounting.  Here they are
:class:`~repro.core.engine.ProtocolVariant`\\ s driven by the *same* session
loop, shipping their traffic through the *same* transports — codecs, bit
budgets (degrade-then-skip ladder), DP noise, and privacy accountants — so
the byte ledger and the epsilon ledger of "ASCII vs FedAvg vs AL at equal
budget" are directly comparable numbers, not apples and oranges:

  * :class:`FedAvgVariant` — one global model over a homogeneous roster.
    Each round every participating client warm-starts a local fit from the
    broadcast flat params ``g`` and uplinks its delta as a
    :class:`~repro.core.engine.GradientMsg` (DP-noised, codec-encoded,
    budget-walked via :meth:`Transport.ship`); the server (agent 0, whose
    own delta never crosses a wire) averages the deltas that actually
    arrived and broadcasts the new ``g`` raw.  Homogeneous rounds lower
    into a single ``lax.scan`` (:mod:`repro.scenarios.compiled`), pinned
    bit-identical to the eager loop.
  * :class:`AssistedLearningVariant` — residual-fitting rounds (Xian et al.
    2020's assisted learning, the paper's closest relative): the label
    one-hot starts as the residual ``R``; each agent in the ring fits a
    closed-form weighted ridge of ``R`` on its private feature block, keeps
    the fitted block as a boosting component, and ships the shrunk residual
    to the next agent as a :class:`~repro.core.engine.ResidualMsg`.  A
    budget-skipped hop leaves the receiver fitting yesterday's residual.
    Eager-only (the ring is data-dependent per round).

Both variants respect the engine's scenario knobs: churned agents skip the
round, non-IID shards mask the fit weights, and the deterministic
participation schedule replays identically across resume boundaries.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.core.engine import (ASCIIVariant, Component, GradientMsg,
                               ProtocolVariant, ResidualMsg,
                               SequentialScheduler, hop_prices)

#: fold_in tag deriving FedAvg's global-init key off the session key, so
#: model init never consumes PRNG state the per-round splits would see
#: (same discipline as the comm channel's COMM/SERVE tags).
FEDAVG_INIT_FOLD = 0x0FEDA6


# ============================================================= shared programs
# The pure expressions below are the single definitions both the eager round
# loop (via cached jits) and the compiled lax.scan lowering
# (repro.scenarios.compiled, traced inline) execute — the same trick as
# learners.base.jitted_fresh_fit, and for the same reason: sharing the
# composition is what keeps the two backends bit-identical.

@functools.lru_cache(maxsize=256)
def _param_template(core, shapes: tuple):
    """(flat param dim, unravel closure) for a core at feature ``shapes`` —
    the fixed flattening every GradientMsg payload uses."""
    params0 = core.init(jax.random.key(0), shapes)
    flat, unravel = ravel_pytree(params0)
    return int(flat.size), unravel


def fedavg_init_flat(core, shapes: tuple, key) -> jnp.ndarray:
    """The flat global init ``g0``: core init under the FEDAVG_INIT_FOLD
    tag, raveled."""
    params = core.init(jax.random.fold_in(key, FEDAVG_INIT_FOLD), shapes)
    return ravel_pytree(params)[0]


def fedavg_local_delta(core, shapes: tuple, g, key, X, onehot,
                       w) -> jnp.ndarray:
    """One client update: warm-start the core's WST fit from the broadcast
    flat params and return the flat delta (the GradientMsg payload)."""
    _, unravel = _param_template(core, shapes)
    local = core.fit(unravel(g), key, X, onehot, w)
    return ravel_pytree(local)[0] - g


def fedavg_combine(g, stack, mask, lr) -> jnp.ndarray:
    """The server's round merge: average the deltas that actually arrived
    (mask [M] bool over stack [M, d]) and step ``g`` by ``lr`` times it."""
    cnt = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    delta = jnp.sum(jnp.where(mask[:, None], stack, 0.0), axis=0)
    return g + jnp.asarray(lr, jnp.float32) * delta / cnt


@functools.lru_cache(maxsize=256)
def jitted_fedavg_init(core, shapes: tuple):
    return jax.jit(functools.partial(fedavg_init_flat, core, shapes))


@functools.lru_cache(maxsize=256)
def jitted_fedavg_fit(core, shapes: tuple):
    return jax.jit(functools.partial(fedavg_local_delta, core, shapes))


@functools.lru_cache(maxsize=64)
def jitted_fedavg_combine(lr: float):
    return jax.jit(lambda g, stack, mask: fedavg_combine(g, stack, mask, lr))


@functools.lru_cache(maxsize=256)
def jitted_fedavg_eval(core, shapes: tuple, num_agents: int):
    """Mean of the global model's logits over the agents' feature blocks —
    FedAvg's prediction rule here.  The roster is vertically partitioned
    (each block holds *different* columns of the holistic matrix), which a
    single averaged model cannot exploit; evaluating it on every block and
    averaging is the best a FedAvg deployment can do without moving raw
    features, and is exactly the handicap the ASCII comparison measures."""
    _, unravel = _param_template(core, shapes)

    def fn(g, Xs):
        params = unravel(g)
        total = core.logits(params, Xs[0])
        for X in Xs[1:]:
            total = total + core.logits(params, X)
        return total / float(num_agents)

    return jax.jit(fn)


def fedavg_train_acc(core, shapes: tuple, g, Xs, classes) -> float:
    """Round-history accuracy through the one shared eval program, so eager
    records and compiled-replay records carry identical floats."""
    logits = jitted_fedavg_eval(core, shapes, len(Xs))(g, tuple(Xs))
    preds = jnp.argmax(logits, axis=-1)
    return float(jnp.mean((preds == classes).astype(jnp.float32)))


def fedavg_fit_weights(classes, num_agents: int, scenario=None) -> jnp.ndarray:
    """[M, n] per-client fit-weight table: uniform rows, masked to the
    scenario's non-IID shard and renormalized (the same arithmetic as
    ``Session.fit_weight`` on a uniform base).  Computed once and passed to
    both backends as data, so they consume identical weights."""
    n = int(np.asarray(classes).shape[0])
    base = jnp.full((n,), 1.0 / n, jnp.float32)
    masks = (None if scenario is None
             else scenario.shard_weights(classes, num_agents))
    if masks is None:
        return jnp.stack([base] * num_agents)
    rows = []
    for m in range(num_agents):
        wm = base * masks[m]
        rows.append(wm / jnp.maximum(jnp.sum(wm), 1e-12))
    return jnp.stack(rows)


def _homogeneous_core(endpoints, num_classes: int):
    """FedAvg averages parameters, so the roster must be homogeneous: every
    agent a functional learner with the same core config and feature shape."""
    cores, shapes = [], []
    for ep in endpoints:
        if not getattr(ep.learner, "functional", False):
            raise ValueError(
                f"fedavg averages model parameters; endpoint {ep.name!r}'s "
                f"{type(ep.learner).__name__} has no functional LearnerCore "
                f"(trees are eager-only) — use logistic/mlp learners")
        cores.append(ep.learner.core(num_classes))
        shapes.append(tuple(ep.X.shape[1:]))
    if any(c != cores[0] for c in cores[1:]):
        raise ValueError(
            "fedavg requires one shared model: all agents must hold "
            f"identically-configured learners, got {sorted(set(map(repr, cores)))}")
    if any(s != shapes[0] for s in shapes[1:]):
        raise ValueError(
            "fedavg averages one global model over a fixed feature shape; "
            f"agents hold blocks of shapes {shapes} — pad or re-split the "
            "vertical partition into equal widths")
    return cores[0], shapes[0]


# ================================================================ FedAvg
@dataclass
class FittedFedAvg:
    """FedAvg's trained result: the flat global params, predicting by
    averaging the model's logits over the agents' feature blocks."""
    core: object
    shapes: tuple
    g: jnp.ndarray
    num_classes: int
    history: list = field(default_factory=list)

    def decision_scores(self, Xs) -> jnp.ndarray:
        return jitted_fedavg_eval(self.core, self.shapes,
                                  len(Xs))(self.g, tuple(Xs))

    def predict(self, Xs) -> jnp.ndarray:
        return jnp.argmax(self.decision_scores(Xs), axis=-1)

    @property
    def num_rounds(self) -> int:
        return len(self.history)


@dataclass
class FedAvgVariant(ProtocolVariant):
    """Federated averaging over the shared channel stack (McMahan et al.
    2017): uplink deltas through ``Transport.ship`` (codec + DP + budget
    ladder), raw model broadcast back, server-side delta averaging.

    ``server_lr`` scales the averaged delta (1.0 = plain FedAvg).  Agent 0
    is the server: its own delta joins the average without crossing a wire
    (no codec loss, no DP release, no budget charge — the standard trusted
    aggregator running its own local shard).
    """
    server_lr: float = 1.0

    name = "fedavg"

    def bind(self, session) -> None:
        core, shapes = _homogeneous_core(session.endpoints,
                                         session.cfg.num_classes)
        session.vctx["core"] = core
        session.vctx["shapes"] = shapes
        session.vctx["onehot"] = jax.nn.one_hot(session.classes,
                                                session.cfg.num_classes)
        session.vctx["fit_w"] = fedavg_fit_weights(session.classes,
                                                   len(session.endpoints),
                                                   session.scenario)
        if session.state.proto is None:
            # fresh session: bind runs before any per-round key splits, so
            # the fold off state.key here and off the fit key in the
            # compiled lowering see the identical key
            session.state.proto = {
                "g": jitted_fedavg_init(core, shapes)(session.state.key)}

    def run_round(self, session, order: list[int], rec: dict) -> bool:
        st = session.state
        eps = {ep.agent_id: ep for ep in session.endpoints}
        core = session.vctx["core"]
        shapes = session.vctx["shapes"]
        onehot, fit_w = session.vctx["onehot"], session.vctx["fit_w"]
        head = session.endpoints[0]
        num = len(session.endpoints)
        part = set(order)
        g = st.proto["g"]
        rows, mask = [], []
        for j in range(num):
            # one split per roster slot, participating or not: the key
            # stream is then a pure function of (round, slot), which is
            # what the compiled scan reproduces
            st.key, sub = jax.random.split(st.key)
            if j not in part:
                rows.append(None)
                mask.append(False)
                continue
            dflat = jitted_fedavg_fit(core, shapes)(
                g, sub, eps[j].X, onehot, fit_w[j])
            if j == 0:
                # the server's own delta joins the average off-wire
                rows.append(dflat)
                mask.append(True)
                continue
            d_hat = session.transport.ship(eps[j], head, dflat, GradientMsg,
                                           key=sub)
            rows.append(d_hat)
            mask.append(d_hat is not None)
        zero = jnp.zeros_like(g)
        stack = jnp.stack([r if r is not None else zero for r in rows])
        g = jitted_fedavg_combine(float(self.server_lr))(
            g, stack, jnp.asarray(mask))
        st.proto["g"] = g
        # raw fp32 broadcast of the new global model to every participating
        # client (the server's own params carry no DP obligation); priced at
        # num_elements x 32 by the ledger, counted against the session cap
        for m in order:
            if m == 0:
                continue
            session.transport.send(GradientMsg(head.name, eps[m].name, g))
        rec["train_acc"] = fedavg_train_acc(
            core, shapes, g, [ep.X for ep in session.endpoints],
            session.classes)
        return False

    def fitted(self, session) -> FittedFedAvg:
        return FittedFedAvg(session.vctx["core"], session.vctx["shapes"],
                            session.state.proto["g"],
                            session.cfg.num_classes, session.state.history)

    # ---- compiled lowering --------------------------------------------------
    def fit_compiled(self, protocol, key, endpoints, classes, validation):
        """One-program FedAvg: the homogeneous round lowers into a
        ``lax.scan`` over the participation mask
        (:mod:`repro.scenarios.compiled`), then the message ledger an eager
        run would have booked is replayed onto the live transport —
        byte-identical metering, same epsilon tally."""
        from repro.scenarios import compiled as scompiled
        cfg = protocol.cfg
        if validation is not None:
            raise ValueError("backend='compiled' does not support the CV "
                             "validation stop; use the eager backend")
        if not (isinstance(protocol.scheduler, SequentialScheduler)
                and not protocol.scheduler.stale):
            raise ValueError(
                f"fedavg's compiled lowering supports sequential scheduling "
                f"only, got {type(protocol.scheduler).__name__}")
        if not all(ep.active for ep in endpoints):
            raise ValueError("backend='compiled' assumes all endpoints "
                             "active for the whole run (scenario churn is "
                             "fine — it rides the participation mask)")
        core, shapes = _homogeneous_core(endpoints, cfg.num_classes)
        transport = protocol.transport
        scenario = protocol.scenario
        num = len(endpoints)
        mask = (np.ones((cfg.max_rounds, num), bool) if scenario is None
                else scenario.participation(cfg.max_rounds, num))
        fit_w = fedavg_fit_weights(classes, num, scenario)
        plan = scompiled.FedAvgPlan(
            core=core, num_classes=cfg.num_classes, num_agents=num,
            max_rounds=cfg.max_rounds, server_lr=float(self.server_lr),
            codec=transport.codec, privacy=transport.privacy,
            budget=getattr(transport, "budget", None))
        Xs = tuple(ep.X for ep in endpoints)
        tele = getattr(protocol, "telemetry", None)
        if tele is None:
            result = scompiled.fedavg_session(plan, key, Xs, classes,
                                              jnp.asarray(mask), fit_w)
        else:
            with tele.span("session", backend="compiled", variant=self.name,
                           agents=num):
                result = tele.fence(scompiled.fedavg_session(
                    plan, key, Xs, classes, jnp.asarray(mask), fit_w))
        self._replay(protocol, endpoints, classes, result, plan, mask)
        history = self._history(core, shapes, result, mask, Xs, classes,
                                scenario)
        protocol._compiled_ctx = None
        return FittedFedAvg(core, shapes, result.g, cfg.num_classes, history)

    @staticmethod
    def _history(core, shapes, result, mask, Xs, classes, scenario):
        """The round records an eager run writes, rebuilt from the scan's
        per-round global-param trace through the same eval program."""
        executed = np.asarray(result.executed)
        history = []
        for t in range(executed.shape[0]):
            if not executed[t]:
                continue
            rec: dict = {"round": t}
            parts = [int(j) for j in np.flatnonzero(mask[t])]
            if scenario is not None:
                rec["participants"] = parts
            if parts:
                rec["train_acc"] = fedavg_train_acc(
                    core, shapes, result.g_trace[t], Xs, classes)
            history.append(rec)
        return history

    @staticmethod
    def _replay(protocol, endpoints, classes, result, plan, mask) -> None:
        """Book the eager run's exact message ledger: collation setup, one
        GradientMsg uplink per sent (round, client) through
        ``Transport.book_hop`` at the rung the scan chose (a skipped one
        through ``skip_hop``), the raw broadcast per participating client —
        then the exhaustion flag."""
        transport = protocol.transport
        transport.bind(endpoints)
        names = [ep.name for ep in endpoints]
        transport.book_setup(names[0], names[1:], int(classes.shape[0]))
        d, _ = _param_template(plan.core, tuple(endpoints[0].X.shape[1:]))
        flat = np.zeros((d,), np.float32)  # ledger prices size, not values
        executed = np.asarray(result.executed)
        sent = np.asarray(result.sent)
        rungs = np.asarray(result.codec_idx)
        # prices per rung, once per replay (the scan ships a budget-less
        # uplink at rung 0: the plan's codec, or raw)
        prices = hop_prices(plan.ladder, (d,),
                            budgeted=plan.budget is not None)
        for t in range(executed.shape[0]):
            if not executed[t]:
                continue
            for j in range(1, len(names)):
                if not mask[t, j]:
                    continue
                if not sent[t, j]:
                    transport.skip_hop((names[j], names[0]))
                    continue
                wire, spend = prices[rungs[t, j]]
                transport.book_hop(GradientMsg(names[j], names[0], flat,
                                               wire_bits=wire), spend=spend)
            for j in range(1, len(names)):
                if mask[t, j]:
                    transport.send(GradientMsg(names[0], names[j], flat))
        if plan.budget is not None:
            transport.exhausted = bool(result.exhausted)


# ====================================================== Assisted Learning
@functools.lru_cache(maxsize=64)
def jitted_ridge(l2: float, lr: float):
    """One AL hop: closed-form weighted ridge of the running residual R on
    the agent's biased feature block, and the shrunk residual it ships.

        B = (Xb' W Xb + l2 I)^-1 Xb' W R,   R' = R - lr (Xb B)

    Cached per (l2, lr) so every hop of every session runs one program."""

    def fn(X, R, w):
        Xb = jnp.concatenate(
            [X, jnp.ones((X.shape[0], 1), X.dtype)], axis=1)
        Xw = Xb * w[:, None]
        A = Xw.T @ Xb + l2 * jnp.eye(Xb.shape[1], dtype=X.dtype)
        B = jnp.linalg.solve(A, Xw.T @ R)
        R_next = R - lr * (Xb @ B)
        return R_next, B

    return jax.jit(fn)


@dataclass
class FittedAL:
    """The AL boosting ensemble: sum of each component's lr-scaled ridge
    scores on its own feature block, argmaxed."""
    components: list
    num_classes: int
    history: list = field(default_factory=list)

    def decision_scores(self, Xs) -> jnp.ndarray:
        n = Xs[0].shape[0]
        total = jnp.zeros((n, self.num_classes), jnp.float32)
        for comp in self.components:
            X = Xs[comp.agent]
            Xb = jnp.concatenate(
                [X, jnp.ones((X.shape[0], 1), X.dtype)], axis=1)
            total = total + comp.alpha * (Xb @ comp.params)
        return total

    def predict(self, Xs) -> jnp.ndarray:
        return jnp.argmax(self.decision_scores(Xs), axis=-1)

    @property
    def num_rounds(self) -> int:
        return max((c.round for c in self.components), default=-1) + 1


@dataclass
class AssistedLearningVariant(ProtocolVariant):
    """Assisted Learning's residual-fitting rounds (Xian et al. 2020): the
    running [n, K] residual circulates the ring as a ResidualMsg, each agent
    L2-boosting it down with a private closed-form ridge.  ``lr`` is the
    boosting shrinkage, ``l2`` the per-hop ridge strength.  Eager-only: the
    data-dependent ring order has no fixed-shape lowering."""
    lr: float = 0.5
    l2: float = 1e-3

    name = "al"

    def bind(self, session) -> None:
        n = int(session.classes.shape[0])
        num = len(session.endpoints)
        masks = (None if session.scenario is None
                 else session.scenario.shard_weights(session.classes, num))
        session.vctx["fit_w"] = (jnp.ones((num, n), jnp.float32)
                                 if masks is None else masks)
        if session.state.proto is None:
            session.state.proto = {
                "R": jax.nn.one_hot(session.classes,
                                    session.cfg.num_classes)}

    def run_round(self, session, order: list[int], rec: dict) -> bool:
        st = session.state
        eps = {ep.agent_id: ep for ep in session.endpoints}
        fit_w = session.vctx["fit_w"]
        t = st.round
        R = st.proto["R"]
        for j, m in enumerate(order):
            # split per hop even though the ridge is deterministic: the
            # channel (DP noise, stochastic rounding) folds off this subkey
            st.key, sub = jax.random.split(st.key)
            R_next, B = jitted_ridge(float(self.l2), float(self.lr))(
                eps[m].X, R, fit_w[m])
            st.components.append(Component(m, t, float(self.lr), B))
            dst = eps[order[(j + 1) % len(order)]]
            shipped = session.transport.ship(eps[m], dst, R_next,
                                             ResidualMsg, key=sub)
            # budget skip: the next agent keeps fitting the stale residual
            R = R if shipped is None else shipped
        st.proto["R"] = R
        rec["resid_norm"] = float(jnp.linalg.norm(R))
        rec["train_acc"] = float(jnp.mean(
            (self.fitted(session).predict([ep.X for ep in session.endpoints])
             == session.classes).astype(jnp.float32)))
        return False

    def fitted(self, session) -> FittedAL:
        return FittedAL(session.state.components, session.cfg.num_classes,
                        session.state.history)


# ===================================================================== registry
PROTOCOLS = {
    "ascii": ASCIIVariant,
    "fedavg": FedAvgVariant,
    "al": AssistedLearningVariant,
}


def make_variant(name: str, **kw) -> ProtocolVariant:
    """Protocol-variant registry lookup for CLI / benchmark names."""
    if name not in PROTOCOLS:
        raise ValueError(
            f"unknown protocol {name!r}; expected {sorted(PROTOCOLS)}")
    return PROTOCOLS[name](**kw)

"""Agent-session engine for the ASCII interchange protocol.

The paper's contribution is an *interchange protocol*: agents passing
ignorance scores and model weights around a ring while all raw features stay
private.  This module is the one place that protocol is implemented; the
variant-branched host loop, the byte-metered simulator, and the mesh-native
ring are now three pluggable pieces of a single engine:

  * ``AgentEndpoint`` — one agent: a private :class:`~repro.learners.base.
    Learner` plus its local feature block, addressable by name, with a typed
    message inbox.  Endpoints can drop out mid-session (``active = False``)
    or join late (:meth:`Session.add_endpoint`).
  * Typed messages — :class:`IgnoranceMsg`, :class:`ModelWeightMsg`,
    :class:`ScoreBlockMsg` (plus the one-time :class:`LabelsMsg` /
    :class:`SampleIdsMsg` collation setup).  Every message knows its size so
    transports can meter it.
  * ``Transport`` — how messages move and where the interchange update
    executes.  :class:`InProcessTransport` is the plain host path,
    :class:`MeteredTransport` additionally books every bit into a
    :class:`~repro.core.transport.TransportLog` (Fig. 4 accounting), and
    :class:`MeshRingTransport` runs the fused update on-device via the
    Pallas kernel / ``core.collectives`` ring.
  * ``Scheduler`` — the round order, replacing the old ``variant`` string
    branching: :class:`SequentialScheduler` (paper chain),
    :class:`RandomScheduler` (ASCII-Random), :class:`AsyncStaleScheduler`
    (beyond-paper stale-read parallel rounds).
  * ``SessionState`` — the explicit protocol state (ignorance vector, PRNG
    key, fitted components, round history, stop bookkeeping).  It is a plain
    tree of arrays + JSON-able metadata, checkpointable mid-run through
    ``train/checkpoint.py`` and resumable to bit-identical trajectories.
  * ``Protocol`` — the engine: wires a config, a scheduler, and a transport,
    and drives endpoints round by round (``start`` / ``step`` / ``run`` /
    ``resume``).

``repro.core.protocol.fit`` is a thin back-compat wrapper over this engine;
its ``variant`` strings map onto schedulers via :func:`variant_setup`.

Quickstart::

    endpoints = [AgentEndpoint(0, DecisionTree(depth=3), X_a),
                 AgentEndpoint(1, DecisionTree(depth=3), X_b)]
    engine = Protocol(SessionConfig(num_classes=10, max_rounds=6),
                      scheduler=SequentialScheduler(),
                      transport=MeteredTransport())
    session = engine.start(jax.random.key(0), endpoints, classes)
    session.run()
    preds = session.fitted().predict([Xte_a, Xte_b])
"""
from __future__ import annotations

import abc
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.codecs import (MODEL_WEIGHT_BITS, jitted_channel, rung_bits,
                               serve_key)
from repro.comm.privacy import PrivacyAccountant
from repro.core import compiled, scores
from repro.core.encoding import encode_labels
from repro.core.transport import TransportLog
from repro.learners.base import Learner
from repro.telemetry.live import installed as live_installed

PyTree = Any

VARIANTS = ("ascii", "simple", "random", "async")


# ===================================================================== messages
@dataclass(frozen=True)
class Message:
    """Base class for everything that crosses an agent boundary.

    ``num_elements``/``bits_per_element`` expose the wire size so transports
    can meter without understanding the payload; messages that went through
    a wire codec (repro.comm) carry their *encoded* size in ``wire_bits``
    instead, and ``bits`` prefers it — the ledger prices what actually
    crossed the wire, not the decoded payload.
    """
    src: str
    dst: str

    kind = "message"
    bits_per_element = 32
    # plain class attribute, NOT a dataclass field: subclasses that carry an
    # encoded payload redeclare it as a trailing field; adding it as a field
    # here would splice it before subclass fields and break positional
    # construction
    wire_bits = None

    @property
    def num_elements(self) -> int:
        return 0

    @property
    def bits(self) -> int:
        if self.wire_bits is not None:
            return self.wire_bits
        return self.num_elements * self.bits_per_element


@dataclass(frozen=True)
class IgnoranceMsg(Message):
    """The length-n ignorance score shipped on every interchange hop.

    ``w`` is the *decoded* payload (what the receiver computes with);
    ``wire_bits`` the encoded size when a codec was active."""
    w: jnp.ndarray = None
    wire_bits: int | None = None

    kind = "ignorance"

    @property
    def num_elements(self) -> int:
        return int(np.size(self.w))


@dataclass(frozen=True)
class ModelWeightMsg(Message):
    """The scalar model weight alpha accompanying each hop."""
    alpha: float = 0.0

    kind = "model_weight"

    @property
    def num_elements(self) -> int:
        return 1


@dataclass(frozen=True)
class ScoreBlockMsg(Message):
    """An [n, K] coded score block: an agent's alpha-weighted votes for the
    collated samples — the O(nK) prediction-time traffic of Algorithm 1
    line 12 (raw features never move).

    ``scores`` is the *decoded* payload the head agent sums; ``wire_bits``
    the encoded size when the serve channel ran a codec."""
    scores: jnp.ndarray = None
    wire_bits: int | None = None

    kind = "score_block"

    @property
    def num_elements(self) -> int:
        return int(np.size(self.scores))


@dataclass(frozen=True)
class GradientMsg(Message):
    """A FedAvg-style flattened model delta (client -> server uplink, or the
    server's raw broadcast of the new global model).

    ``delta`` is the *decoded* payload (what the server averages);
    ``wire_bits`` the encoded size when the channel ran a codec."""
    delta: jnp.ndarray = None
    wire_bits: int | None = None

    kind = "gradient"

    @property
    def num_elements(self) -> int:
        return int(np.size(self.delta))


@dataclass(frozen=True)
class ResidualMsg(Message):
    """An Assisted-Learning [n, K] residual block passed along the ring:
    agent m ships what remains of the label signal after its local fit.

    ``residual`` is the *decoded* payload the next agent fits against;
    ``wire_bits`` the encoded size when the channel ran a codec."""
    residual: jnp.ndarray = None
    wire_bits: int | None = None

    kind = "residual"

    @property
    def num_elements(self) -> int:
        return int(np.size(self.residual))


@dataclass(frozen=True)
class LabelsMsg(Message):
    """One-time setup: the head agent shares the numeric labels."""
    num_samples: int = 0

    kind = "labels"

    @property
    def num_elements(self) -> int:
        return self.num_samples


@dataclass(frozen=True)
class SampleIdsMsg(Message):
    """One-time setup: collation IDs aligning rows across agents."""
    num_samples: int = 0

    kind = "sample_ids"

    @property
    def num_elements(self) -> int:
        return self.num_samples


def setup_bits(num_agents: int, n: int) -> int:
    """Bits the collation setup books (:meth:`Transport.book_setup`): one
    LabelsMsg and one SampleIdsMsg of ``n`` rows per non-head agent."""
    return (num_agents - 1) * (LabelsMsg("", "", n).bits
                               + SampleIdsMsg("", "", n).bits)


def hop_prices(ladder, shape, *, budgeted: bool, extra: int = 0) -> tuple:
    """Per rung of ``ladder``, what a replay books a shipped hop of
    ``shape`` with: its payload message's ``wire_bits`` (the
    :func:`~repro.comm.codecs.rung_bits` price, or None on a raw rung —
    priced through ``num_elements``, as the eager channel leaves it) and,
    when ``budgeted``, the ``spend`` for :meth:`Transport.book_hop`: the
    rung and its cost, the payload plus ``extra`` (the alpha message's
    bits on an interchange hop)."""
    return tuple((None if c is None else bits,
                  (i, bits + extra) if budgeted else None)
                 for i, (c, bits) in enumerate(zip(ladder,
                                                   rung_bits(ladder, shape))))


# =================================================================== transports
class Transport(abc.ABC):
    """How messages move between endpoints and where interchange math runs.

    ``bind`` gives the transport the endpoint registry; ``send`` routes a
    message into the destination inbox (subclasses hook ``_on_send`` for
    accounting); ``interchange`` executes one hop of eqs. (10)/(12): update
    the ignorance score with ``src``'s reward and alpha, then deliver it to
    ``dst``.

    Every transport optionally carries a wire channel (repro.comm): a
    ``codec`` (the outgoing score is encoded, priced at its *encoded* size,
    and the protocol continues from the decoded array — a genuinely lossy
    wire) and/or a ``privacy`` Gaussian mechanism (DP noise on the outgoing
    vector, per-agent epsilon tallied in ``accountant``).
    """

    def __init__(self, codec=None, privacy=None, serve_codec=None,
                 controller=None, accountant=None,
                 serve_controller=None) -> None:
        self._endpoints: dict[str, "AgentEndpoint"] = {}
        if controller is not None:
            if codec is not None:
                raise ValueError(
                    "an adaptive controller drives codec choice through its "
                    "ladder; drop codec= (or pass the codec as a one-rung "
                    "controller ladder)")
            codec = controller.ladder[0]
        if serve_controller is not None and serve_codec is not None:
            raise ValueError(
                "a serve controller picks the serve rung per score block "
                "through its ladder; drop serve_codec=")
        self.codec = codec
        self.privacy = privacy
        # serve-path codec override: prediction-time ScoreBlockMsg traffic
        # encodes with this codec when set, else with ``codec`` (so one
        # codec serves both payload types by default)
        self.serve_codec = serve_codec
        # per-hop codec-rung policy (repro.control.adaptive) + its EMA state
        self.controller = controller
        self.ctrl_state = (None if controller is None
                           else controller.init_state())
        # per-block serve rung policy (repro.control.adaptive
        # .ServeController): stateless — each block's uncertainty statistic
        # picks its own codec rung, no EMA to checkpoint
        self.serve_controller = serve_controller
        if accountant is not None and privacy is None:
            raise ValueError("an accountant without a privacy mechanism has "
                             "nothing to account; pass privacy= too")
        self.accountant = None
        if privacy is not None:
            if accountant is None:
                accountant = PrivacyAccountant()
            self.accountant = accountant

    @property
    def has_channel(self) -> bool:
        return self.codec is not None or self.privacy is not None

    @property
    def effective_serve_codec(self):
        if self.serve_codec is not None:
            return self.serve_codec
        if self.serve_controller is not None:
            # the serve controller picks the rung per block inside
            # serve_block; there is no single static serve codec
            return None
        if self.controller is not None:
            # the controller is a training-interchange policy (its entropy
            # statistic is defined on the ignorance vector, not on score
            # blocks) and mutates ``codec`` hop by hop — serve traffic ships
            # raw unless an explicit serve_codec is set, identically on both
            # backends (SessionPlan.serve_ladder applies the same rule)
            return None
        return self.codec

    @property
    def has_serve_channel(self) -> bool:
        return (self.effective_serve_codec is not None
                or self.serve_controller is not None
                or self.privacy is not None)

    def bind(self, endpoints: Sequence["AgentEndpoint"]) -> None:
        self._endpoints = {ep.name: ep for ep in endpoints}

    def send(self, msg: Message, rung: int | None = None) -> None:
        """Deliver ``msg``; ``rung`` is the budget-ladder rung that priced
        it (:meth:`book_hop` passes it), stamped on its ledger entry."""
        self._on_send(msg, rung)
        ep = self._endpoints.get(msg.dst)
        if ep is not None:
            ep.receive(msg)

    def _on_send(self, msg: Message, rung: int | None) -> None:
        pass                                    # metering hook

    # ---- the wire ledger ----------------------------------------------------
    # Every hop either backend books goes through these three methods: the
    # eager hop methods below call them from their tails, and the compiled
    # backend's replays (Protocol._replay_traffic / _replay_serve, the
    # FedAvg lowering's _replay) call them once per hop of the result arrays,
    # priced by repro.comm.rung_bits.

    def book_hop(self, msg: Message, *, spend=None, alpha=None) -> None:
        """Book one shipped hop: ``msg`` is its payload message, priced
        (``wire_bits`` set, or None for a raw payload).  In order: the
        budget spend ``(rung, cost)`` on a budgeted transport; the payload,
        its ledger entry stamped with that rung; the ``ModelWeightMsg`` when
        ``alpha`` is given; the DP release of the sender when privacy is
        on."""
        rung = None
        if spend is not None:
            rung, cost = spend
            self.record_spend((msg.src, msg.dst), cost, rung)
        self.send(msg, rung)
        if alpha is not None:
            self.send(ModelWeightMsg(msg.src, msg.dst, float(alpha)))
        if self.privacy is not None:
            self.accountant.record(msg.src)

    def skip_hop(self, link) -> None:
        """Book one hop a budget dropped on ``link`` = (src, dst); only a
        budgeted transport drops hops, so this books nothing here."""

    def book_setup(self, head: str, dsts: Sequence[str], n: int) -> None:
        """Collation setup: the head agent shares the labels and sample IDs
        of ``n`` rows with each of ``dsts`` (metered under Fig. 4)."""
        for dst in dsts:
            self.send(LabelsMsg(head, dst, n))
            self.send(SampleIdsMsg(head, dst, n))

    def _execute_update(self, w: jnp.ndarray, r: jnp.ndarray, alpha,
                        reweight: Callable, standard: bool) -> jnp.ndarray:
        return reweight(w, r, alpha)

    def _controller_rung(self, w_prev: jnp.ndarray,
                         w_out: jnp.ndarray) -> int:
        """One adaptive-controller step: observe the hop (receiver's stale
        vector, outgoing vector), advance the EMA state, return the chosen
        ladder rung.  Runs the cached-jit controller program (the exact
        computation the compiled session scan embeds)."""
        from repro.control.adaptive import jitted_controller
        rung, self.ctrl_state = jitted_controller(self.controller)(
            w_prev, w_out, self.ctrl_state)
        return int(rung)

    def _choose_codec(self, w_prev: jnp.ndarray, w_out: jnp.ndarray) -> None:
        """Per-hop codec selection hook: with an adaptive controller the
        outgoing codec is the controller's rung for this hop.  Budgeted
        transports override this as a no-op — their ladder walk consumes
        the controller rung as a floor instead."""
        if self.controller is not None:
            self.codec = self.controller.ladder[
                self._controller_rung(w_prev, w_out)]

    def interchange(self, src: "AgentEndpoint", dst: "AgentEndpoint",
                    w: jnp.ndarray, r: jnp.ndarray, alpha,
                    reweight: Callable, standard: bool = True, *,
                    key=None, codec_state=None, _w_out=None, _spend=None):
        """One hop: w' = reweight(w, r, alpha), through the wire channel
        (DP noise, then codec encode/decode), shipped src -> dst.

        Returns ``(w_received, codec_state)`` — what the receiver decodes
        (the trajectory continues from it) plus the updated per-link codec
        state (error-feedback residual; None for stateless codecs).
        ``key`` is the hop's per-fit subkey; the channel folds its own keys
        from it, so attaching a channel never shifts the fit PRNG stream.
        ``_w_out`` lets a subclass that already ran the update (the
        budgeted transport's controller floor) pass it through instead of
        recomputing it; ``_spend`` is the ``(rung, cost)`` a budgeted
        transport's ladder walk chose, booked by :meth:`book_hop`.
        """
        w_next = (_w_out if _w_out is not None
                  else self._execute_update(w, r, alpha, reweight, standard))
        self._choose_codec(w, w_next)
        wire_bits = None
        if self.has_channel:
            if (self.codec is not None and self.codec.stateful
                    and codec_state is None):
                codec_state = self.codec.init_state(int(w.shape[0]))
            w_next, codec_state = jitted_channel(self.codec, self.privacy)(
                w_next, key, codec_state)
            if self.codec is not None:
                wire_bits = self.codec.wire_bits(int(w.shape[0]))
        self.book_hop(IgnoranceMsg(src.name, dst.name, w_next,
                                   wire_bits=wire_bits),
                      spend=_spend, alpha=alpha)
        return w_next, codec_state

    def serve_block(self, src: "AgentEndpoint", dst: "AgentEndpoint",
                    block: jnp.ndarray, *, key=None, _spend=None):
        """One prediction-time hop: ship ``src``'s [n, K] score block to
        ``dst`` (the head agent) through the serve channel — DP noise, then
        codec encode/decode — priced at its *encoded* size.

        Returns the decoded block the head agent sums (the serve-path
        analogue of :meth:`interchange`'s decoded score), or ``None`` when a
        budgeted transport drops the block (see
        :class:`repro.comm.budget.BudgetedTransport`).  ``key`` is the
        per-block serve subkey; stateful codecs run with a fresh residual —
        serve calls are independent, there is no next hop to defer mass to.
        """
        codec = self.effective_serve_codec
        if self.serve_controller is not None and codec is None:
            # per-block rung policy: the controller reads the raw outgoing
            # block (pre-noise) through the cached-jit program the compiled
            # serve step embeds, so both backends pick identical rungs
            from repro.control.adaptive import jitted_serve_controller
            rung = int(jitted_serve_controller(self.serve_controller)(block))
            codec = self.serve_controller.ladder[rung]
        wire_bits = None
        if codec is not None or self.privacy is not None:
            block, _ = jitted_channel(codec, self.privacy)(block, key, None)
            if codec is not None:
                wire_bits = int(codec.wire_bits(tuple(block.shape)))
        self.book_hop(ScoreBlockMsg(src.name, dst.name, block,
                                    wire_bits=wire_bits), spend=_spend)
        return block

    def ship(self, src: "AgentEndpoint", dst: "AgentEndpoint",
             payload: jnp.ndarray, wrap, *, key=None, _spend=None):
        """One generic protocol-variant hop: ship ``payload`` (a FedAvg
        model delta, an Assisted-Learning residual block, ...) src -> dst
        through the wire channel — DP noise, then codec encode/decode —
        priced at its *encoded* size and wrapped in the ``wrap`` message
        type (:class:`GradientMsg` / :class:`ResidualMsg`).

        Returns the decoded payload the receiver computes with (the
        protocol continues from it — a genuinely lossy wire), or ``None``
        when a budgeted transport drops the hop (the receiver keeps its
        stale state, exactly like a skipped interchange hop).  ``key`` is
        the hop's per-fit subkey; the channel folds its own keys from it.
        Stateful (error-feedback) codecs run with a fresh residual per hop,
        like serve blocks — variant traffic has no per-link residual state.
        """
        wire_bits = None
        if self.has_channel:
            payload, _ = jitted_channel(self.codec, self.privacy)(
                payload, key, None)
            if self.codec is not None:
                wire_bits = int(self.codec.wire_bits(tuple(payload.shape)))
        self.book_hop(wrap(src.name, dst.name, payload, wire_bits=wire_bits),
                      spend=_spend)
        return payload

    def barrier_release(self, head: "AgentEndpoint", w_bar: jnp.ndarray, *,
                        key=None, codec_state=None, _spend=None):
        """One asynchronous-barrier release: the merged, renormalized score
        crosses the wire channel *once per round* — DP noise, then codec
        encode/decode, priced at its encoded size — published to the round
        head as a single IgnoranceMsg from the synthetic ``"barrier"``
        sender (the merge itself has no single agent source, and per-agent
        alphas already crossed raw).

        Returns ``(w_released, codec_state)``; a budgeted transport may
        instead skip the release (``(None, codec_state)``) when the session
        budget cannot afford even the cheapest rung, leaving the published
        score stale for one more round.  ``key`` is the per-barrier subkey
        (split *after* the round's fit splits, so attaching a channel never
        shifts the fit PRNG stream); ``codec_state`` is the barrier link's
        error-feedback residual for stateful codecs.
        """
        if (self.codec is not None and self.codec.stateful
                and codec_state is None):
            codec_state = self.codec.init_state(int(w_bar.shape[0]))
        w_rel, codec_state = jitted_channel(self.codec, self.privacy)(
            w_bar, key, codec_state)
        wire_bits = (self.codec.wire_bits(int(w_bar.shape[0]))
                     if self.codec is not None else None)
        self.book_hop(IgnoranceMsg("barrier", head.name, w_rel,
                                   wire_bits=wire_bits), spend=_spend)
        return w_rel, codec_state


class InProcessTransport(Transport):
    """Direct in-memory delivery; the plain single-host path."""


class MeteredTransport(Transport):
    """In-process delivery that books every bit into a
    :class:`~repro.core.transport.TransportLog` — the byte-accounted
    simulator behind the Fig. 4 transmission-cost benchmark.  With a codec
    attached the ledger books *encoded* bits."""

    def __init__(self, log: TransportLog | None = None, codec=None,
                 privacy=None, serve_codec=None, controller=None,
                 accountant=None, serve_controller=None) -> None:
        super().__init__(codec=codec, privacy=privacy,
                         serve_codec=serve_codec, controller=controller,
                         accountant=accountant,
                         serve_controller=serve_controller)
        self.log = log if log is not None else TransportLog()

    def _on_send(self, msg: Message, rung: int | None) -> None:
        # the budget rung rides the ledger entry, so a registry attached
        # *after* the traffic can still backfill hops_by_rung_total
        self.log.send_bits(msg.src, msg.dst, msg.kind, msg.bits, rung=rung)

    @property
    def total_bits(self) -> int:
        return self.log.total_bits

    def bits_by_kind(self) -> dict:
        return self.log.bits_by_kind()


class MeshRingTransport(Transport):
    """Device-resident interchange.

    The per-hop ignorance update runs the fused Pallas kernel
    (``kernels.ops.ignorance_update``); given a mesh with an ``agent`` axis,
    :meth:`ring_step` executes a whole round of hops as one
    ``shard_map``-ed neighbour ``ppermute`` via ``core.collectives`` — one
    ICI hop of n/|data| floats per device, zero resharding.

    The beyond-paper ``exact_reweight`` surrogate has no fused kernel; those
    hops fall back to the host formula.
    """

    def __init__(self, mesh=None, *, agent_axis: str = "agent",
                 data_axis: str = "data",
                 interpret: bool | None = None, codec=None,
                 privacy=None, serve_codec=None, controller=None,
                 accountant=None, serve_controller=None) -> None:
        super().__init__(codec=codec, privacy=privacy,
                         serve_codec=serve_codec, controller=controller,
                         accountant=accountant,
                         serve_controller=serve_controller)
        self.mesh = mesh
        self.agent_axis = agent_axis
        self.data_axis = data_axis
        self.interpret = interpret
        self._ring = None

    def _execute_update(self, w, r, alpha, reweight, standard):
        if not standard:
            return reweight(w, r, alpha)
        from repro.kernels import ops
        return ops.ignorance_update(w, r, jnp.asarray(alpha, w.dtype),
                                    interpret=self.interpret)

    def ring_step(self, w_stack: jnp.ndarray, r_stack: jnp.ndarray,
                  alphas: jnp.ndarray) -> jnp.ndarray:
        """All-lanes ring hop on the mesh: agent m+1 receives agent m's
        updated score.  Shapes [M, n], [M, n], [M]."""
        if self.mesh is None:
            raise ValueError("ring_step needs a mesh with an agent axis")
        if self._ring is None:
            from repro.core.collectives import make_ring_interchange
            self._ring = make_ring_interchange(
                self.mesh, agent_axis=self.agent_axis,
                data_axis=self.data_axis)
        return self._ring(w_stack, r_stack, alphas)


# =================================================================== schedulers
class Scheduler(abc.ABC):
    """Round-order policy: which active agents act, in what order.

    ``stale`` selects the asynchronous execution model (all agents read the
    same round-t ignorance score; updates merge at the round barrier) instead
    of the sequential chain.
    """

    stale = False

    def reset(self) -> None:
        """Called at session start; clears any per-run RNG state."""

    def bind_transport(self, transport: "Transport") -> None:
        """Budget-introspection hook: schedulers that order agents by live
        channel state (repro.control.scheduler) receive the transport here;
        stateless schedulers ignore it."""

    def observe(self, agent_id: int, acc: float) -> None:
        """Reward-observation hook: the session reports each agent's
        weighted accuracy after its fit, for schedulers that bias order by
        expected reward; stateless schedulers ignore it."""

    @abc.abstractmethod
    def round_order(self, round_idx: int, active: list[int]) -> list[int]:
        """Agent ids (a permutation of ``active``) for round ``round_idx``."""

    def skip_to(self, order_sizes: Sequence[int]) -> None:
        """Fast-forward RNG state past already-executed rounds (resume).
        ``order_sizes`` holds each completed round's active-agent count, so
        the replayed RNG draws match even if agents dropped out or joined
        mid-session."""
        for t, size in enumerate(order_sizes):
            self.round_order(t, list(range(size)))


class SequentialScheduler(Scheduler):
    """The paper's chain 1 -> 2 -> ... -> M, every round."""

    def round_order(self, round_idx: int, active: list[int]) -> list[int]:
        return list(active)


class RandomScheduler(Scheduler):
    """ASCII-Random: a fresh random agent order each round."""

    def __init__(self, seed: int = 0) -> None:
        self.seed = seed
        self._rng = np.random.default_rng(seed)

    def reset(self) -> None:
        self._rng = np.random.default_rng(self.seed)

    def round_order(self, round_idx: int, active: list[int]) -> list[int]:
        perm = self._rng.permutation(len(active))
        return [active[i] for i in perm]


class AsyncStaleScheduler(SequentialScheduler):
    """Beyond-paper asynchronous rounds (the paper's open problem): all
    agents train concurrently against the same stale round-t score; positive
    updates merge multiplicatively (damped by 1/M) at the round barrier, so
    the M WST fits parallelize."""

    stale = True


# ======================================================================= agents
@dataclass
class AgentEndpoint:
    """One protocol participant: a private learner plus its local feature
    block.  Raw features never leave the endpoint; only messages do.

    ``active`` gates participation round by round — flip it off to simulate
    dropout mid-session, or append a fresh endpoint to a live session
    (:meth:`Session.add_endpoint`) for a late join.
    """

    agent_id: int
    learner: Learner
    X: jnp.ndarray
    name: str = ""
    active: bool = True
    inbox: list[Message] = field(default_factory=list)

    def __post_init__(self) -> None:
        if not self.name:
            self.name = f"agent{self.agent_id}"

    def receive(self, msg: Message) -> None:
        # keep only the freshest message per kind: the protocol never reads
        # stale state, and retaining every length-n IgnoranceMsg would grow
        # memory O(rounds * n)
        self.inbox = [m for m in self.inbox if m.kind != msg.kind]
        self.inbox.append(msg)

    def latest(self, kind: str) -> Message | None:
        for msg in reversed(self.inbox):
            if msg.kind == kind:
                return msg
        return None

    # ---- local computation (Algorithm 2: weighted supervised training)
    def fit_local(self, key, classes: jnp.ndarray, w: jnp.ndarray,
                  num_classes: int) -> PyTree:
        return self.learner.fit(key, self.X, classes, w, num_classes)

    def reward(self, params: PyTree, classes: jnp.ndarray) -> jnp.ndarray:
        return self.learner.reward(params, self.X, classes)

    def score_block(self, components: Sequence["Component"], num_classes: int,
                    X: jnp.ndarray | None = None,
                    max_round: int | None = None) -> jnp.ndarray:
        """This agent's [n, K] alpha-weighted coded votes over its own
        components (the prediction-time ScoreBlockMsg payload)."""
        X = self.X if X is None else X
        total = jnp.zeros((X.shape[0], num_classes), jnp.float32)
        for comp in components:
            if comp.agent != self.agent_id:
                continue
            if max_round is not None and comp.round > max_round:
                continue
            total = total + _component_score(comp, self.learner, X,
                                             num_classes)
        return total


# ================================================================ fitted result
@dataclass
class Component:
    """One boosting component: (agent, round, alpha, fitted params)."""
    agent: int
    round: int
    alpha: float
    params: PyTree


def _component_score(comp: "Component", learner: Learner, X: jnp.ndarray,
                     num_classes: int) -> jnp.ndarray:
    """One component's [n, K] contribution: alpha * coded votes (Algorithm 1
    line 12 term) — the single definition shared by host-side prediction and
    endpoint score blocks."""
    pred = learner.predict(comp.params, X)
    return comp.alpha * encode_labels(pred, num_classes)


@dataclass
class FittedASCII:
    """The trained ensemble: Algorithm 1's output, usable for prediction.

    Also the engine's session result (``Session.fitted()``) and the
    back-compat return type of ``protocol.fit``.
    """
    components: list[Component]
    learners: Sequence[Learner]
    num_classes: int
    history: list[dict] = field(default_factory=list)

    def decision_scores(self, Xs: Sequence[jnp.ndarray],
                        max_round: int | None = None) -> jnp.ndarray:
        """Line 12 of Algorithm 1: sum_t sum_m alpha * g (coded scores).

        Each agent evaluates only its own components on its own features and
        ships a [n, K] score block — O(nK) communication, not raw data.
        """
        n = Xs[0].shape[0]
        k = self.num_classes
        # NB: summed in component order (not grouped per agent) so float
        # addition order — and therefore predictions — match the legacy loop
        # bit for bit.
        total = jnp.zeros((n, k), jnp.float32)
        for comp in self.components:
            if max_round is not None and comp.round > max_round:
                continue
            total = total + _component_score(comp, self.learners[comp.agent],
                                             Xs[comp.agent], k)
        return total

    def predict(self, Xs: Sequence[jnp.ndarray],
                max_round: int | None = None) -> jnp.ndarray:
        return jnp.argmax(self.decision_scores(Xs, max_round), axis=-1)

    @property
    def num_rounds(self) -> int:
        return max((c.round for c in self.components), default=-1) + 1


# ============================================================ protocol variants
class ProtocolVariant(abc.ABC):
    """The round rule of one decentralized-learning protocol.

    The engine's session loop (scheduling, churn filtering, budget
    exhaustion, CV stop, checkpointing) is protocol-agnostic; a variant
    supplies what happens *inside* one round and how the trained model
    predicts.  ASCII (ignorance interchange) is the built-in variant;
    FedAvg and Assisted Learning live in :mod:`repro.scenarios.protocols`
    and ship their traffic through the same transports, codecs, budgets,
    and DP accounting — that is the whole point: one wire, comparable
    ledgers.
    """

    name = "variant"

    def bind(self, session: "Session") -> None:
        """Session-start hook: validate the endpoint roster and initialize
        the variant's protocol state (``session.state.proto``, a
        checkpointable pytree) when the session is fresh.  Called on both
        fresh starts and resumes; ``state.proto`` is only initialized when
        missing."""

    @abc.abstractmethod
    def run_round(self, session: "Session", order: list[int],
                  rec: dict) -> bool:
        """Execute one round over the (churn-filtered) agent ``order``,
        recording into the history record ``rec``.  Returns True when the
        protocol's own stop criterion fired."""

    @abc.abstractmethod
    def fitted(self, session: "Session"):
        """The trained, predict-capable result of this session."""

    def fit_compiled(self, protocol: "Protocol", key, endpoints, classes,
                     validation):
        """Lower a whole run into one XLA program (optional).  Variants
        without a lowering run eager only."""
        raise ValueError(
            f"protocol variant {self.name!r} has no compiled lowering; "
            f"use backend='eager'")


class ASCIIVariant(ProtocolVariant):
    """The paper's protocol: ignorance-score interchange around the chain
    (Algorithm 1 lines 3-11), including the stale-read async barrier."""

    name = "ascii"

    def bind(self, session: "Session") -> None:
        sc = session.scenario
        if sc is not None and getattr(sc, "clock_skew", None):
            if session.state.proto is None:
                # bounded ignorance history for clock-skewed stale reads:
                # agent m reads the score from skew_m barriers ago
                session.state.proto = {"w_hist": [session.state.w]}

    def run_round(self, session: "Session", order: list[int],
                  rec: dict) -> bool:
        st, cfg = session.state, session.cfg
        eps = {ep.agent_id: ep for ep in session.endpoints}
        rec.setdefault("alphas", [])
        rec.setdefault("accs", [])
        if session.scheduler.stale:
            return session._step_stale(order, eps, rec)
        reweight, standard = session._reweight()
        k = cfg.num_classes
        t = st.round
        n = st.w.shape[0]
        u = jnp.ones((n,), jnp.float32)
        stop = False
        for j, m in enumerate(order):
            dst = eps[order[(j + 1) % len(order)]]
            with session._span("hop", src=eps[m].name, dst=dst.name):
                st.key, sub = jax.random.split(st.key)
                w_fit = session.fit_weight(m, st.w)
                params = eps[m].fit_local(sub, session.classes, w_fit, k)
                r = eps[m].reward(params, session.classes)
                if (not cfg.upstream) or j == 0:
                    a, rbar = scores.model_weight(st.w, r, k,
                                                  alpha_cap=cfg.alpha_cap)
                else:
                    a, rbar = scores.model_weight(st.w, r, k, u=u,
                                                  alpha_cap=cfg.alpha_cap)
                rec["alphas"].append(float(a))
                rec["accs"].append(float(rbar))
                session.scheduler.observe(m, float(rbar))
                if cfg.stop_on_negative_alpha and float(a) <= 0:
                    return True        # Algorithm 1, line 8
                st.components.append(Component(m, t, float(a), params))
                u = scores.upstream_factor_update(u, a, r, k)
                link_state = (None if st.codec_state is None
                              else st.codec_state.get(eps[m].name))
                st.w, link_state = session.transport.interchange(
                    eps[m], dst, st.w, r, a, reweight, standard,
                    key=sub if session.transport.has_channel else None,
                    codec_state=link_state)
                if link_state is not None:
                    if st.codec_state is None:
                        st.codec_state = {}
                    st.codec_state[eps[m].name] = link_state
        return stop

    def fitted(self, session: "Session") -> "FittedASCII":
        return FittedASCII(session.state.components,
                           [ep.learner for ep in session.endpoints],
                           session.cfg.num_classes, session.state.history)


# ================================================================ session state
@dataclass
class SessionState:
    """Explicit, checkpointable protocol state.

    Arrays (ignorance score, PRNG key, component params) serialize through
    ``train/checkpoint.py``'s structured tree writer; everything else is
    JSON-able metadata.  Saving mid-run and resuming reproduces the exact
    trajectory: the PRNG key is part of the state and schedulers fast-forward
    their RNG via :meth:`Scheduler.skip_to`.
    """

    w: jnp.ndarray
    key: jax.Array
    round: int = 0
    components: list[Component] = field(default_factory=list)
    history: list[dict] = field(default_factory=list)
    stopped: bool = False
    best_val: float = -1.0
    cv_stale: int = 0
    # per-round active-agent counts (for exact scheduler-RNG replay on
    # resume) and the endpoint active flags at checkpoint time
    order_sizes: list[int] = field(default_factory=list)
    active: list[bool] | None = None
    # per-link wire-codec state (top-k error-feedback residuals, keyed by
    # sender name) — part of the protocol state, so checkpoint/resume
    # reproduces lossy-channel trajectories exactly
    codec_state: dict | None = None
    # JSON-able transport channel bookkeeping captured at checkpoint time
    # (budget spent-bits / link spend / exhaustion, DP release counts):
    # without it a resumed run would restart the bit budget and epsilon
    # ledger from zero, violating the caps the paused run was under
    comm: dict | None = None
    # protocol-variant state (repro.scenarios): a checkpointable pytree of
    # arrays — FedAvg's flat global params, Assisted Learning's running
    # residual, the clock-skew ignorance history.  None for plain ASCII.
    proto: PyTree = None

    # ---- (de)serialization --------------------------------------------------
    def to_tree(self) -> tuple[PyTree, dict]:
        """Split into (array tree, JSON-able metadata)."""
        tree = {"w": self.w,
                "key": jax.random.key_data(self.key),
                "params": [c.params for c in self.components],
                "codec_state": self.codec_state,
                "proto": self.proto}
        meta = {"round": self.round,
                "stopped": self.stopped,
                "best_val": self.best_val,
                "cv_stale": self.cv_stale,
                "history": self.history,
                "order_sizes": self.order_sizes,
                "active": self.active,
                "comm": self.comm,
                "components": [{"agent": c.agent, "round": c.round,
                                "alpha": c.alpha} for c in self.components]}
        return tree, meta

    @classmethod
    def from_tree(cls, tree: PyTree, meta: dict) -> "SessionState":
        components = [
            Component(int(c["agent"]), int(c["round"]), float(c["alpha"]), p)
            for c, p in zip(meta["components"], tree["params"])]
        return cls(w=jnp.asarray(tree["w"]),
                   key=jax.random.wrap_key_data(jnp.asarray(tree["key"])),
                   round=int(meta["round"]),
                   components=components,
                   history=list(meta["history"]),
                   stopped=bool(meta["stopped"]),
                   best_val=float(meta["best_val"]),
                   cv_stale=int(meta["cv_stale"]),
                   order_sizes=[int(s) for s in meta.get("order_sizes", [])],
                   active=meta.get("active"),
                   codec_state=tree.get("codec_state"),
                   comm=meta.get("comm"),
                   proto=tree.get("proto"))

    def save(self, directory: str, step: int | None = None) -> str:
        from repro.train import checkpoint
        tree, meta = self.to_tree()
        return checkpoint.save_structured(
            directory, self.round if step is None else step, tree, meta=meta)

    @classmethod
    def restore(cls, directory: str, step: int | None = None) -> "SessionState":
        from repro.train import checkpoint
        tree, meta, _ = checkpoint.restore_structured(directory, step=step)
        return cls.from_tree(tree, meta)


# ======================================================================= config
@dataclass(frozen=True)
class SessionConfig:
    """Engine knobs (the old ASCIIConfig minus variant/seed, which became
    the Scheduler)."""
    num_classes: int
    max_rounds: int = 20
    upstream: bool = True             # eqs. 11/13 side info (False = -Simple)
    stop_on_negative_alpha: bool = True
    cv_patience: int = 2
    alpha_cap: float = 20.0
    exact_reweight: bool = False      # beyond-paper exact exp-loss reweight


def holdout_split(Xs: Sequence[jnp.ndarray], classes: jnp.ndarray,
                  fraction: float):
    """The paper's CV stop criterion split (Section III-C): reserve the
    trailing rows (aligned by sample ID) for validation."""
    cut = int(round((1.0 - fraction) * Xs[0].shape[0]))
    return ([x[:cut] for x in Xs], classes[:cut],
            [x[cut:] for x in Xs], classes[cut:])


# ====================================================================== session
class Session:
    """A live protocol run: endpoints + scheduler + transport + state.

    ``step()`` executes one interchange round and returns whether the
    session should continue; ``run()`` loops to completion.  Between steps
    callers may drop endpoints (``active = False``), add late joiners
    (:meth:`add_endpoint`), or checkpoint (:meth:`checkpoint`).
    """

    def __init__(self, cfg: SessionConfig, scheduler: Scheduler,
                 transport: Transport, endpoints: Sequence[AgentEndpoint],
                 classes: jnp.ndarray, state: SessionState,
                 validation: tuple[Sequence[jnp.ndarray], jnp.ndarray] | None = None,
                 variant: ProtocolVariant | None = None,
                 scenario=None, telemetry=None,
                 _send_setup: bool = True) -> None:
        self.cfg = cfg
        self.scheduler = scheduler
        self.transport = transport
        # optional repro.telemetry.Telemetry: pure observation — attached
        # before any traffic so the registry sees every booking, never read
        # by protocol logic (telemetry on == off, bit for bit)
        self.telemetry = telemetry
        if telemetry is not None:
            telemetry.attach_transport(transport)
        self.endpoints = list(endpoints)
        for i, ep in enumerate(self.endpoints):
            assert ep.agent_id == i, "endpoint agent_ids must be 0..M-1"
        self.classes = classes
        self.state = state
        self.validation = validation
        self.variant = variant if variant is not None else ASCIIVariant()
        self.scenario = scenario
        # per-session variant context (derived, non-checkpointed: unravel
        # closures, one-hot labels, fit-weight tables) — variants stash what
        # bind() computes here so one variant object can drive many sessions
        self.vctx: dict = {}
        if scheduler.stale and transport.controller is not None:
            raise ValueError(
                "adaptive controllers do not apply to the stale-read async "
                "path: their EMA statistic is defined on per-hop "
                "interchange, and the barrier releases once per round; "
                "drop controller= (codec/privacy/budget channels release "
                "per barrier and are supported)")
        if not isinstance(self.variant, ASCIIVariant):
            if scheduler.stale:
                raise ValueError(
                    f"the stale-read async barrier is an ASCII merge rule; "
                    f"protocol variant {self.variant.name!r} needs a "
                    f"sequential or random scheduler")
            if transport.controller is not None \
                    or transport.serve_controller is not None:
                raise ValueError(
                    "adaptive controllers read ignorance-vector statistics; "
                    f"they do not apply to protocol variant "
                    f"{self.variant.name!r} traffic — drop controller=/"
                    "serve_controller=")
        self._participation = None
        self._shard_w = None
        if scenario is not None:
            scenario.validate(len(self.endpoints), scheduler, self.variant)
            self._participation = scenario.participation(
                cfg.max_rounds, len(self.endpoints))
            self._shard_w = scenario.shard_weights(classes,
                                                   len(self.endpoints))
        transport.bind(self.endpoints)
        scheduler.bind_transport(transport)
        self.variant.bind(self)
        # live in-flight emission (telemetry.live): eager rounds tap the
        # sink directly with per-round registry deltas.  Metered transports
        # only — an unmetered run books nothing, so its taps would read
        # all-zero and break the eager==compiled live-series pin.  The prev
        # counters snapshot *before* the collation setup so the setup bits
        # land in round 0's delta, matching the compiled t==0 tap.
        self._live = None
        if telemetry is not None \
                and getattr(telemetry, "live", None) is not None \
                and getattr(transport, "log", None) is not None:
            self._live = telemetry.live
            self._live_prev = self._live_counters()
        if _send_setup:
            self._send_setup_to(self.endpoints[1:])

    # ---- live emission ------------------------------------------------------
    def _live_counters(self) -> tuple:
        """The replay-equal counters the eager round taps difference: total
        wire bits, ignorance messages, budget skips, the exhausted flag."""
        reg = self.telemetry.registry
        return (reg.total("wire_bits_total"),
                reg.value("messages_total", kind="ignorance"),
                reg.total("budget_skips_total"),
                bool(getattr(self.transport, "exhausted", False)))

    def _emit_live_round(self, t: int) -> None:
        """One eager round tap: the same (round, bits, sent, skipped,
        exhaustion-edge) payload the compiled scan's emit_round stages, so
        the two backends fold identical live series."""
        bits, ign, skips, exh = cur = self._live_counters()
        p_bits, p_ign, p_skips, p_exh = self._live_prev
        self._live_prev = cur
        self._live.round_tap(t, int(bits - p_bits), int(ign - p_ign),
                             int(skips - p_skips), int(exh and not p_exh))

    # ---- wiring -------------------------------------------------------------
    def _span(self, name: str, step: int | None = None, **attrs):
        """A telemetry span when telemetry is attached, else a no-op
        context — call sites stay branch-free."""
        if self.telemetry is None:
            return nullcontext()
        return self.telemetry.span(name, step, **attrs)

    def _send_setup_to(self, eps: Sequence[AgentEndpoint]) -> None:
        """Collation setup for ``eps`` (:meth:`Transport.book_setup`)."""
        self.transport.book_setup(self.endpoints[0].name,
                                  [ep.name for ep in eps],
                                  int(self.classes.shape[0]))

    def add_endpoint(self, learner: Learner, X: jnp.ndarray,
                     name: str = "") -> AgentEndpoint:
        """Late join: a new agent enters the live session.  It receives the
        collation setup and participates from the next round on."""
        ep = AgentEndpoint(len(self.endpoints), learner, X, name=name)
        self.endpoints.append(ep)
        self.transport.bind(self.endpoints)
        self._send_setup_to([ep])
        return ep

    def _reweight(self):
        cfg = self.cfg
        if cfg.exact_reweight:
            return (lambda w, r, a:
                    scores.ignorance_update_exact(w, r, a, cfg.num_classes)), False
        return scores.ignorance_update, True

    def fit_weight(self, m: int, w: jnp.ndarray) -> jnp.ndarray:
        """Agent m's fit-weight vector: the protocol weight ``w`` masked to
        the agent's non-IID shard (repro.scenarios partitions) and
        renormalized.  Identity when the scenario is IID — the zero-scenario
        path is untouched, byte for byte."""
        if self._shard_w is None:
            return w
        wm = w * self._shard_w[m]
        return wm / jnp.maximum(jnp.sum(wm), 1e-12)

    # ---- the round loop -----------------------------------------------------
    def step(self) -> bool:
        """One interchange round (Algorithm 1 lines 3-11 / the Section-IV
        chain).  Returns False once the session stopped."""
        st, cfg = self.state, self.cfg
        if st.stopped or st.round >= cfg.max_rounds:
            return False
        if getattr(self.transport, "exhausted", False):
            # budget-aware scheduling: the session bit budget can no longer
            # afford even the cheapest codec rung — stop scheduling rounds
            st.stopped = True
            return False
        t = st.round
        active = [ep.agent_id for ep in self.endpoints if ep.active]
        if not active:
            st.stopped = True          # everyone dropped out: nothing to run
            return False
        order = self.scheduler.round_order(t, active)
        # record the *pre-churn* order size: scheduler-RNG replay on resume
        # redraws from the active roster, then re-applies the (pure, seeded)
        # participation schedule
        st.order_sizes.append(len(order))
        rec: dict = {"round": t}
        if self._participation is not None:
            order = [m for m in order if self._participation[t, m]]
            rec["participants"] = list(order)
        stop = False
        with self._span("round", step=t, agents=len(order)):
            if order:
                stop = self.variant.run_round(self, order, rec)
        # an all-churned round is an empty round, not a stop: stragglers
        # come back

        if self.validation is not None:
            Xs_val, c_val = self.validation
            val_acc = float(jnp.mean(self.fitted().predict(Xs_val) == c_val))
            rec["val_acc"] = val_acc
            if val_acc > st.best_val + 1e-9:
                st.best_val, st.cv_stale = val_acc, 0
            else:
                st.cv_stale += 1
                if st.cv_stale >= cfg.cv_patience:
                    stop = True        # out-sample error no longer decreasing
        st.history.append(rec)
        st.round += 1
        if stop:
            st.stopped = True
        if self._live is not None:
            self._emit_live_round(t)
        return not st.stopped and st.round < cfg.max_rounds

    def _step_stale(self, order: list[int], eps: dict, rec: dict) -> bool:
        """Asynchronous round: stale reads, damped multiplicative merge at
        the barrier (see AsyncStaleScheduler)."""
        st, cfg = self.state, self.cfg
        k = cfg.num_classes
        t = st.round
        fits = []
        for m in order:
            st.key, sub = jax.random.split(st.key)
            w_read = self._stale_view(m)
            params = eps[m].fit_local(sub, self.classes,
                                      self.fit_weight(m, w_read), k)
            r = eps[m].reward(params, self.classes)
            a, rbar = scores.model_weight(w_read, r, k,
                                          alpha_cap=cfg.alpha_cap)
            fits.append((m, params, r, a, rbar))
        w_next = st.w
        any_pos = False
        total = len(order)
        channel = self.transport.has_channel
        for j, (m, params, r, a, rbar) in enumerate(fits):
            rec["alphas"].append(float(a))
            rec["accs"].append(float(rbar))
            self.scheduler.observe(m, float(rbar))
            if float(a) <= 0:
                continue
            any_pos = True
            st.components.append(Component(m, t, float(a), params))
            # damp the stale multiplicative updates by 1/M: the naive product
            # of M per-agent reweights diverges for large M (measured:
            # chance-level at M=20); damping restores the per-round weight
            # movement of the sequential chain.
            w_next = w_next * jnp.exp((a / total) * (1.0 - r))
            if channel:
                # under a wire channel the barrier is the release point:
                # only the raw scalar alphas cross per agent; the merged
                # score ships once, below
                self.transport.send(ModelWeightMsg(eps[m].name, "barrier",
                                                   float(a)))
            else:
                dst = eps[order[(j + 1) % total]]
                self.transport.send(IgnoranceMsg(eps[m].name, dst.name,
                                                 w_next))
                self.transport.send(ModelWeightMsg(eps[m].name, dst.name,
                                                   float(a)))
        w_bar = w_next / jnp.maximum(jnp.sum(w_next), 1e-12)
        if not channel:
            st.w = w_bar
        else:
            # per-barrier release semantics: DP noise + codec encode happen
            # at merge time, once per round, and a budgeted transport walks
            # its ladder at the *barrier* granularity — a skipped release
            # leaves the published score stale for one more round
            st.key, kbar = jax.random.split(st.key)
            link_state = (None if st.codec_state is None
                          else st.codec_state.get("barrier"))
            released, link_state = self.transport.barrier_release(
                eps[order[0]], w_bar, key=kbar, codec_state=link_state)
            if link_state is not None:
                if st.codec_state is None:
                    st.codec_state = {}
                st.codec_state["barrier"] = link_state
            if released is not None:
                st.w = released
        self._push_stale_hist()
        return not any_pos and cfg.stop_on_negative_alpha

    def _stale_view(self, m: int) -> jnp.ndarray:
        """The ignorance score agent ``m`` reads at the barrier: the current
        one, or — under a clock-skewed scenario — the one from ``skew_m``
        barriers ago (a slow agent trains against an old broadcast)."""
        sc = self.scenario
        skew = None if sc is None else getattr(sc, "clock_skew", None)
        if not skew or not skew[m]:
            return self.state.w
        hist = self.state.proto["w_hist"]
        return hist[max(0, len(hist) - 1 - int(skew[m]))]

    def _push_stale_hist(self) -> None:
        """Advance the bounded clock-skew history after a barrier merge."""
        sc = self.scenario
        skew = None if sc is None else getattr(sc, "clock_skew", None)
        if not skew:
            return
        hist = self.state.proto["w_hist"]
        hist.append(self.state.w)
        depth = max(int(s) for s in skew) + 1
        del hist[:-depth]

    def run(self, max_rounds: int | None = None) -> SessionState:
        """Drive ``step()`` to completion (or for ``max_rounds`` more)."""
        budget = float("inf") if max_rounds is None else max_rounds
        with self._span("session", backend="eager",
                        agents=len(self.endpoints)):
            while budget > 0:
                budget -= 1
                if not self.step():
                    break
        return self.state

    # ---- results ------------------------------------------------------------
    def fitted(self):
        return self.variant.fitted(self)

    def predict_distributed(self, Xs: Sequence[jnp.ndarray] | None = None,
                            max_round: int | None = None, *,
                            key=None, request=None) -> jnp.ndarray:
        """Prediction as the protocol actually runs it: every endpoint ships
        its [n, K] ScoreBlockMsg to the head agent, which sums and argmaxes.

        The blocks travel through the transport's wire channel
        (:meth:`Transport.serve_block`): DP-noised, codec-encoded, booked at
        their *encoded* size, and — on a budgeted transport — walked down
        the same degrade-then-skip ladder as training hops.  A skipped block
        degrades the answer toward head-only prediction instead of booking
        bits the budget cannot afford.  ``key`` seeds the serve channel
        (stochastic rounding / DP noise); by default it folds off the
        session's current PRNG key with the SERVE tag (plus the integer
        ``request`` tag when given — request-keyed serving: distinct
        requests against one session draw independent channel noise, and
        the serve engine's batched slots derive the identical key), so
        serving never perturbs the fit stream and resumed sessions serve
        identically."""
        if not isinstance(self.variant, ASCIIVariant):
            raise ValueError(
                f"score-block serving is ASCII's prediction protocol; "
                f"variant {self.variant.name!r} predicts via "
                f"session.fitted().predict(Xs)")
        head = self.endpoints[0]
        if key is None and self.transport.has_serve_channel:
            key = serve_key(self.state.key, request)
        if self._live is not None:
            reg = self.telemetry.registry
            p_bits = reg.total("wire_bits_total")
            p_blk = reg.value("messages_total", kind="score_block")
            p_skips = reg.total("budget_skips_total")
        total = None
        with self._span("serve", backend="eager",
                        agents=len(self.endpoints)):
            for i, ep in enumerate(self.endpoints):
                X = None if Xs is None else Xs[i]
                block = ep.score_block(self.state.components,
                                       self.cfg.num_classes, X=X,
                                       max_round=max_round)
                if ep is head:
                    contrib = block
                else:
                    sub = None if key is None else jax.random.fold_in(key, i)
                    contrib = self.transport.serve_block(ep, head, block,
                                                         key=sub)
                    if contrib is None:
                        continue       # budget skip: head-only fallback
                total = contrib if total is None else total + contrib
        if self._live is not None:
            # one serve tap per request — the eager twin of the traced
            # emit_serve, differencing the same booked counters
            self._live.serve_tap(
                int(reg.total("wire_bits_total") - p_bits),
                int(reg.value("messages_total", kind="score_block")
                    - p_blk),
                int(reg.total("budget_skips_total") - p_skips))
        return jnp.argmax(total, axis=-1)

    # ---- checkpointing ------------------------------------------------------
    def _comm_snapshot(self) -> dict | None:
        """JSON-able channel bookkeeping that must survive pause/resume:
        budget spend (the cap applies to the whole session, not to one
        process lifetime) and DP release counts (epsilon composes across
        the resume boundary)."""
        t = self.transport
        snap: dict = {}
        if t.accountant is not None:
            snap["releases"] = dict(t.accountant.releases)
        if hasattr(t, "budget"):
            snap["ledger_bits"] = (int(t.log.total_bits)
                                   + int(getattr(t, "carryover_bits", 0)))
            snap["link_spent"] = [[s, d, int(b)]
                                  for (s, d), b in t.link_spent.items()]
            snap["exhausted"] = bool(t.exhausted)
        if t.controller is not None:
            # the adaptive controller's EMA (a float32 scalar — exact
            # through the JSON float round-trip): a resumed session must
            # pick the rungs the uninterrupted one would, not restart the
            # policy at the uniform-entropy state
            snap["ctrl_state"] = float(np.asarray(t.ctrl_state))
        state_dict = getattr(self.scheduler, "state_dict", None)
        if state_dict is not None:
            snap["scheduler"] = state_dict()
        return snap or None

    def _comm_restore(self, snap: dict | None) -> None:
        t = self.transport
        if not snap:
            return
        if snap.get("releases") and t.accountant is not None:
            t.accountant.releases.update(snap["releases"])
        if hasattr(t, "budget"):
            # the resumed transport's log starts empty; the paused run's
            # spend counts against the session cap via carryover_bits
            t.carryover_bits = int(snap.get("ledger_bits", 0))
            t.link_spent = {(s, d): b
                            for s, d, b in snap.get("link_spent", [])}
            t.exhausted = bool(snap.get("exhausted", False))
        if t.controller is not None and snap.get("ctrl_state") is not None:
            t.ctrl_state = jnp.asarray(snap["ctrl_state"], jnp.float32)
        load_state = getattr(self.scheduler, "load_state_dict", None)
        if load_state is not None and snap.get("scheduler") is not None:
            load_state(snap["scheduler"])

    def checkpoint(self, directory: str, step: int | None = None) -> str:
        """Save the live SessionState mid-run (resumable via
        ``Protocol.resume``)."""
        self.state.active = [ep.active for ep in self.endpoints]
        self.state.comm = self._comm_snapshot()
        return self.state.save(directory, step)


# ======================================================================= engine
BACKENDS = ("eager", "compiled")


class Protocol:
    """The ASCII engine: config + scheduler + transport, driving endpoints.

    ``start`` opens a fresh session, ``resume`` restores one from a
    checkpoint directory (fast-forwarding the scheduler RNG), and ``fit`` is
    the one-call convenience that runs a session to completion.

    ``backend`` selects how ``fit`` executes the rounds:

      * ``"eager"`` (default) — the host loop above: one dispatch per fit /
        reward / hop.  Works with every learner, scheduler, and transport,
        and supports mid-run checkpointing, dropout, and late joins.
      * ``"compiled"`` — lower the whole run (all agents x all rounds of
        weighted fit, reward, alpha, ignorance update) into a single
        ``lax.scan`` program via :mod:`repro.core.compiled`.  Requires
        sequential scheduling, no CV validation split, and learners with a
        :class:`~repro.learners.base.LearnerCore` (``functional = True``);
        reproduces the eager trajectory bit for bit, and metered transports
        still receive the exact same message ledger (replayed post-run).
        ``start``/``resume`` (interactive stepping) always run eager.
    """

    def __init__(self, cfg: SessionConfig, scheduler: Scheduler | None = None,
                 transport: Transport | None = None,
                 backend: str = "eager",
                 variant: ProtocolVariant | None = None,
                 scenario=None, telemetry=None) -> None:
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
        self.cfg = cfg
        self.scheduler = scheduler if scheduler is not None else SequentialScheduler()
        self.transport = transport if transport is not None else InProcessTransport()
        self.backend = backend
        self.variant = variant if variant is not None else ASCIIVariant()
        self.scenario = scenario
        # optional repro.telemetry.Telemetry, threaded into sessions (eager)
        # and attached around the ledger replay (compiled) — observation
        # only, never read by protocol logic
        self.telemetry = telemetry
        # last fit() context, so predict_distributed works on both backends:
        # the eager session, or the compiled (endpoints, plan, result)
        self._fit_key = None
        self._session: Session | None = None
        self._compiled_ctx = None

    def start(self, key: jax.Array, endpoints: Sequence[AgentEndpoint],
              classes: jnp.ndarray,
              validation=None) -> Session:
        n = endpoints[0].X.shape[0]
        state = SessionState(w=scores.init_ignorance(n), key=key)
        self.scheduler.reset()
        return Session(self.cfg, self.scheduler, self.transport, endpoints,
                       classes, state, validation=validation,
                       variant=self.variant, scenario=self.scenario,
                       telemetry=self.telemetry)

    def resume(self, directory: str, endpoints: Sequence[AgentEndpoint],
               classes: jnp.ndarray, validation=None,
               step: int | None = None) -> Session:
        """Restore a checkpointed session and continue where it left off."""
        state = SessionState.restore(directory, step=step)
        self.scheduler.reset()
        self.scheduler.skip_to(state.order_sizes)
        if state.active is not None:
            if len(endpoints) != len(state.active):
                raise ValueError(
                    f"resume expects {len(state.active)} endpoints (the "
                    f"checkpointed session's roster, incl. late joiners), "
                    f"got {len(endpoints)}")
            for ep, flag in zip(endpoints, state.active):
                ep.active = bool(flag)
        session = Session(self.cfg, self.scheduler, self.transport, endpoints,
                          classes, state, validation=validation,
                          variant=self.variant, scenario=self.scenario,
                          telemetry=self.telemetry, _send_setup=False)
        session._comm_restore(state.comm)
        return session

    def fit(self, key: jax.Array, endpoints: Sequence[AgentEndpoint],
            classes: jnp.ndarray, validation=None) -> FittedASCII:
        self._fit_key = key
        with self._span("fit", backend=self.backend, agents=len(endpoints)):
            if self.backend == "compiled":
                return self._fit_compiled(key, endpoints, classes,
                                          validation)
            session = self.start(key, endpoints, classes,
                                 validation=validation)
            session.run()
            self._session = session
            return session.fitted()

    # ---- compiled backend ---------------------------------------------------
    def _span(self, name: str, step: int | None = None, **attrs):
        if self.telemetry is None:
            return nullcontext()
        return self.telemetry.span(name, step, **attrs)

    def _fence(self, value):
        return value if self.telemetry is None else \
            self.telemetry.fence(value)

    def _live_sink(self):
        """The live sink when in-flight emission applies to this run:
        telemetry opened the live plane AND the transport is metered (an
        unmetered run books no wire bits on either backend, so live taps
        would have nothing to mirror)."""
        if self.telemetry is not None \
                and getattr(self.telemetry, "live", None) is not None \
                and getattr(self.transport, "log", None) is not None:
            return self.telemetry.live
        return None

    def _fit_compiled(self, key, endpoints: Sequence[AgentEndpoint],
                      classes: jnp.ndarray, validation) -> FittedASCII:
        """One-program execution of the whole run (core/compiled.py), with
        the transport ledger replayed afterwards so Fig.-4 metering is
        byte-identical to the eager path.

        Under telemetry the ASCII run is four spans below ``fit``: ``plan``
        (transport attach, scheduler bind, ``plan_for``), ``session`` (the
        fenced compiled call; ``traced`` counts the programs it traced),
        ``extract`` (the fitted ensemble and the agent-major view; its
        ``components``, the parameter ``leaves`` they hold, and
        ``dispatches``, the device programs extraction launched: the one
        extraction program, plus a slice per (round, leaf) and a stack per
        leaf where a permuting plan needs the agent-major view), and
        ``replay`` (the ledger; ``messages``, the entries it books on a
        metered transport, and ``dispatches``, the device programs it
        launched: the one row-split program that gives every booked
        ``IgnoranceMsg`` its payload, whatever the hop count).  Where the
        learners count their work (the neural backbone), the ``session``
        span also carries the executed hops' sums: ``tokens_fit``,
        ``tokens_predict`` and ``expert_tokens`` (split as
        ``expert_tokens_fit`` and ``expert_tokens_predict``), the tokens
        routed to held experts."""
        if not isinstance(self.variant, ASCIIVariant):
            self._attach_telemetry()
            # protocol variants own their lowering (repro.scenarios.compiled
            # lowers FedAvg's homogeneous round into a lax.scan); the engine
            # stays variant-agnostic
            return self.variant.fit_compiled(self, key, endpoints, classes,
                                             validation)
        with self._span("plan"):
            self._attach_telemetry()
            plan = self._compiled_plan(endpoints, validation)
        live_sink = self._live_sink()
        stale = isinstance(plan.scheduler, compiled.AsyncStalePlan)
        run = compiled.async_session if stale else compiled.compiled_session
        with self._span("session", backend="compiled",
                        agents=len(endpoints)) as span:
            traced = sum(compiled.TRACE_COUNTS.values())
            # the fence closes the span at computation-done, not at
            # async-dispatch enqueue — timing only, values untouched
            with live_installed(live_sink):
                result = self._fence(run(
                    plan, key, tuple(ep.X for ep in endpoints), classes,
                    live=live_sink is not None))
            if span is not None:
                span.attrs["traced"] = (sum(compiled.TRACE_COUNTS.values())
                                        - traced)
                if not stale:
                    span.attrs.update(compiled.work_counts(result))
        learners = [ep.learner for ep in endpoints]
        with self._span("extract") as span:
            if stale:
                fitted = compiled.fitted_from_async_result(plan, result,
                                                           learners)
                kept = result
            else:
                fitted = compiled.fitted_from_result(plan, result, learners)
                # the serve path indexes per-agent state positionally:
                # store the agent-major view (identity re-collection for
                # sequential plans)
                kept = compiled.agent_major_result(result)
            if span is not None:
                leaves = sum(len(jax.tree.leaves(c.params))
                             for c in fitted.components)
                dispatches = 1
                if kept is not result:   # one slice per (round, leaf)
                    stacked = len(jax.tree.leaves(result.params))
                    leaves += result.alphas.shape[0] * stacked
                    dispatches += (result.alphas.shape[0] + 1) * stacked
                span.attrs.update(components=len(fitted.components),
                                  leaves=leaves, dispatches=dispatches)
        log = getattr(self.transport, "log", None)
        with self._span("replay", backend="compiled") as span:
            booked = 0 if log is None else len(log.entries)
            dispatches = self._replay_traffic(endpoints, classes, result,
                                              plan)
            if span is not None:
                span.attrs["dispatches"] = dispatches
                if log is not None:
                    span.attrs["messages"] = len(log.entries) - booked
        self._compiled_ctx = (tuple(endpoints), plan, kept)
        return fitted

    @property
    def compiled_result(self):
        """The last compiled ASCII fit's result, agent-major: every (round,
        agent) hop's parameters and scores, the hops that stopped the run
        included (None before one)."""
        return None if self._compiled_ctx is None else self._compiled_ctx[2]

    def _attach_telemetry(self) -> None:
        if self.telemetry is not None:
            # attach before any booking: the replay walk (and the variant
            # lowerings' replays) then emit into the registry through the
            # same TransportLog/accountant hooks the eager path uses
            self.telemetry.attach_transport(self.transport)

    def _compiled_plan(self, endpoints: Sequence[AgentEndpoint],
                       validation):
        """The :class:`repro.core.compiled.SessionPlan` of an ASCII run on
        this protocol's config, transport and scheduler; raises for what
        the compiled backend does not lower."""
        cfg = self.cfg
        if self.scenario is not None and not self.scenario.trivial:
            raise ValueError(
                "backend='compiled' does not lower ASCII scenario knobs "
                "(churn/subsampling/partitions change the chain per round); "
                "use backend='eager', or protocol='fedavg' whose lowering "
                "takes a participation mask")
        sched_plan = None
        if self.scheduler.stale:
            # the stale-read barrier has its own lowering (one scan over
            # barrier rounds) — selected by the AsyncStalePlan marker
            sched_plan = compiled.AsyncStalePlan()
        elif not isinstance(self.scheduler, SequentialScheduler):
            plan_fn = getattr(self.scheduler, "plan", None)
            if plan_fn is None:
                raise ValueError(
                    f"backend='compiled' supports sequential, budget-aware "
                    f"and async-stale scheduling, "
                    f"got {type(self.scheduler).__name__}")
            # the scheduler's static twin (spend signal depends on which
            # transport it will order against)
            self.scheduler.bind_transport(self.transport)
            sched_plan = plan_fn()
        if validation is not None:
            raise ValueError("backend='compiled' does not support the CV "
                             "validation stop; use the eager backend")
        if not all(ep.active for ep in endpoints):
            raise ValueError("backend='compiled' assumes all endpoints "
                             "active for the whole run")
        return compiled.plan_for(
            [ep.learner for ep in endpoints], cfg.num_classes,
            max_rounds=cfg.max_rounds, upstream=cfg.upstream,
            stop_on_negative_alpha=cfg.stop_on_negative_alpha,
            alpha_cap=cfg.alpha_cap, exact_reweight=cfg.exact_reweight,
            # mirror the eager transport's update implementation: mesh-ring
            # runs the fused Pallas kernel (with its configured interpret
            # mode), the host transports the jnp formula — so both backends
            # reduce the normalizer the same way at any score length
            use_kernel=isinstance(self.transport, MeshRingTransport),
            kernel_interpret=getattr(self.transport, "interpret", None),
            # the wire channel rides the scan: same codec/privacy/budget
            # objects the eager transport holds, so the traced channel and
            # the rung-choice rule are shared, not re-implemented
            codec=self.transport.codec, privacy=self.transport.privacy,
            budget=getattr(self.transport, "budget", None),
            serve_codec=self.transport.serve_codec,
            controller=self.transport.controller,
            serve_controller=self.transport.serve_controller,
            scheduler=sched_plan)

    def _replay_traffic(self, endpoints: Sequence[AgentEndpoint],
                        classes: jnp.ndarray, result, plan) -> int:
        """Book the message ledger the eager run would have produced, from
        the compiled result arrays: the collation setup, then every hop
        through :meth:`Transport.book_hop` (a budget-dropped one through
        ``skip_hop``) at the price of the rung the program shipped it at,
        then the exhaustion flag — byte-identical to the eager ledger.

        Sequential: one IgnoranceMsg + alpha per component-producing hop, in
        chain order, carrying row ``w_trace[t, j]``.  Async-stale without a
        channel: the per-agent mid-merge pairs, carrying ``w_trace[t, m]``;
        with one: the raw per-agent alphas to the barrier, then the round's
        one release of ``w_bar[t]``.  The payload rows come from one launch
        of :func:`repro.core.compiled.split_rows` (an eager slice per hop
        costs a host dispatch each).  Returns the device programs launched:
        1."""
        tr = self.transport
        tr.bind(endpoints)
        n = int(classes.shape[0])
        names = [ep.name for ep in endpoints]
        num = len(names)
        tr.book_setup(names[0], names[1:], n)
        stale = isinstance(result, compiled.AsyncSessionResult)
        barrier = stale and plan.has_channel
        executed = np.asarray(result.executed)
        valid = np.asarray(result.valid)
        alphas = np.asarray(result.alphas)
        sent = np.asarray(result.sent)
        rungs = np.asarray(result.codec_idx)
        # the async flags are per round (the barrier's); its channel-less
        # per-agent hops all ship, raw
        hop_sent, hop_rungs = ((valid, np.zeros(valid.shape, int)) if stale
                               else (sent, rungs))
        order = getattr(result, "order", None)
        order = None if order is None else np.asarray(order)
        # launched after the reads above wait for the session, so the copies
        # never sit beside its working memory
        rows = compiled.split_rows(result.w_bar if barrier
                                   else result.w_trace)
        # prices per rung, once per replay (a channel-less plan's ladder is
        # the one raw rung)
        budgeted = plan.budget is not None
        prices = hop_prices(plan.ladder, n, budgeted=budgeted,
                            extra=0 if barrier else MODEL_WEIGHT_BITS)
        # a permuting scheduler replays too: round_order reads the live
        # ledger state at each round entry (telemetry + RNG side effects)
        # and observe feeds the reward EMAs — so post-run scheduler state
        # and registry counters match the eager session's exactly
        permuted = not stale and plan.scheduler is not None
        accs = np.asarray(result.accs) if permuted else None
        for t in range(valid.shape[0]):
            if permuted and executed[t].any():
                self.scheduler.round_order(t, list(range(num)))
            for j in range(num):
                src = j if order is None else int(order[t, j])
                dst = ((j + 1) % num if order is None
                       else int(order[t, (j + 1) % num]))
                if permuted and executed[t, j]:
                    self.scheduler.observe(src, float(accs[t, j]))
                if not valid[t, j]:
                    continue
                if barrier:
                    tr.send(ModelWeightMsg(names[src], "barrier",
                                           float(alphas[t, j])))
                elif not hop_sent[t, j]:
                    tr.skip_hop((names[src], names[dst]))
                else:
                    wire, spend = prices[hop_rungs[t, j]]
                    tr.book_hop(IgnoranceMsg(names[src], names[dst],
                                             rows[t][j], wire_bits=wire),
                                spend=spend, alpha=alphas[t, j])
            if not (barrier and executed[t].any()):
                continue
            if not sent[t]:
                tr.skip_hop(("barrier", names[0]))
                continue
            wire, spend = prices[rungs[t]]
            tr.book_hop(IgnoranceMsg("barrier", names[0], rows[t],
                                     wire_bits=wire), spend=spend)
        if budgeted:
            tr.exhausted = bool(result.exhausted)
        return 1

    # ---- serve path ---------------------------------------------------------
    def predict_distributed(self, Xs: Sequence[jnp.ndarray] | None = None,
                            max_round: int | None = None, *,
                            key=None, request=None) -> jnp.ndarray:
        """Distributed prediction after :meth:`fit`, on either backend:
        every endpoint ships its [n, K] ScoreBlockMsg to the head agent
        through the transport's serve channel (codec, DP noise, budget
        ladder).  The compiled backend runs the traced serve step
        (:func:`repro.core.compiled.serve_session`) and replays the exact
        encoded-bit ledger the eager path books — predictions and ledgers
        are pinned bit-identical across backends per codec.

        The default serve ``key`` is the same on both backends: the
        session's *evolved* PRNG key (post-run ``state.key``) folded with
        the SERVE tag (and the integer ``request`` tag when given) — the
        only derivation a resumed session can also reproduce, since it no
        longer knows the original fit key."""
        if self.backend == "eager":
            if self._session is None:
                raise RuntimeError("predict_distributed needs a completed "
                                   "fit() on this Protocol (or use "
                                   "Session.predict_distributed directly)")
            # key=None: the Session derives the default from its evolved
            # state.key, matching the compiled branch below
            return self._session.predict_distributed(Xs, max_round, key=key,
                                                     request=request)
        if self._compiled_ctx is None:
            raise RuntimeError("predict_distributed needs a completed fit()")
        endpoints, plan, result = self._compiled_ctx
        if key is None and self.transport.has_serve_channel:
            key = serve_key(self._evolved_key(result), request)
        Xs_serve = (tuple(ep.X for ep in endpoints) if Xs is None
                    else tuple(jnp.asarray(x) for x in Xs))
        valid = result.valid
        if max_round is not None:
            mask = (jnp.arange(valid.shape[0]) <= max_round)[:, None]
            valid = jnp.logical_and(valid, mask)
        shape = (int(Xs_serve[0].shape[0]), self.cfg.num_classes)
        rem_session, rem_link = self._serve_remaining(endpoints, plan)
        live_sink = self._live_sink()
        with self._span("serve", backend="compiled",
                        agents=len(endpoints)):
            with live_installed(live_sink):
                serve = self._fence(compiled.serve_session(
                    plan, result, key, Xs_serve, valid=valid,
                    rem_session=rem_session, rem_link=rem_link,
                    live=live_sink is not None))
        with self._span("replay", backend="compiled"):
            self._replay_serve(endpoints, serve, shape, plan)
        return serve.preds

    def _evolved_key(self, result):
        """The eager session's post-run ``state.key``, reconstructed from
        the fit key: the eager loop splits once per fit slot it reaches
        (plus once per executed round for the channelized async barrier's
        release subkey), and the compiled scan's key chain agrees with it
        on every executed slot (post-stop splits are masked out), so the
        same split count lands on the identical key."""
        executed = np.asarray(result.executed)
        splits = int(executed.sum())
        if isinstance(result, compiled.AsyncSessionResult) \
                and self.transport.has_channel:
            splits += int(executed.any(axis=1).sum())
        k = self._fit_key
        for _ in range(splits):
            k, _ = jax.random.split(k)
        return k

    def _serve_remaining(self, endpoints, plan) -> tuple:
        """The remaining-budget counters the traced serve step starts from
        (the compiled analogue of the budgeted transport's per-hop reads):
        session bits, and each agent's link bits to the head, clamped to
        int32 (uncapped: its max)."""
        top = int(np.iinfo(np.int32).max)
        if plan.budget is None or not hasattr(self.transport, "remaining"):
            return top, (top,) * len(endpoints)
        head = endpoints[0].name
        rem = [self.transport.remaining((ep.name, head)) for ep in endpoints]
        return (min(rem[0][0], top),
                tuple(min(rem_l, top) for _, rem_l in rem))

    def _replay_serve(self, endpoints, serve, shape, plan) -> None:
        """Book the serve-path ledger the eager path would have produced:
        one ScoreBlockMsg per shipped block through
        :meth:`Transport.book_hop`, at the price of the rung the traced
        serve step chose; skipped links through ``skip_hop`` —
        byte-identical to eager ``Session.predict_distributed``."""
        tr = self.transport
        names = [ep.name for ep in endpoints]
        sent = np.asarray(serve.sent)
        rungs = np.asarray(serve.codec_idx)
        prices = hop_prices(plan.serve_ladder, shape,
                            budgeted=plan.budget is not None)
        for j in range(1, len(names)):
            if not sent[j]:
                tr.skip_hop((names[j], names[0]))
                continue
            # a block shipped at rung -1 rode the one raw serve rung
            wire, spend = prices[max(int(rungs[j]), 0)]
            tr.book_hop(ScoreBlockMsg(names[j], names[0], serve.blocks[j],
                                      wire_bits=wire), spend=spend)
        if plan.budget is not None:
            tr.exhausted = bool(tr.exhausted or bool(serve.exhausted))


def variant_setup(variant: str, seed: int = 0) -> tuple[Scheduler, bool]:
    """Map a legacy ``variant`` string to (scheduler, upstream flag):

      ascii  -> sequential chain, upstream side info (eqs. 11/13)
      simple -> sequential chain, own-loss alphas only
      random -> random order per round, upstream side info
      async  -> stale-read parallel rounds (beyond paper)
    """
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; expected {VARIANTS}")
    if variant == "random":
        return RandomScheduler(seed), True
    if variant == "async":
        return AsyncStaleScheduler(), True
    return SequentialScheduler(), variant != "simple"


def endpoints_for(learners: Sequence[Learner],
                  Xs: Sequence[jnp.ndarray]) -> list[AgentEndpoint]:
    """Build the endpoint list for aligned (learner, feature-block) pairs."""
    assert len(learners) == len(Xs)
    return [AgentEndpoint(m, lr, X) for m, (lr, X) in
            enumerate(zip(learners, Xs))]

"""Bit budgets: the byte ledger as an *active* constraint, not accounting.

PR 1 made every message meterable; this module makes the meter enforceable.
A :class:`BudgetSpec` caps how many bits a session (and optionally each
directed src->dst link) may spend, and :class:`BudgetedTransport` enforces
it per hop with a two-stage response:

  1. **degrade** — walk the codec ladder (best-first) and ship the hop with
     the first codec whose wire cost still fits the remaining budget;
  2. **defer/skip** — when not even the cheapest codec fits, the hop is
     dropped: the receiving agent proceeds with its stale ignorance score
     (the fit and its boosting component still happen — only the score
     transfer is lost).  A skip caused by the *session* budget marks the
     transport ``exhausted``, and the engine stops scheduling further
     rounds (``Session.step`` checks it at round entry) — budget-aware
     round scheduling.

The same ladder walk runs inside the compiled session scan
(`core/compiled.py` carries spent-bit counters through the ``lax.scan``),
so eager and compiled budgeted runs pick identical codecs hop for hop and
book identical ledgers.

Ladder codecs must be stateless (error-feedback residuals can't migrate
between codecs mid-run); setup messages (labels/sample IDs) count against
the session budget, interchange hops against both session and link budgets.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

from repro.comm.codecs import (MODEL_WEIGHT_BITS, Codec, Fp16Codec,
                               Fp32Codec, QuantCodec, rung_bits)
from repro.core.engine import MeteredTransport

DEFAULT_LADDER = (Fp32Codec(), Fp16Codec(), QuantCodec(bits=8),
                  QuantCodec(bits=4))


@dataclass(frozen=True)
class BudgetSpec:
    """Bit caps plus the degradation ladder (best codec first).

    ``session_bits`` caps everything the transport books; ``link_bits`` caps
    each directed (src, dst) interchange link.  Either may be None
    (uncapped).  Hashable frozen dataclass — a valid jit static argument,
    so the compiled backend takes it verbatim."""
    session_bits: int | None = None
    link_bits: int | None = None
    ladder: tuple = DEFAULT_LADDER

    def __post_init__(self):
        if not self.ladder:
            raise ValueError("budget ladder must hold at least one codec")
        for c in self.ladder:
            if not isinstance(c, Codec) or c.stateful:
                raise ValueError(
                    f"budget ladder entries must be stateless Codecs, got "
                    f"{c!r} (error-feedback state cannot migrate between "
                    f"ladder rungs)")
        for cap in (self.session_bits, self.link_bits):
            if cap is not None and cap <= 0:
                raise ValueError(f"budget caps must be positive, got {cap}")

    def hop_costs(self, n: int) -> tuple:
        """Per-ladder-rung cost of one hop for a length-n score: the encoded
        IgnoranceMsg plus the scalar ModelWeightMsg."""
        return rung_bits(self.ladder, n, MODEL_WEIGHT_BITS)

    def payload_costs(self, shape) -> tuple:
        """Per-ladder-rung encoded size of one bare payload of ``shape`` —
        no accompanying ModelWeightMsg: serve-path ScoreBlockMsgs, the
        async barrier release and protocol-variant traffic (GradientMsg /
        ResidualMsg) alike."""
        return rung_bits(self.ladder, shape)

    def choose_costs(self, costs, remaining_session: float,
                     remaining_link: float, floor: int = 0) -> int | None:
        """First ladder index affordable under both remaining budgets, or
        None when the hop must be skipped — the single decision rule both
        engine backends implement, for training hops and serve blocks
        alike.  ``floor`` is the adaptive controller's rung (the walk never
        picks a *finer* rung than the policy asked for; the budget may
        still degrade past it — ladder costs descend, so the floor never
        changes when a hop is skippable)."""
        remaining = min(remaining_session, remaining_link)
        for i in range(floor, len(costs)):
            if costs[i] <= remaining:
                return i
        return None


@dataclass
class TenantBudget:
    """A per-tenant view of serve-traffic spend: one running bit ledger per
    tenant against an optional cap, shared across every session and request
    that tenant submits.

    :class:`BudgetSpec` caps one *session*; a serve fleet fields a stream
    of requests from many tenants against many sessions, and the admission
    layer (:mod:`repro.serve.admission`) needs a per-tenant aggregate to
    gate on *before* any work is done.  ``charge`` books the encoded bits a
    request actually shipped (the same numbers the transport ledger prices),
    so the view and the ledger can never drift."""
    bits: int | None = None         # cap; None = uncapped
    spent: int = 0

    def __post_init__(self):
        if self.bits is not None and self.bits <= 0:
            raise ValueError(f"tenant bit cap must be positive, got "
                             f"{self.bits}")

    @property
    def remaining(self) -> float:
        return math.inf if self.bits is None else self.bits - self.spent

    def affordable(self, cost: int) -> bool:
        return cost <= self.remaining

    def charge(self, bits: int) -> None:
        if isinstance(bits, bool) or not isinstance(bits, int):
            raise TypeError(f"bits must be an integer, got {bits!r}")
        if bits < 0:
            raise ValueError(f"bits must be >= 0, got {bits}")
        self.spent += bits


class BudgetedTransport(MeteredTransport):
    """Byte-metered transport that *enforces* a :class:`BudgetSpec` —
    degrade down the codec ladder, then defer/skip hops (see module
    docstring).  ``exhausted`` flips when the session budget can no longer
    afford even the cheapest rung; the engine stops scheduling rounds."""

    def __init__(self, budget: BudgetSpec, log=None, privacy=None,
                 controller=None, accountant=None, serve_controller=None):
        for what, ctl in (("an adaptive", controller),
                          ("a serve", serve_controller)):
            if ctl is not None and tuple(ctl.ladder) != tuple(budget.ladder):
                raise ValueError(
                    f"{what} controller on a budgeted transport must share "
                    f"the budget's ladder (its rung is a floor on the walk); "
                    f"got {ctl.ladder} vs {budget.ladder}")
        super().__init__(log=log,
                         codec=None if controller is not None
                         else budget.ladder[0],
                         privacy=privacy, controller=controller,
                         accountant=accountant,
                         serve_controller=serve_controller)
        self.budget = budget
        self.link_spent: dict = {}      # (src, dst) -> bits
        self.skipped: list = []         # (src, dst) of dropped hops
        self.exhausted = False
        # bits a paused run already spent against the session cap (restored
        # from SessionState.comm on resume; this process's log starts empty)
        self.carryover_bits = 0

    # ------------------------------------------------------- budget ledger
    # Every skip and every spend — eager ladder walks and compiled ledger
    # replays alike — reaches these two methods through Transport.book_hop /
    # skip_hop, so the telemetry registry (attached to self.log) sees
    # identical budget traffic on both backends.

    def skip_hop(self, link) -> None:
        """Book one dropped hop on ``link`` = (src, dst)."""
        self.skipped.append(link)
        registry = getattr(self.log, "registry", None)
        if registry is not None:
            registry.inc("budget_skips_total", 1, src=link[0], dst=link[1])

    def record_spend(self, link, cost: int, rung: int) -> None:
        """Book ``cost`` bits of link spend for a hop shipped at ladder
        index ``rung`` (``book_hop`` calls it before the payload's send).
        Also sets ``codec`` to the chosen rung, so a replayed run ends with
        the same last-used codec as the eager walk."""
        self.codec = self.budget.ladder[int(rung)]
        self.link_spent[link] = self.link_spent.get(link, 0) + cost
        registry = getattr(self.log, "registry", None)
        if registry is not None:
            registry.inc("hops_by_rung_total", 1, rung=int(rung))

    def remaining(self, link) -> tuple:
        """The (session, link) bits left under the caps for a hop on
        ``link``; ``math.inf`` where uncapped."""
        b = self.budget
        return ((math.inf if b.session_bits is None
                 else b.session_bits - self.log.total_bits
                 - self.carryover_bits),
                (math.inf if b.link_bits is None
                 else b.link_bits - self.link_spent.get(link, 0)))

    def _walk(self, link, costs, floor: int = 0, *, link_capped=True):
        """The one eager ladder walk: the ``(rung, cost)`` the hop on
        ``link`` ships at, with ``codec`` degraded to that rung for the
        channel; or None once the hop is booked as a skip (the receiver
        keeps its stale score), a session-budget skip flipping
        ``exhausted`` so round scheduling ends.  ``link_capped=False``
        exempts a hop with no directed link (the barrier broadcast) from
        the link cap."""
        rem_s, rem_l = self.remaining(link)
        rung = self.budget.choose_costs(
            costs, rem_s, rem_l if link_capped else math.inf, floor)
        if rung is None:
            if rem_s < min(costs):
                self.exhausted = True
            self.skip_hop(link)
            return None
        self.codec = self.budget.ladder[rung]
        return rung, costs[rung]

    def _choose_codec(self, w_prev, w_out) -> None:
        # rung choice already happened in interchange (the controller floor
        # feeds the ladder walk); the base-class per-hop hook must not run
        # the controller a second time
        pass

    @property
    def effective_serve_codec(self):
        # the budget ladder drives serve codec choice: serve_block walks it
        # and sets ``codec`` to the chosen rung before shipping.  The base
        # property's controller bypass (serve raw under a controller) must
        # not apply here — it would ship raw blocks at encoded prices and
        # break eager==compiled serve parity (the compiled serve_ladder is
        # the budget ladder too).
        return self.serve_codec if self.serve_codec is not None else self.codec

    def interchange(self, src, dst, w, r, alpha, reweight,
                    standard=True, *, key=None, codec_state=None):
        floor, w_out = 0, None
        if self.controller is not None:
            # observe the hop the way the base hook would: the controller
            # statistic reads the outgoing (post-reweight) vector, computed
            # once here and threaded through to the base interchange
            w_out = self._execute_update(w, r, alpha, reweight, standard)
            floor = self._controller_rung(w, w_out)
        spend = self._walk((src.name, dst.name),
                           self.budget.hop_costs(int(w.shape[0])), floor)
        if spend is None:
            return w, codec_state
        return super().interchange(src, dst, w, r, alpha, reweight,
                                   standard, key=key,
                                   codec_state=codec_state, _w_out=w_out,
                                   _spend=spend)

    def serve_block(self, src, dst, block, *, key=None):
        """Budgeted serve hop: the same degrade-then-skip ladder walk as
        :meth:`interchange`, applied to the [n, K] ScoreBlockMsg.  A skipped
        block is simply not delivered — the head agent predicts without this
        agent's votes (head-only degradation) and no bits are booked; a
        session-budget skip flips ``exhausted`` exactly like a training
        hop."""
        floor = 0
        if self.serve_controller is not None:
            # the serve policy's rung floors the walk, exactly like the
            # training controller on interchange hops: the budget may
            # degrade coarser than the policy asked for, never finer
            from repro.control.adaptive import jitted_serve_controller
            floor = int(jitted_serve_controller(self.serve_controller)(block))
        spend = self._walk((src.name, dst.name),
                           self.budget.payload_costs(tuple(block.shape)),
                           floor)
        if spend is None:
            return None
        return super().serve_block(src, dst, block, key=key, _spend=spend)

    def barrier_release(self, head, w_bar, *, key=None, codec_state=None):
        """Budgeted async-barrier release: one *session-level* ladder walk
        over the bare payload costs (the per-agent alpha messages book raw
        before this reads the ledger; link caps don't apply — the barrier
        is a broadcast, not a directed link).  A skip leaves the published
        score stale and flips ``exhausted``, ending round scheduling —
        per-barrier budget metering on the one ledger."""
        spend = self._walk(("barrier", head.name),
                           self.budget.payload_costs(int(w_bar.shape[0])),
                           link_capped=False)
        if spend is None:
            return None, codec_state
        return super().barrier_release(head, w_bar, key=key,
                                       codec_state=codec_state, _spend=spend)

    def ship(self, src, dst, payload, wrap, *, key=None):
        """Budgeted protocol-variant hop (GradientMsg / ResidualMsg): the
        same degrade-then-skip ladder walk as :meth:`interchange`, priced
        at the bare encoded payload.  A skipped hop returns None — the
        receiver keeps its stale state (FedAvg: the server averages without
        this client; AL: the next agent fits yesterday's residual) — and a
        session-budget skip flips ``exhausted`` so the engine stops
        scheduling rounds."""
        spend = self._walk((src.name, dst.name),
                           self.budget.payload_costs(tuple(payload.shape)))
        if spend is None:
            return None
        return super().ship(src, dst, payload, wrap, key=key, _spend=spend)

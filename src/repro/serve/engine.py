"""The serve engine: admission -> resident cache -> continuous batching.

``ServeEngine`` turns fitted protocols into *servable sessions* and fields
prediction requests against them behind one API:

    engine = ServeEngine(cache_capacity=8, max_batch=8, ...)
    engine.add_session("s0", fitted_protocol)
    rid, decision = engine.submit("tenant-a", "s0", Xs_block)
    outcomes = engine.flush()          # {rid: ServeOutcome}

``submit`` runs per-tenant admission FIRST (deny / degrade-to-head-only /
accept — no session state is touched for a denied request), then
materializes an admitted request into a batch slot: the session's array
state from the LRU cache (restored from spill if evicted), the per-request
serve key ``serve_key(evolved_session_key, request_id)``, and the
admission ``deliver`` mask.  ``flush`` drains the queue through the
bucketed vmapped serve programs (:mod:`repro.serve.batcher`) and then
books the ledgers exactly the way ``Protocol._replay_serve`` would have
for each request alone — one ``score_block`` entry per shipped block at
its encoded rung size under session-prefixed endpoint names, per-session
DP releases, budget counters advanced, and the tenant account charged the
same bits the wire ledger booked.

The defining invariant (pinned by ``tests/test_serve_engine.py``): a
request served through the batch is **bit-identical** to the same request
served alone via ``Protocol.predict_distributed(Xs, request=rid)`` —
predictions, booked wire bits, and accountant releases.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from time import perf_counter

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.codecs import rung_bits
from repro.comm.privacy import PrivacyAccountant
from repro.serve.admission import DENY, AdmissionController, Decision
from repro.serve.batcher import Batcher, Slot
from repro.serve.cache import ServeSessionState, SessionCache
from repro.telemetry.live import installed as live_installed
from repro.telemetry.slo import SLOConfig, SLOTracker

_INT32_MAX = int(np.iinfo(np.int32).max)


@functools.lru_cache(maxsize=1)
def _zero_key():
    return jax.random.key(0)


@dataclass
class SessionMeta:
    """Static host-side half of a servable session (never spilled): the
    compiled plan, endpoint names, and the per-session serve ledgers the
    engine replays into."""
    plan: object
    names: tuple
    accountant: PrivacyAccountant = field(default_factory=PrivacyAccountant)
    skipped: list = field(default_factory=list)
    exhausted: bool = False
    served: int = 0

    @property
    def has_serve_channel(self) -> bool:
        return (self.plan.serve_ladder[0] is not None
                or self.plan.serve_controller is not None
                or self.plan.privacy is not None)


@dataclass(frozen=True)
class ServeOutcome:
    """What one request came to: the admission verdict, the head agent's
    predictions (None when denied), and what it cost."""
    request_id: int
    session_id: str
    tenant: str
    decision: Decision
    preds: object = None
    bits: int = 0
    releases: int = 0


class ServeEngine:
    """Continuous-batching serve engine over fitted ASCII protocols.

    ``telemetry`` (optional :class:`repro.telemetry.Telemetry`) makes the
    engine emit into one shared registry: the wire ledger, the admission/
    cache/batcher counters, per-session request counts, budget skips, and
    ``flush``/``flush_wave``/``bucket_dispatch`` spans.  Without it the
    engine still keeps a private registry so every counter surface reads
    from the same sink either way."""

    def __init__(self, *, cache_capacity: int = 8, max_batch: int = 8,
                 spill_dir: str | None = None,
                 admission: AdmissionController | None = None,
                 telemetry=None, slo: SLOConfig | None = None) -> None:
        from repro.telemetry.registry import MetricsRegistry
        self.telemetry = telemetry
        self.registry = (telemetry.registry if telemetry is not None
                         else MetricsRegistry())
        # the live plane: batch programs stage in-flight serve taps, and
        # flush() installs this sink around the dispatch so they land here
        self.live = (telemetry.live if telemetry is not None else None)
        self.cache = SessionCache(cache_capacity, spill_dir,
                                  registry=self.registry)
        self.batcher = Batcher(
            max_batch=max_batch,
            resolve=lambda slot: self.cache.get(slot.session_id),
            registry=self.registry,
            tracer=telemetry.tracer if telemetry is not None else None,
            live=self.live is not None)
        self.admission = (admission if admission is not None
                          else AdmissionController())
        self.slo = (SLOTracker(slo, self.registry)
                    if slo is not None else None)
        # denials book their SLO violation at the admission settle point
        self.admission.slo = self.slo
        self._submitted: dict[int, float] = {}
        # a caller-supplied controller keeps its history: fold what it
        # already counted into the shared registry, then rebind
        if self.admission.registry is not self.registry:
            for e in self.admission.registry.to_events():
                if e["type"] == "counter":
                    self.registry.inc(e["name"], e["value"], **e["labels"])
            self.admission.registry = self.registry
        self.log = None             # lazily a TransportLog
        self.sessions: dict[str, SessionMeta] = {}
        self.outcomes: dict[int, ServeOutcome] = {}
        self._next_request = 0

    # -------------------------------------------------------------- sessions
    def add_session(self, session_id: str, protocol) -> None:
        """Register a fitted compiled-backend Protocol as servable: its
        static plan goes in the host registry, its array state (params,
        alphas, valid, evolved key, remaining budget counters) into the
        LRU cache."""
        if session_id in self.sessions:
            raise ValueError(f"session {session_id!r} already registered")
        ctx = getattr(protocol, "_compiled_ctx", None)
        if ctx is None:
            raise ValueError(
                "add_session needs a *fitted* backend='compiled' Protocol "
                "(the serve engine batches traced serve programs)")
        endpoints, plan, result = ctx
        evolved = protocol._evolved_key(result)
        rem_s, rem_l = protocol._serve_remaining(endpoints, plan)
        state = ServeSessionState(
            params=result.params, alphas=result.alphas, valid=result.valid,
            key_data=jax.random.key_data(evolved),
            rem_session=jnp.asarray(rem_s, jnp.int32),
            rem_link=jnp.asarray(rem_l, jnp.int32))
        self.sessions[session_id] = SessionMeta(
            plan=plan, names=tuple(ep.name for ep in endpoints))
        self.cache.put(session_id, state)

    # ------------------------------------------------------------- admission
    def _min_full_bits(self, meta: SessionMeta, shape: tuple) -> int:
        """Cheapest-rung full-serve wire cost: the coarsest serve-ladder
        price for every non-head block (raw fp32 when the rung is None)."""
        cheapest = min(rung_bits(meta.plan.serve_ladder, shape))
        return cheapest * (len(meta.names) - 1)

    # ---------------------------------------------------------------- submit
    def submit(self, tenant: str, session_id: str, Xs,
               request: int | None = None) -> tuple[int, Decision]:
        """Gate, materialize, and enqueue one prediction request.  ``Xs``
        is the per-agent serve-time feature blocks (same layout as
        ``Protocol.predict_distributed``).  Returns (request_id, decision);
        a denied request completes immediately (its ServeOutcome carries no
        predictions), admitted ones resolve at the next :meth:`flush`."""
        meta = self.sessions[session_id]
        rid = self._next_request if request is None else int(request)
        self._next_request = max(self._next_request, rid) + 1
        Xs = tuple(x if isinstance(x, jax.Array) else jnp.asarray(x)
                   for x in Xs)
        if len(Xs) != len(meta.names):
            raise ValueError(f"session {session_id!r} has "
                             f"{len(meta.names)} agents, got {len(Xs)} "
                             f"feature blocks")
        n = int(Xs[0].shape[0])
        shape = (n, meta.plan.num_classes)
        releases = (len(meta.names) - 1
                    if meta.plan.privacy is not None else 0)
        decision = self.admission.admit(
            tenant, min_full_bits=self._min_full_bits(meta, shape),
            releases=releases)
        if decision.outcome == DENY:
            self.admission.book(tenant, decision)
            out = ServeOutcome(rid, session_id, tenant, decision)
            self.outcomes[rid] = out
            return rid, decision
        state = self.cache.get(session_id)
        num = len(meta.names)
        deliver = np.ones((num,), bool)
        if decision.outcome == "degrade":
            deliver[1:] = False                     # head-only
        if meta.has_serve_channel:
            # hand the batch program the evolved session key + request id;
            # the serve_key fold happens in-program (one dispatch per
            # flush, not two per submit)
            key, request = state.key, rid
        else:
            key, request = _zero_key(), None
        self._submitted[rid] = perf_counter()
        self.batcher.add(Slot(
            request_id=rid, session_id=session_id, tenant=tenant,
            plan=meta.plan, key=key, Xs=Xs, deliver=deliver,
            decision=decision, request=request))
        return rid, decision

    # ----------------------------------------------------------------- flush
    def _book(self, slot: Slot, res) -> ServeOutcome:
        """Settle one served slot: replay the per-request serve ledger the
        standalone path books (``Protocol._replay_serve``), priced by the
        same ``rung_bits`` table, under session-prefixed endpoint names so
        sessions never collide in the fleet-wide log (hence its own loop,
        not ``Transport.book_hop``), then charge the tenant the same
        bits."""
        from repro.core.transport import TransportLog
        if self.log is None:
            self.log = TransportLog(registry=self.registry)
        sid = slot.session_id
        meta = self.sessions[sid]
        plan, names = meta.plan, meta.names
        shape = (int(slot.Xs[0].shape[0]), plan.num_classes)
        costs = rung_bits(plan.serve_ladder, shape)
        sent = np.asarray(res.sent)
        rungs = np.asarray(res.codec_idx)
        deliver = np.asarray(slot.deliver)
        budgeted = plan.budget is not None
        head = f"{sid}:{names[0]}"
        bits_total, releases = 0, 0
        link_cost = np.zeros(len(names), np.int64)
        for j in range(1, len(names)):
            if not deliver[j]:
                continue            # head-only degrade: the hop never ran
            link = (f"{sid}:{names[j]}", head)
            if not sent[j]:
                meta.skipped.append(link)       # budget skip
                self.registry.inc("budget_skips_total", 1,
                                  src=link[0], dst=link[1])
                continue
            rung = int(rungs[j])
            # a block shipped at rung -1 rode the one raw serve rung
            bits = costs[max(rung, 0)]
            self.log.send_bits(link[0], link[1], "score_block", bits)
            bits_total += bits
            link_cost[j] = bits
            if budgeted and rung >= 0:
                self.registry.inc("hops_by_rung_total", 1, rung=rung)
            if plan.privacy is not None:
                meta.accountant.record(names[j])
                # session-prefixed in the fleet-wide registry, matching the
                # wire ledger's link naming (per-session epsilon stays on
                # meta.accountant)
                self.registry.inc("dp_releases_total", 1, agent=link[0])
                releases += 1
        if budgeted:
            state = self.cache.get(sid)
            state.rem_session = state.rem_session - jnp.asarray(
                min(bits_total, _INT32_MAX), jnp.int32)
            state.rem_link = state.rem_link - jnp.asarray(
                np.minimum(link_cost, _INT32_MAX), jnp.int32)
            meta.exhausted = bool(meta.exhausted or bool(res.exhausted))
        meta.served += 1
        self.registry.inc("serve_requests_total", 1, session=sid)
        self.admission.book(slot.tenant, slot.decision, bits=bits_total,
                            releases=releases)
        # the single submit -> flush-complete latency stamp: one histogram
        # observation per settled request, at settle time
        t0 = self._submitted.pop(slot.request_id, None)
        if t0 is not None:
            seconds = perf_counter() - t0
            self.registry.observe("request_seconds", seconds,
                                  tenant=slot.tenant)
            if self.slo is not None:
                self.slo.observe(slot.tenant, seconds)
        return ServeOutcome(slot.request_id, sid, slot.tenant,
                            slot.decision, preds=np.asarray(res.preds),
                            bits=bits_total, releases=releases)

    def flush(self) -> dict:
        """Drain the queue through the bucketed batch programs and settle
        every request.  Returns {request_id: ServeOutcome} for requests
        completed by this flush (denied requests completed at submit).
        Settlement happens per batching wave (before the next wave runs),
        so a later request against the same budgeted session starts from
        post-spend counters — exactly like sequential serving."""
        done = {}

        def settle(slot, res):
            out = self._book(slot, res)
            self.outcomes[out.request_id] = out
            done[out.request_id] = out

        if self.telemetry is not None:
            with self.telemetry.span("flush", queued=len(self.batcher)):
                with live_installed(self.live):
                    self.batcher.flush(settle=settle)
        else:
            self.batcher.flush(settle=settle)
        return done

    # --------------------------------------------------------------- summary
    def summary(self) -> dict:
        """Fleet-level accounting: per-tenant counters, cache and batcher
        stats, per-session serve ledgers."""
        total_bits = self.log.total_bits if self.log is not None else 0
        out = {
            "tenants": self.admission.counters(),
            "cache": self.cache.stats(),
            "batcher": self.batcher.stats(),
            "sessions": {
                sid: {"served": m.served, "skipped": len(m.skipped),
                      "exhausted": m.exhausted,
                      "releases": dict(sorted(m.accountant.releases.items()))}
                for sid, m in sorted(self.sessions.items())},
            "total_bits": total_bits,
            "requests": len(self.outcomes),
        }
        if self.slo is not None:
            out["slo"] = self.slo.report()
        return out

    def close(self) -> None:
        self.cache.close()

"""Telemetry: the unified observability layer for train/serve/scenario runs.

One :class:`Telemetry` object owns a :class:`MetricsRegistry` (every
counter the system keeps: wire bits, DP releases, budget skips, admission
outcomes, cache/batch events) and a :class:`SpanTracer` (fit -> session ->
round -> hop on the eager train path, fit -> plan/session/extract/replay on
the compiled one, flush -> flush_wave -> bucket_dispatch on the serve path),
plus the attach/export plumbing that wires them into a run:

    tele = Telemetry()
    proto = Protocol(..., telemetry=tele)
    proto.fit(...)
    tele.write_artifacts(trace="run.jsonl", metrics_out="run.json",
                         transport=proto.transport)

The hard invariant (asserted by tests/test_telemetry.py): a run with
telemetry attached is bit-identical to the same run without — observation
reads already-computed host values, never folds keys, never adds device
dispatches inside traced code, never perturbs the budget ladder walk.

Emission sits at the choke points both engine backends share
(`TransportLog.send_bits`, `PrivacyAccountant.record`,
`BudgetedTransport.skip_hop`/`record_spend`), all reached through the one
wire ledger `Transport.book_hop`/`skip_hop`: eager hops book as they
happen, the compiled replays book hop by hop from the result arrays — so
eager and compiled runs produce identical registries wherever their ledgers
already agree (which the backend-parity tests pin).
"""
from __future__ import annotations

from repro.telemetry.export import (StreamingTraceWriter,  # noqa: F401
                                    snapshot, write_metrics, write_trace)
from repro.telemetry.live import LiveSink  # noqa: F401
from repro.telemetry.registry import MetricsRegistry
from repro.telemetry.spans import Span, SpanTracer  # noqa: F401


class Telemetry:
    """Registry + tracer + attach/export plumbing for one run.

    ``profile`` additionally opens ``jax.profiler`` trace annotations per
    span (pair with ``jax.profiler.trace(dir)`` around the run).  Spans
    fence at dispatch boundaries (``block_until_ready``, timing only), so
    their durations measure computation, not async-dispatch enqueue.
    ``live`` opens the in-flight emission plane
    (:mod:`repro.telemetry.live`): compiled programs stream per-round taps
    into this registry *while executing* instead of going dark until the
    post-run replay.
    """

    def __init__(self, *, profile: bool = False, live: bool = False):
        self.registry = MetricsRegistry()
        self.tracer = SpanTracer(self.registry, profile=profile)
        self.live: LiveSink | None = (LiveSink(self.registry)
                                      if live else None)
        self._stream: StreamingTraceWriter | None = None

    def stream_trace(self, path: str) -> StreamingTraceWriter:
        """Open a crash-durable JSONL trace at ``path``: the meta line
        lands now, every span appends as it closes, and
        :meth:`write_artifacts` (or :meth:`StreamingTraceWriter.close`)
        seals it with the metric events.  A run killed in between leaves
        a truncated-but-well-formed prefix ``repro.telemetry.check
        --allow-partial`` accepts — instead of no trace at all."""
        self._stream = StreamingTraceWriter(path, registry=self.registry,
                                            tracer=self.tracer)
        if self.live is not None:
            self.live.writer = self._stream
        return self._stream

    def span(self, name: str, step: int | None = None, **attrs):
        return self.tracer.span(name, step, **attrs)

    def fence(self, value):
        return self.tracer.fence(value)

    # ------------------------------------------------------------- attach
    def attach_transport(self, transport) -> None:
        """Point a transport's ledger surfaces at this registry.

        Idempotent (re-attaching the same transport is a no-op) and
        backfilling: entries and DP releases booked *before* attach are
        folded in once, so attach order doesn't skew totals.  Budgeted
        entries carry the codec rung that priced them, so ``hops_by_rung``
        backfills too — a registry attached after traffic agrees with one
        attached before.
        """
        log = getattr(transport, "log", None)
        if log is None and hasattr(transport, "send_bits"):
            log = transport                  # a bare TransportLog
        if log is not None and \
                getattr(log, "registry", None) is not self.registry:
            for e in log.entries:
                self.registry.inc("wire_bits_total", e["bits"],
                                  kind=e["kind"], src=e["src"],
                                  dst=e["dst"])
                self.registry.inc("messages_total", 1, kind=e["kind"])
                if "rung" in e:
                    self.registry.inc("hops_by_rung_total", 1,
                                      rung=e["rung"])
            for link in getattr(transport, "skipped", ()):
                self.registry.inc("budget_skips_total", 1,
                                  src=link[0], dst=link[1])
            log.registry = self.registry
        accountant = getattr(transport, "accountant", None)
        if accountant is not None and \
                getattr(accountant, "registry", None) is not self.registry:
            for agent, count in accountant.releases.items():
                self.registry.inc("dp_releases_total", count, agent=agent)
            accountant.registry = self.registry

    def sync_gauges(self, transport=None) -> None:
        """Copy the state that isn't event-shaped into gauges — called at
        export time: the transport's budget state (per-link spent bits,
        the exhausted flag) and the process's compiled-program trace counts
        (``program_traces{program=...}``, from
        :data:`repro.core.compiled.TRACE_COUNTS`)."""
        from repro.core.compiled import TRACE_COUNTS
        for family, count in sorted(TRACE_COUNTS.items()):
            self.registry.set_gauge("program_traces", count, program=family)
        for (src, dst), bits in sorted(
                getattr(transport, "link_spent", {}).items()):
            self.registry.set_gauge("budget_link_spent_bits", bits,
                                    src=src, dst=dst)
        if hasattr(transport, "exhausted"):
            self.registry.set_gauge("budget_exhausted",
                                    int(transport.exhausted))

    # ------------------------------------------------------------- export
    def write_artifacts(self, *, trace: str | None = None,
                        metrics_out: str | None = None,
                        transport=None) -> None:
        """Write the requested artifacts (``--trace`` JSONL event log,
        ``--metrics-out`` JSON snapshot or ``.prom`` text)."""
        self.sync_gauges(transport)
        if trace:
            if self._stream is not None and self._stream.path == trace:
                # the run streamed here all along: seal with the metric
                # events rather than rewriting from scratch
                self._stream.close()
            else:
                write_trace(trace, registry=self.registry,
                            tracer=self.tracer)
        if metrics_out:
            write_metrics(metrics_out, self.registry, self.tracer)

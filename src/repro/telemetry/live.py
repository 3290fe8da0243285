"""In-flight metric emission from compiled programs (and eager twins).

The compiled backend books every metric *after* the run: its replay
(`Protocol._replay_traffic`, one `Transport.book_hop` per hop) walks the
scanned ledger once the scan has returned, so a long `fleet_run` or
`control_sweep_run` is a black box while it executes.
This module adds the live plane: tiny `jax.debug.callback` taps inside the
scanned round body (and the serve dispatch) stream per-round scalars to a
host-side :class:`LiveSink` *while the program runs* — feeding the same
:class:`~repro.telemetry.registry.MetricsRegistry`, the streaming JSONL
trace, and the terminal dashboard.

Zero-interference contract (pinned by tests/test_telemetry_live.py and
`benchmarks/telemetry_bench.py --live`):

  * **live-on == live-off bit-identical** — the taps read values the round
    body already computes and feed them to `jax.debug.callback`, which has
    no data-flow back into the program; posteriors/ledgers are unchanged.
  * **final live registry == replay-booked registry** — the per-round
    deltas are priced by the *same table* the replay walk books
    (`repro.comm.codecs.rung_bits`), so at
    program exit ``live_wire_bits_total == wire_bits_total``,
    ``live_messages_total{kind=ignorance} == messages_total{kind=
    ignorance}``, ``live_budget_skips_total == budget_skips_total``.
  * **eager == compiled** — eager paths call the sink directly with the
    same payloads, and every sink update is commutative (sums, max), so
    the two backends produce identical live series even though compiled
    taps may arrive unordered (``jax.debug.callback`` ordering is not
    guaranteed under ``vmap``).

Design notes the taps depend on:

  * Gating happens **host-side**: compiled taps always fire for every scan
    step (including rounds after early stop and batch pad slots) and carry
    an ``active`` flag; the sink drops inactive taps.  Branch-level gating
    via `lax.cond` is unsafe — under `vmap` a cond lowers to `select` and
    both branches execute.
  * Wall-clock time appears **only** in streamed trace events and the
    dashboard feed, never in the registry — registry equality across
    backends is a pinned invariant and timestamps would break it.
  * One live session at a time per sink: the module-level ``_SINK`` is the
    single routing point the compiled callbacks can reach (they close over
    nothing), installed around each compiled dispatch via
    :func:`installed` and called directly by eager paths.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

#: The active sink compiled-program callbacks route to.  Module-global on
#: purpose: `jax.debug.callback` payloads are staged at trace time and the
#: cached program must reach whatever sink the *current* run installed.
_SINK: "LiveSink | None" = None


@contextmanager
def installed(sink: "LiveSink | None"):
    """Route compiled-program taps to ``sink`` for the duration of the
    block (no-op when ``sink`` is None).  On exit, drains any callbacks
    still in flight (`jax.effects_barrier`) before restoring the previous
    sink, so a tap never lands on a dead run's sink."""
    global _SINK
    if sink is None:
        yield
        return
    prev = _SINK
    _SINK = sink
    try:
        yield
    finally:
        import jax
        try:
            jax.effects_barrier()
        finally:
            _SINK = prev


# ----------------------------------------------------- traced-side helpers
def _pack(*vals):
    """One int32 vector per tap: a single device->host transfer instead of
    one per scalar (per-buffer transfer overhead dominated tap cost)."""
    import jax.numpy as jnp
    return jnp.stack([jnp.asarray(v, jnp.int32) for v in vals])


def key_salt(key):
    """A zero that *depends* on the session PRNG key, added to one tap
    operand at every emit site.  Under ``vmap`` (``fleet_run``,
    ``serve_batch``) ``jax.debug.callback`` unrolls one call per batch
    element only for operands the batch axis actually reaches; with
    identical cohorts and a deterministic learner every metric operand can
    be batch-invariant, and a fully unbatched payload would collapse S
    sessions' taps into one.  The key is batched by construction, so the
    salt forces per-session delivery without changing any value."""
    import jax
    import jax.numpy as jnp
    return (jax.random.key_data(key).sum() * 0).astype(jnp.int32)


def emit_round(t, active, bits, sent, skipped, new_exh) -> None:
    """Stage a per-round progress tap inside traced code.  All arguments
    are scalar arrays the round body already computed; ``active`` is False
    for scan steps past the early-stop point (the sink drops them)."""
    import jax
    jax.debug.callback(_round_tap,
                       _pack(t, active, bits, sent, skipped, new_exh))


def emit_serve(active, bits, sent, skipped) -> None:
    """Stage a per-request serve tap inside traced code.  ``active`` is
    False for the batch-pad filler slots (deliver mask all-False)."""
    import jax
    jax.debug.callback(_serve_tap, _pack(active, bits, sent, skipped))


def _round_tap(packed) -> None:
    sink = _SINK
    if sink is not None:
        t, active, bits, sent, skipped, new_exh = (int(v) for v in packed)
        if active:
            sink.round_tap(t, bits, sent, skipped, new_exh)


def _serve_tap(packed) -> None:
    sink = _SINK
    if sink is not None:
        active, bits, sent, skipped = (int(v) for v in packed)
        if active:
            sink.serve_tap(bits, sent, skipped)


class LiveSink:
    """Host-side endpoint of the live taps: folds per-round deltas into
    the registry's ``live_*`` series, streams ``{"type": "live", ...}``
    events to the open JSONL trace, and notifies the dashboard hook.

    Every update is commutative over the tap multiset — counter sums and
    a running max for the round gauge — so unordered compiled delivery,
    eager sequential delivery, and vmapped fleet delivery all converge to
    the same registry.  The ``live_*`` prefix keeps the in-flight series
    disjoint from the replay-booked ones they must equal at exit.
    """

    def __init__(self, registry, writer=None, on_event=None) -> None:
        self.registry = registry
        #: open StreamingTraceWriter (set by Telemetry.stream_trace)
        self.writer = writer
        #: dashboard hook: called with each live event dict
        self.on_event = on_event
        self.taps = 0
        self._max_round = -1
        self._t0: float | None = None
        self._last_t: float | None = None

    # --------------------------------------------------------------- taps
    def round_tap(self, t: int, bits: int, sent: int, skipped: int,
                  new_exh: int) -> None:
        reg = self.registry
        reg.inc("live_rounds_total", 1)
        reg.inc("live_wire_bits_total", bits)
        reg.inc("live_messages_total", sent, kind="ignorance")
        reg.inc("live_budget_skips_total", skipped)
        reg.inc("live_exhausted_total", new_exh)
        self._max_round = max(self._max_round, t)
        reg.set_gauge("live_round", self._max_round)
        self._stamp({"type": "live", "tag": "round", "t": t, "bits": bits,
                     "sent": sent, "skipped": skipped,
                     "exhausted": new_exh})

    def serve_tap(self, bits: int, sent: int, skipped: int) -> None:
        reg = self.registry
        reg.inc("live_serve_requests_total", 1)
        reg.inc("live_wire_bits_total", bits)
        reg.inc("live_messages_total", sent, kind="score_block")
        reg.inc("live_budget_skips_total", skipped)
        self._stamp({"type": "live", "tag": "serve", "bits": bits,
                     "sent": sent, "skipped": skipped})

    def _stamp(self, event: dict) -> None:
        now = time.perf_counter()
        if self._t0 is None:
            self._t0 = now
        self._last_t = now
        self.taps += 1
        event["t_s"] = round(now - self._t0, 6)
        if self.writer is not None:
            self.writer.write_event(event)
        if self.on_event is not None:
            self.on_event(event)

    # -------------------------------------------------------------- reads
    def rate(self) -> float:
        """Taps per second over the sink's lifetime (0.0 before the second
        tap) — the dashboard's rounds/sec feed."""
        if self.taps < 2 or self._last_t is None or self._t0 is None:
            return 0.0
        elapsed = self._last_t - self._t0
        return (self.taps - 1) / elapsed if elapsed > 0 else 0.0

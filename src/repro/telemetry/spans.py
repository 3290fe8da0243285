"""Span tracing for protocol runs: fit -> session -> round -> hop on the
eager train path, fit -> {plan, session, extract, replay} on the compiled
one, flush -> flush_wave -> bucket_dispatch on the serve path.

A :class:`Span` is a closed wall-clock interval with a name, a parent, and
JSON-able attributes; the :class:`SpanTracer` maintains the open-span stack
(so nesting falls out of lexical scope), records every closed span, and
feeds per-span durations into the metrics registry as ``span_seconds``
histograms.

Two JIT-awareness knobs, both timing-only (numerics are never touched):

  * ``fence`` — :meth:`SpanTracer.fence` runs ``jax.block_until_ready`` on
    the value a dispatch boundary produced, so the enclosing span measures
    the *computation*, not the async-dispatch enqueue.  Callers place
    fences at dispatch boundaries only (the compiled session / serve-batch
    call sites); traced code never fences.
  * ``profile`` — spans additionally open ``jax.profiler``
    ``TraceAnnotation`` scopes (``StepTraceAnnotation`` when the span has a
    ``step``), so an XLA profile captured with ``jax.profiler.trace`` lines
    up with protocol rounds and flush waves.
"""
from __future__ import annotations

import time
from contextlib import ExitStack, contextmanager


class Span:
    """One closed (or still-open) traced interval."""

    __slots__ = ("span_id", "parent_id", "name", "start_s", "end_s", "attrs")

    def __init__(self, span_id: int, parent_id: int | None, name: str,
                 start_s: float, attrs: dict) -> None:
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start_s = start_s
        self.end_s: float | None = None
        self.attrs = attrs

    @property
    def duration_s(self) -> float | None:
        return None if self.end_s is None else self.end_s - self.start_s

    def to_event(self) -> dict:
        return {"type": "span", "id": self.span_id,
                "parent": self.parent_id, "name": self.name,
                "start_s": self.start_s, "end_s": self.end_s,
                "attrs": self.attrs}


class SpanTracer:
    """Open/close spans with automatic parenting; record them all.

    ``registry`` (optional) receives a ``span_seconds{name=...}`` histogram
    observation per closed span.  ``clock`` is injectable for tests.
    """

    def __init__(self, registry=None, *, profile: bool = False,
                 fence: bool = True, clock=time.perf_counter) -> None:
        self.registry = registry
        self.profile = profile
        self.fence_enabled = fence
        self.clock = clock
        self.spans: list[Span] = []
        self._stack: list[Span] = []
        self._next_id = 0
        # close hook: called with each span as it closes (streaming trace
        # export appends it to disk there, so a killed run keeps every
        # span that finished).  Observation only — never touches the span.
        self.on_close = None

    @property
    def current(self) -> Span | None:
        return self._stack[-1] if self._stack else None

    @contextmanager
    def span(self, name: str, step: int | None = None, **attrs):
        """Open a child of the current span for the ``with`` body."""
        parent = self._stack[-1].span_id if self._stack else None
        if step is not None:
            attrs = dict(attrs, step=int(step))
        sp = Span(self._next_id, parent, name, self.clock(), attrs)
        self._next_id += 1
        self.spans.append(sp)
        self._stack.append(sp)
        try:
            with ExitStack() as es:
                if self.profile:
                    import jax.profiler
                    if step is not None:
                        es.enter_context(jax.profiler.StepTraceAnnotation(
                            name, step_num=int(step)))
                    else:
                        es.enter_context(
                            jax.profiler.TraceAnnotation(name))
                yield sp
        finally:
            sp.end_s = self.clock()
            self._stack.pop()
            if self.registry is not None:
                self.registry.observe("span_seconds", sp.duration_s,
                                      name=name)
            if self.on_close is not None:
                self.on_close(sp)

    def fence(self, value):
        """Wall-clock fence at a dispatch boundary: block until ``value``'s
        arrays are ready (when fencing is on), then return it unchanged.
        Synchronization only — the value is never modified."""
        if self.fence_enabled and value is not None:
            import jax
            jax.block_until_ready(value)
        return value

    # ------------------------------------------------------------- readback
    def to_events(self) -> list[dict]:
        return [sp.to_event() for sp in self.spans]

    def well_formed(self) -> bool:
        """Every span closed, every parent id resolvable and opened before
        its child — the invariant the span-tree test pins."""
        by_id = {sp.span_id: sp for sp in self.spans}
        for sp in self.spans:
            if sp.end_s is None:
                return False
            if sp.parent_id is not None:
                parent = by_id.get(sp.parent_id)
                if parent is None or parent.start_s > sp.start_s:
                    return False
        return not self._stack

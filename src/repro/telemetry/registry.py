"""The unified metrics registry: one sink for every counter the system keeps.

ASCII's currencies — interchange bits, DP releases, budget skips, serve
admission outcomes — were tallied in four disjoint ad-hoc surfaces
(`TransportLog.bits_by_kind`, `AdmissionController` per-tenant ints,
batcher/cache counters, `PrivacyAccountant.releases`).  This registry is the
single store behind all of them: labeled counters, gauges, and histograms
with deterministic ordering, JSON-able event export, and exact integer
arithmetic for bit tallies.

Design constraints (the telemetry hard invariant):

  * **observation only** — the registry is written from host-side code that
    reads already-computed values (ledger bookings, replay walks, settle
    hooks).  It never folds PRNG keys, never adds device dispatches, and is
    never read by protocol logic, so telemetry-on and telemetry-off runs are
    bit-identical on every pinned trajectory.
  * **both backends, one layer** — emission hooks sit at the choke points
    both backends already share (`TransportLog.send_bits`,
    `PrivacyAccountant.record`, `BudgetedTransport.skip_hop`/
    `record_spend`, all reached through `Transport.book_hop`/`skip_hop`):
    eager paths emit live, the compiled backend emits during its post-run
    ledger replay, so eager and compiled runs produce identical registries
    wherever their ledgers already agree.
  * **cheap** — an increment is one dict update on a sorted-label key; no
    locks, no strings formatted until export.

Metric name conventions (see README "Observability" for the full table):
``*_total`` counters, ``*_bits``/``*_seconds`` units in the name, labels
for the dimension that varies (kind/src/dst/agent/tenant/rung/event).
"""
from __future__ import annotations

import bisect
import math

#: Fixed exponential histogram bucket bounds (powers of two, seconds-
#: oriented: ~1 microsecond to 32 seconds, plus a +Inf overflow bucket).
#: Fixed and global on purpose: every histogram in every run buckets
#: identically, so traces diff, registries from different processes merge,
#: and the validator needs no per-metric bound configuration.
BUCKET_BOUNDS: tuple = tuple(2.0 ** e for e in range(-20, 6))
NUM_BUCKETS = len(BUCKET_BOUNDS) + 1          # trailing +Inf bucket


def bucket_index(value: float) -> int:
    """The bucket a value lands in: smallest i with value <= BUCKET_BOUNDS
    [i] (Prometheus ``le`` semantics), NUM_BUCKETS-1 for the overflow."""
    return bisect.bisect_left(BUCKET_BOUNDS, value)


def quantile_estimate(agg: dict, q: float) -> float | None:
    """Estimate the q-quantile of one histogram aggregate from its bucket
    counts: find the bucket holding the target rank and interpolate
    linearly inside it (clamped to the observed [min, max], so single-
    bucket and overflow cases stay sane).  Returns None for an empty
    aggregate or a bucketless (schema-v1) one."""
    count = agg.get("count", 0)
    buckets = agg.get("buckets")
    if not count or not buckets:
        return None
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    rank = max(1, math.ceil(q * count))
    cum = 0
    for i, c in enumerate(buckets):
        cum += c
        if cum >= rank:
            lo = BUCKET_BOUNDS[i - 1] if i > 0 else agg["min"]
            hi = (BUCKET_BOUNDS[i] if i < len(BUCKET_BOUNDS)
                  else agg["max"])
            frac = (rank - (cum - c)) / c
            est = lo + (hi - lo) * frac
            return min(max(est, agg["min"]), agg["max"])
    return agg["max"]


def _label_key(labels: dict) -> tuple:
    """Canonical hashable key: sorted (name, value) pairs, values
    stringified once so ints/bools label identically to their str forms."""
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class MetricsRegistry:
    """Labeled counters, gauges, and histogram aggregates.

    A *series* is (metric name, label set); counters accumulate, gauges
    hold the last set value, histograms keep {count, sum, min, max} plus
    fixed exponential bucket counts (:data:`BUCKET_BOUNDS` — global, so
    no per-metric bound configuration can drift) from which
    :meth:`quantile` estimates percentiles to within one bucket.
    """

    def __init__(self) -> None:
        self._counters: dict[str, dict[tuple, int | float]] = {}
        self._gauges: dict[str, dict[tuple, float]] = {}
        self._hists: dict[str, dict[tuple, dict]] = {}

    # -------------------------------------------------------------- writes
    def inc(self, name: str, value: int | float = 1, /, **labels) -> None:
        if value < 0:
            raise ValueError(f"counter {name!r} increments must be >= 0, "
                             f"got {value}")
        series = self._counters.setdefault(name, {})
        key = _label_key(labels)
        series[key] = series.get(key, 0) + value

    def set_gauge(self, name: str, value: float, /, **labels) -> None:
        self._gauges.setdefault(name, {})[_label_key(labels)] = value

    def observe(self, name: str, value: float, /, **labels) -> None:
        series = self._hists.setdefault(name, {})
        key = _label_key(labels)
        agg = series.get(key)
        if agg is None:
            counts = [0] * NUM_BUCKETS
            counts[bucket_index(value)] = 1
            series[key] = {"count": 1, "sum": value, "min": value,
                           "max": value, "buckets": counts}
        else:
            agg["count"] += 1
            agg["sum"] += value
            agg["min"] = min(agg["min"], value)
            agg["max"] = max(agg["max"], value)
            counts = agg.get("buckets")
            if counts is not None:       # absent on reloaded v1 aggregates
                counts[bucket_index(value)] += 1

    # --------------------------------------------------------------- reads
    def value(self, name: str, /, **labels) -> int | float:
        """Counter value of one exact series (0 when never incremented)."""
        return self._counters.get(name, {}).get(_label_key(labels), 0)

    def gauge(self, name: str, /, **labels) -> float | None:
        return self._gauges.get(name, {}).get(_label_key(labels))

    def histogram(self, name: str, /, **labels) -> dict | None:
        agg = self._hists.get(name, {}).get(_label_key(labels))
        if agg is None:
            return None
        out = dict(agg)
        if "buckets" in out:
            out["buckets"] = list(out["buckets"])
        return out

    def quantile(self, name: str, q: float, /, **labels) -> float | None:
        """Estimated q-quantile of one exact histogram series (None when
        the series doesn't exist or carries no buckets).  Accurate to
        within one bucket of the exact percentile — the resolution the
        fixed exponential bounds buy."""
        agg = self._hists.get(name, {}).get(_label_key(labels))
        return None if agg is None else quantile_estimate(agg, q)

    def merged_histogram(self, name: str) -> dict | None:
        """One aggregate folding every label set of ``name`` together —
        the cross-tenant view ``quantile_all`` and the dashboard read."""
        series = self._hists.get(name)
        if not series:
            return None
        merged: dict | None = None
        for agg in series.values():
            if merged is None:
                merged = {"count": agg["count"], "sum": agg["sum"],
                          "min": agg["min"], "max": agg["max"],
                          "buckets": list(agg.get("buckets") or
                                          [0] * NUM_BUCKETS)}
            else:
                merged["count"] += agg["count"]
                merged["sum"] += agg["sum"]
                merged["min"] = min(merged["min"], agg["min"])
                merged["max"] = max(merged["max"], agg["max"])
                for i, c in enumerate(agg.get("buckets") or ()):
                    merged["buckets"][i] += c
        return merged

    def quantile_all(self, name: str, q: float) -> float | None:
        """Estimated q-quantile across every label set of ``name``."""
        merged = self.merged_histogram(name)
        return None if merged is None else quantile_estimate(merged, q)

    def total(self, name: str) -> int | float:
        """Counter total across every label set of ``name``."""
        return sum(self._counters.get(name, {}).values())

    def series(self, name: str) -> dict[tuple, int | float]:
        """{label-key tuple: value} for one counter, deterministically
        ordered — the raw readback the serve counters build on."""
        return dict(sorted(self._counters.get(name, {}).items()))

    def label_values(self, name: str, label: str) -> list[str]:
        """Distinct values of one label across a counter's series, sorted."""
        out = set()
        for key in self._counters.get(name, {}):
            for k, v in key:
                if k == label:
                    out.add(v)
        return sorted(out)

    def counter_names(self) -> list[str]:
        return sorted(self._counters)

    # -------------------------------------------------------------- events
    def to_events(self) -> list[dict]:
        """The registry as a deterministic list of JSON-able metric events —
        the JSONL trace payload, loss-free: ``from_events`` round-trips."""
        events: list[dict] = []
        for name in sorted(self._counters):
            for key, value in sorted(self._counters[name].items()):
                events.append({"type": "counter", "name": name,
                               "labels": dict(key), "value": value})
        for name in sorted(self._gauges):
            for key, value in sorted(self._gauges[name].items()):
                events.append({"type": "gauge", "name": name,
                               "labels": dict(key), "value": value})
        for name in sorted(self._hists):
            for key, agg in sorted(self._hists[name].items()):
                e = {"type": "histogram", "name": name,
                     "labels": dict(key), **agg}
                if "buckets" in e:
                    e["buckets"] = list(e["buckets"])
                events.append(e)
        return events

    @classmethod
    def from_events(cls, events: list[dict]) -> "MetricsRegistry":
        """Rebuild a registry from ``to_events`` output (JSONL reload)."""
        reg = cls()
        for e in events:
            kind = e.get("type")
            if kind == "counter":
                reg.inc(e["name"], e["value"], **e.get("labels", {}))
            elif kind == "gauge":
                reg.set_gauge(e["name"], e["value"], **e.get("labels", {}))
            elif kind == "histogram":
                series = reg._hists.setdefault(e["name"], {})
                agg = {"count": e["count"], "sum": e["sum"],
                       "min": e["min"], "max": e["max"]}
                if e.get("buckets") is not None:   # absent in v1 traces
                    agg["buckets"] = list(e["buckets"])
                series[_label_key(e.get("labels", {}))] = agg
        return reg

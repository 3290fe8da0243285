"""Shares the span and scope readers of ``bench/metrics/`` compute from a
run's record: a program span's time as a share of the ``fit`` spans', and
the device time of ops under a name scope as a share of all op time."""
from __future__ import annotations

import re


def span_share(rec: dict, name: str):
    """Σ ``name`` over Σ ``fit`` in the window's tracer spans, in %; None
    where the run has no ``fit`` span or no ``name`` span."""
    spans = rec.get("spans", ())
    fit = sum(d for n, d in spans if n == "fit")
    part = sum(d for n, d in spans if n == name)
    if fit <= 0 or part <= 0:
        return None
    return 100.0 * part / fit


def scope_share(rec: dict, pattern: str):
    """Device time of the traced window's leaf ops whose scope path (or
    name) matches ``pattern``, each clipped to the window as
    ``trace.reduce`` clips it, over all op time (Σ ``scope_s``), in %; None
    where no op matches."""
    tr = rec["trace"]
    total = sum(tr["scope_s"].values())
    match = re.compile(pattern)
    lo, hi = tr["lo"], tr["hi"]
    part = sum(min(o[1], hi) - max(o[0], lo) for o in tr["ops"]
               if min(o[1], hi) > max(o[0], lo)
               and (match.search(o[3]) or match.search(o[2]))) * 1e-9
    if total <= 0 or part <= 0:
        return None
    return 100.0 * part / total

"""A queue of ASCII training sessions, run back to back.

Each session is one ``Protocol.fit(backend="compiled")`` on the same cohort
with a fresh session key from the seed (``session_key``): a collaboration
working through its training sessions and replication runs.  The window
counts the sessions completed over the time they took, the host's ledger
replay and the building of the fitted ensemble included.

After the window, one session drawn from the seed is checked against the
plain reference (``bench/ref.py``) run on the same key: of the numbers
``session_numbers`` reads, those the configuration's ``limits`` name.
"""
from __future__ import annotations

import time
from contextlib import nullcontext

import jax
import numpy as np

from bench import data, program, ref

SESSIONS = 1          # fold_in tag of the session keys under the seed's key


def session_key(seed_key, i: int):
    """The key of the i-th session of the queue."""
    return jax.random.fold_in(jax.random.fold_in(seed_key, SESSIONS), i)


def annotate(name: str, on: bool):
    return jax.profiler.TraceAnnotation(name) if on else nullcontext()


class Traffic:
    def __init__(self, cell):
        self.cell = cell
        self.config = cell.config
        self.trace = cell.trace
        self.key = cell.key

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        self.Xtr, self.ctr, _, _ = data.make(
            self.config, jax.random.fold_in(self.key, 0), self.cell.seed)
        # one session on a key the window never uses compiles and loads
        # every program the window runs
        self._fit(jax.random.fold_in(self.key, 2), None)

    def _fit(self, key, tele):
        proto = program.protocol(self.config, telemetry=tele)
        with annotate("bench.session", self.trace):
            fitted = proto.fit(key, program.endpoints(self.config, self.Xtr),
                               self.ctr)
        return proto, fitted

    # ------------------------------------------------------------- window
    def window(self, seconds: float, tick=lambda: None) -> dict:
        self.tele = program.telemetry() if self.trace else None
        self.done = []
        t0 = time.perf_counter()
        end = t0
        while end - t0 < seconds:
            key = session_key(self.key, len(self.done))
            s = time.perf_counter()
            proto, fitted = self._fit(key, self.tele)
            end = time.perf_counter()
            self.done.append({
                "key": key, "fitted": fitted, "wall_s": end - s,
                "ledger": ledger(proto),
                "hops": [len(rec["alphas"]) for rec in fitted.history]})
            tick()
        self.elapsed = end - t0
        return {"sessions_per_s": len(self.done) / self.elapsed}

    def attempted(self) -> tuple[int, int]:
        return len(self.done), 0

    def record(self) -> dict:
        spans = self.tele.tracer.spans if self.tele is not None else []
        return {
            "n_train": int(self.ctr.shape[0]),
            "sessions": [{"wall_s": d["wall_s"], "hops": d["hops"]}
                         for d in self.done],
            "spans": [(sp.name, sp.duration_s) for sp in spans],
        }

    # -------------------------------------------------------------- check
    def release(self) -> None:
        self.tele = None

    def check(self) -> list:
        i = int(np.random.default_rng(self.cell.seed).integers(
            len(self.done)))
        done = self.done[i]
        got = program_result(done)
        want = reference_result(ref.session(done["key"], self.Xtr, self.ctr,
                                            self.config))
        return with_limits(
            session_numbers(got, want, self.config,
                            first_loss(self.config, self.Xtr, self.ctr)),
            self.config["limits"]["session"])


def ledger(proto) -> list:
    """The wire ledger of a finished session: (kind, bits) per message."""
    return [(e["kind"], e["bits"]) for e in proto.transport.log.entries]


def first_loss(config: dict, Xtr, ctr):
    """The first hop's objective (head agent, uniform weights) in the
    float32 reference's precision, as a function of fitted parameters."""
    lr = ref.learner_from(config)
    n = int(ctr.shape[0])
    onehot = jax.nn.one_hot(ctr, int(config["num_classes"]))
    w = jax.numpy.full((n,), 1.0 / n, jax.numpy.float32)
    return lambda params: lr.eval_loss(params, Xtr[0], onehot, w)


def with_limits(numbers: dict, limits: dict) -> list:
    return [(name, float(numbers[name]), float(limits[name]))
            for name in limits]


def program_result(done: dict) -> dict:
    """What the timed path produced: components and the wire ledger."""
    return {"components": [(c.agent, c.round, c.alpha, c.params)
                           for c in done["fitted"].components],
            "ledger": done["ledger"]}


def reference_result(sess) -> dict:
    return {"components": sess.components, "ledger": sess.ledger}


def worst_leaf_gap(got, want) -> float:
    """The largest norm of (got - want) over the leaves, each against the
    larger of its own reference norm and the median leaf's."""
    g = [np.asarray(x, np.float64) for x in jax.tree.leaves(got)]
    w = [np.asarray(x, np.float64) for x in jax.tree.leaves(want)]
    norms = [np.linalg.norm(x) for x in w]
    floor = float(np.median(norms))
    return max(float(np.linalg.norm(a - b)) / max(nb, floor, 1e-30)
               for a, b, nb in zip(g, w, norms))


# hops at the head of a session that ``alpha_gap_head`` and ``wire_head``
# compare, and never past the first round.  Beyond them the trajectories
# of two sound computations part: on the blob20 cohort rounding flips a
# few borderline rows and 120 hops of reweighting amplify it; on the
# Fashion halves the ignorance vector has gathered, after the first round,
# on the few hundred rows both agents missed, and whether the next MLP fits
# them outright (an alpha at the cap) turns on rounding
HEAD = 4


def session_numbers(got: dict, want: dict, config: dict, loss=None) -> dict:
    """What one session produced (``got``) against the reference
    (``want``): components as (agent, round, alpha, params) and the wire
    ledger.  ``loss(params)`` evaluates the first hop's loss in the
    reference's precision, for ``loss_gap``."""
    g_c, w_c = got["components"], want["components"]
    common = 0
    while (common < min(len(g_c), len(w_c))
           and g_c[common][:2] == w_c[common][:2]):
        common += 1
    # each alpha against the reference's, relative to max(1, |alpha|): the
    # hop's fit, the weighted accuracy it earns under the ignorance vector
    # the earlier hops' reweights and wire left, and eq. (13)
    gaps = [abs(g[2] - w[2]) / max(1.0, abs(w[2]))
            for g, w in zip(g_c[:common], w_c[:common])]
    hops = min(HEAD, len(config["splits"]))
    g_l, w_l = got["ledger"], want["ledger"]
    setup = sum(1 for kind, _ in w_l if kind in ("labels", "sample_ids"))
    head = setup + 2 * hops
    first = (abs(float(loss(g_c[0][3])) / float(loss(w_c[0][3])) - 1.0)
             if common and loss is not None else 1.0)
    return {
        "components_differ": max(len(g_c), len(w_c)) - common,
        "alpha_gap": max(gaps, default=0.0),
        "alpha_gap_head": max(gaps[:hops], default=0.0),
        "loss_gap": first,
        "param_gap": (worst_leaf_gap(g_c[0][3], w_c[0][3]) if common
                      else 1.0),
        "wire_mismatch": (sum(a != b for a, b in zip(g_l, w_l))
                          + abs(len(g_l) - len(w_l))),
        "wire_head": (sum(a != b for a, b in zip(g_l[:head], w_l[:head]))
                      + abs(len(g_l[:head]) - len(w_l[:head]))),
    }

"""Signal-adaptive codec controller: pick the rung per hop from the signal.

The ASCII interchange is bit-hungry exactly where it is most informative:
early rounds ship the hops that move the ignorance vector the most (and,
on concentrating cohorts, the highest-entropy vectors), while late rounds
ship a signal the receiver mostly already has — cheap to quantize
coarsely.  The fixed codec rung the comm subsystem spends per hop (PR 3/4)
is therefore wrong at both ends; :class:`AdaptiveController` replaces it
with a *policy*: observe a scalar statistic of the hop, smooth it with an
EMA, and map it through a descending threshold ladder to a codec rung —
high statistic buys fp32/fp16, a quiet signal degrades to int8/int4,
front-loading precision in the early rounds where the statistic is high.

Three statistics, all in [0, 1], higher = more precision:

  * ``"resid"`` (default) — the hop's *innovation*: the total-variation
    distance between the outgoing vector and the state the receiver
    already holds.  This is the quantization-relevant signal: a hop that
    barely moves the ignorance distribution (in the limit, a re-shipped
    uniform vector, which every integer codec reproduces exactly) needs no
    precision at all, while the large early-round updates are exactly
    where coarse rounding feeds visible error back into the next fit.
  * ``"entropy"`` — H(w)/log n of the outgoing vector: front-load
    precision while the ignorance mass is still spread wide, degrade as it
    collapses onto the few still-hard samples.
  * ``"l2"`` — the participation ratio 1/(n·Σw²), an L2 concentration
    measure (the cheap entropy surrogate).

Everything is a pure fixed-shape function of (w, ema), so the policy runs
identically on both engine backends:

  * eager — every transport routes rung choice through
    :func:`jitted_controller` (the cached-jit trick of
    ``comm.codecs.jitted_channel``, for the same last-ulp reason);
  * compiled — ``core.compiled.make_session_fn`` carries the EMA scalar in
    the ``lax.scan`` carry and computes the rung *branchlessly*
    (``sum(ema < thresholds)``) next to the budget ladder rule, so the
    whole adaptive session still lowers to one XLA program and
    ``quant_sweep_run``-style fleets still vmap.

Composition with a bit budget: the controller's rung is a *floor* on the
ladder index — the budget walk may degrade further (coarser) when bits run
low, never finer (``BudgetSpec.choose_costs(..., floor=rung)``).

The EMA is protocol state: it rides the scan carry (compiled), lives on the
transport between hops (eager), is snapshotted into ``SessionState.comm``
at checkpoint time, and is restored on resume — a resumed adaptive session
picks the exact rungs the uninterrupted one would have.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.comm.codecs import Codec, Fp16Codec, Fp32Codec, QuantCodec

#: Same rungs as ``comm.budget.DEFAULT_LADDER`` (best codec first), declared
#: from the codec classes directly so this module never imports the engine.
DEFAULT_LADDER = (Fp32Codec(), Fp16Codec(), QuantCodec(bits=8),
                  QuantCodec(bits=4))

STATS = ("resid", "entropy", "l2")

#: Per-statistic default threshold ladders for the 4-rung DEFAULT_LADDER
#: (descending; one cut per rung boundary).  The resid cuts are calibrated
#: so a quiet channel decays fp16 -> int8 -> int4 within a few hops while
#: any sustained innovation holds the fine rungs.
DEFAULT_THRESHOLDS = {
    "resid": (0.75, 0.3, 0.03),
    "entropy": (0.99, 0.85, 0.7),
    "l2": (0.99, 0.85, 0.7),
}


@dataclass(frozen=True)
class AdaptiveController:
    """Per-hop codec-rung policy over a degradation ladder.

    ``ladder`` is the codec rungs, best first (stateless codecs only — the
    same constraint as :class:`~repro.comm.budget.BudgetSpec`, and for the
    same reason: error-feedback residuals cannot migrate between rungs).
    ``thresholds`` is one descending cut per rung boundary
    (``len(ladder) - 1`` entries): the smoothed statistic at or above
    ``thresholds[0]`` ships rung 0, below ``thresholds[-1]`` ships the last
    rung; ``None`` picks the per-``stat`` default
    (:data:`DEFAULT_THRESHOLDS`, defined for the default 4-rung ladder).
    ``beta`` is the EMA smoothing (0 = react to the raw per-hop statistic;
    the EMA starts at 1.0 — assume maximal signal until observed otherwise,
    which is what front-loads precision).  ``stat`` picks the observed
    signal statistic (module docstring).

    Hashable frozen dataclass of pure functions: a valid jit static
    argument, rides ``SessionPlan`` and the session scan like a codec.
    """
    ladder: tuple = DEFAULT_LADDER
    thresholds: tuple | None = None
    beta: float = 0.5
    stat: str = "resid"

    def __post_init__(self):
        if not self.ladder:
            raise ValueError("controller ladder must hold at least one codec")
        for c in self.ladder:
            if not isinstance(c, Codec) or c.stateful:
                raise ValueError(
                    f"controller ladder entries must be stateless Codecs, "
                    f"got {c!r}")
        if self.stat not in STATS:
            raise ValueError(f"unknown stat {self.stat!r}; expected {STATS}")
        if self.thresholds is None:
            cuts = DEFAULT_THRESHOLDS[self.stat][:len(self.ladder) - 1]
            object.__setattr__(self, "thresholds", tuple(cuts))
        if len(self.thresholds) != len(self.ladder) - 1:
            raise ValueError(
                f"need len(ladder) - 1 = {len(self.ladder) - 1} thresholds "
                f"(one per rung boundary), got {len(self.thresholds)}")
        if list(self.thresholds) != sorted(self.thresholds, reverse=True):
            raise ValueError(
                f"thresholds must descend (rung 0 is the best codec), got "
                f"{self.thresholds}")
        if not 0.0 <= self.beta < 1.0:
            raise ValueError(f"need 0 <= beta < 1, got {self.beta}")

    def init_state(self) -> jnp.ndarray:
        """Fresh EMA state: 1.0 — assume a maximal signal until the channel
        shows otherwise (this is what front-loads precision in round 1)."""
        return jnp.ones((), jnp.float32)

    def observe(self, w_prev: jnp.ndarray,
                w_out: jnp.ndarray) -> jnp.ndarray:
        """The raw per-hop statistic, in [0, 1] (higher = finer rung).

        ``w_out`` is the outgoing (post-reweight) ignorance vector the hop
        encodes; ``w_prev`` the vector the receiver already holds (its
        stale state) — only ``"resid"`` reads it.
        """
        n = int(w_out.shape[0])
        p = w_out.astype(jnp.float32)
        p = p / jnp.maximum(jnp.sum(p), 1e-12)
        if self.stat == "resid":
            q = w_prev.astype(jnp.float32)
            q = q / jnp.maximum(jnp.sum(q), 1e-12)
            return 0.5 * jnp.sum(jnp.abs(p - q))     # total variation
        if self.stat == "entropy":
            h = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-30)),
                                   0.0))
            return h / math.log(max(n, 2))
        return 1.0 / (n * jnp.maximum(jnp.sum(p * p), 1e-12))

    def step(self, w_prev: jnp.ndarray, w_out: jnp.ndarray,
             ema: jnp.ndarray, cuts=None, beta=None):
        """One controller step: observe, smooth, pick the rung.

        Returns ``(rung int32, new_ema f32)``.  The rung computation is
        branchless — ``sum(ema < thresholds)`` counts how many boundaries
        the smoothed statistic has fallen below — so it traces into the
        compiled session scan with no control flow.

        ``cuts``/``beta`` optionally override the static ``thresholds`` /
        ``beta`` fields with *traced* operands (same shapes): the sweep
        program (``core.compiled.control_sweep_run``) vmaps one traced
        session over per-config threshold/beta arrays so N controller
        hyperparameters compile exactly once.  When traced and static
        values coincide the arithmetic is identical, so the override path
        stays bit-compatible with the static one.
        """
        s = self.observe(w_prev, w_out)
        b = self.beta if beta is None else beta
        ema = b * ema + (1.0 - b) * s
        c = jnp.asarray(self.thresholds if cuts is None else cuts,
                        jnp.float32)
        rung = jnp.sum((ema < c).astype(jnp.int32))
        return rung, ema


SERVE_STATS = ("margin", "entropy")

#: Per-statistic default threshold ladders for the 4-rung DEFAULT_LADDER on
#: the serve path (descending).  Margin-derived uncertainty on a trained
#: ensemble's score blocks concentrates low, so the cuts sit well below the
#: training-path resid cuts; an unsure block (many near-tied rows) buys
#: fp32/fp16, a confident one degrades to int8/int4 — coarse rounding
#: cannot flip an argmax that top-2 margins already separate.
DEFAULT_SERVE_THRESHOLDS = {
    "margin": (0.8, 0.5, 0.2),
    "entropy": (0.9, 0.6, 0.3),
}


@dataclass(frozen=True)
class ServeController:
    """Per-block codec-rung policy for prediction-time ScoreBlockMsg traffic.

    The training controller (:class:`AdaptiveController`) reads the hop
    innovation of the ignorance vector; serve traffic has no analogous
    recurrence — each [n, K] score block is an independent release — so the
    serve policy is *stateless*: observe one scalar uncertainty statistic of
    the outgoing block, map it through descending thresholds to a ladder
    rung.  Two statistics, both in [0, 1], higher = more precision:

      * ``"margin"`` (default) — 1 minus the mean per-row top-2 margin of
        the row-normalized block: near-tied votes (the rows where coarse
        quantization could flip the argmax) read as high uncertainty.
      * ``"entropy"`` — mean per-row entropy H(p)/log K of the normalized
        block: spread vote mass buys precision, collapsed mass degrades.

    Pure fixed-shape functions of the raw (pre-noise) block: the eager
    transports route through :func:`jitted_serve_controller`, the compiled
    serve step (``core.compiled.make_serve_fn``) embeds :meth:`rung_for`
    branchlessly — both backends pick identical rungs per block.  Under a
    bit budget the rung floors the degrade-then-skip ladder walk, exactly
    like the training controller (``BudgetSpec.choose_costs(floor=)``).
    """
    ladder: tuple = DEFAULT_LADDER
    thresholds: tuple | None = None
    stat: str = "margin"

    def __post_init__(self):
        if not self.ladder:
            raise ValueError("serve-controller ladder must hold at least "
                             "one codec")
        for c in self.ladder:
            if not isinstance(c, Codec) or c.stateful:
                raise ValueError(
                    f"serve-controller ladder entries must be stateless "
                    f"Codecs, got {c!r} (serve hops have no next call to "
                    f"defer error-feedback state to)")
        if self.stat not in SERVE_STATS:
            raise ValueError(f"unknown serve stat {self.stat!r}; expected "
                             f"{SERVE_STATS}")
        if self.thresholds is None:
            cuts = DEFAULT_SERVE_THRESHOLDS[self.stat][:len(self.ladder) - 1]
            object.__setattr__(self, "thresholds", tuple(cuts))
        if len(self.thresholds) != len(self.ladder) - 1:
            raise ValueError(
                f"need len(ladder) - 1 = {len(self.ladder) - 1} thresholds "
                f"(one per rung boundary), got {len(self.thresholds)}")
        if list(self.thresholds) != sorted(self.thresholds, reverse=True):
            raise ValueError(
                f"thresholds must descend (rung 0 is the best codec), got "
                f"{self.thresholds}")

    def observe(self, block: jnp.ndarray) -> jnp.ndarray:
        """The block's uncertainty statistic, in [0, 1] (higher = finer
        rung).  ``block`` is the raw outgoing [n, K] score block — observed
        before DP noise, so the policy reads the sender's own signal."""
        k = int(block.shape[-1])
        # row-normalize the coded-vote mass into a distribution: shift each
        # row to nonnegative (coded votes carry -1/(K-1) off-class terms),
        # then divide by the row sum
        b = block.astype(jnp.float32)
        b = b - jnp.min(b, axis=-1, keepdims=True)
        p = b / jnp.maximum(jnp.sum(b, axis=-1, keepdims=True), 1e-12)
        if self.stat == "margin":
            top2 = jax.lax.top_k(p, min(2, k))[0]
            gap = (top2[..., 0] - top2[..., 1]) if k > 1 \
                else jnp.ones(p.shape[:-1], jnp.float32)
            return 1.0 - jnp.mean(gap)
        h = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-30)),
                               0.0), axis=-1)
        return jnp.mean(h) / math.log(max(k, 2))

    def rung_for(self, block: jnp.ndarray) -> jnp.ndarray:
        """The chosen ladder rung (int32) for one outgoing block —
        branchless (``sum(stat < thresholds)``), so it traces into the
        compiled serve program with no control flow."""
        s = self.observe(block)
        cuts = jnp.asarray(self.thresholds, jnp.float32)
        return jnp.sum((s < cuts).astype(jnp.int32))


@functools.lru_cache(maxsize=64)
def jitted_serve_controller(controller: ServeController):
    """Cached jit of :meth:`ServeController.rung_for` — the eager
    ``Transport.serve_block`` routes rung choice through this so both
    backends run the exact same XLA computation (a last-ulp statistic
    difference at a threshold boundary would flip a rung)."""
    return jax.jit(controller.rung_for)


def controller_rung(controller: AdaptiveController, w_prev, w_out, ema):
    """Functional alias of :meth:`AdaptiveController.step` (sweep-friendly
    entry point for tests and benchmarks)."""
    return controller.step(w_prev, w_out, ema)


@functools.lru_cache(maxsize=64)
def jitted_controller(controller: AdaptiveController):
    """Cached jit of one controller step — the eager transports route rung
    choice through this so the eager engine runs the exact XLA computation
    the compiled session scan embeds (the ``jitted_channel`` discipline:
    op-by-op dispatch may fuse differently at the last ulp, and a last-ulp
    EMA difference at a threshold boundary would flip a rung)."""
    return jax.jit(functools.partial(controller_rung, controller))

"""One-program FedAvg: the homogeneous round as a ``lax.scan``.

ASCII's compiled backend (:mod:`repro.core.compiled`) cannot lower scenario
churn — the chain's *shape* changes per round.  FedAvg's round is
star-shaped and homogeneous, so churn is just a boolean participation mask
over fixed work: every roster slot fits every round, and non-participants
are masked out of the average.  That makes the whole session one scan over
the scenario's precomputed [T, M] mask, carrying the same spent-bit /
link-bit counters and the same noise-once-then-per-rung-codec channel
decomposition as the ASCII round body — and it is pinned bit-identical to
the eager :class:`~repro.scenarios.protocols.FedAvgVariant` loop
(tests/test_scenarios.py), skipped hops, exhaustion round, and all.

Key discipline mirrors the eager loop exactly: one split per roster slot
per *live* round (a round every participant churned out of — or one after
budget exhaustion — advances no PRNG state, because the eager engine never
enters ``run_round`` for it).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.codecs import rung_bits
from repro.core.compiled import (_INT32_MAX, check_caps, ladder_walk,
                                 rung_price, rung_select)
from repro.core.engine import setup_bits
from repro.scenarios.protocols import (fedavg_combine, fedavg_init_flat,
                                       fedavg_local_delta,
                                       fedavg_fit_weights, _param_template)


@dataclass(frozen=True)
class FedAvgPlan:
    """Everything static about one compiled FedAvg run — hashable, so it
    keys the cached program.  ``codec``/``privacy``/``budget`` are the
    *same* objects the eager transport holds (a budgeted plan nulls
    ``codec``: the ladder drives rung choice, as in ``plan_for``)."""
    core: object
    num_classes: int
    num_agents: int
    max_rounds: int
    server_lr: float = 1.0
    codec: object = None
    privacy: object = None
    budget: object = None

    def __post_init__(self):
        if self.budget is not None:
            object.__setattr__(self, "codec", None)

    @property
    def ladder(self) -> tuple:
        if self.budget is not None:
            return self.budget.ladder
        return (self.codec,)

    @property
    def has_channel(self) -> bool:
        return (self.codec is not None or self.privacy is not None
                or self.budget is not None)


class FedAvgResult(NamedTuple):
    """Everything the replay + history rebuild needs, all fixed-shape."""
    g: jnp.ndarray          # [d] final flat global params
    g_trace: jnp.ndarray    # [T, d] post-round global params
    executed: jnp.ndarray   # [T] bool: round entered (not yet stopped)
    sent: jnp.ndarray       # [T, M] bool: uplink actually crossed the wire
    codec_idx: jnp.ndarray  # [T, M] int32 ladder rung per uplink (-1 = none)
    exhausted: jnp.ndarray  # [] bool: session budget can't afford min rung


def make_fedavg_fn(plan: FedAvgPlan, feature_shape: tuple):
    """Lower ``plan`` into a pure callable

        fedavg_fn(key, Xs, classes, mask, fit_w) -> FedAvgResult

    — one ``lax.scan`` over rounds with the [T, M] participation mask as
    the scanned input, roster slots unrolled in the body.  ``fit_w`` is the
    [M, n] fit-weight table (non-IID shard masks ride it as data, so one
    program serves every scenario of the same shape)."""
    core = plan.core
    k = plan.num_classes
    num = plan.num_agents
    codec, privacy, budget = plan.codec, plan.privacy, plan.budget
    ladder = plan.ladder
    has_channel = plan.has_channel
    d, _ = _param_template(core, tuple(feature_shape))
    # one uplink's bits per rung: what the replay books for its GradientMsg
    uplink_bits = rung_bits(ladder, (d,))
    if budget is not None:
        check_caps(budget)
        if max(uplink_bits) >= _INT32_MAX:
            raise ValueError("uplink payload costs must fit int32")

    def fedavg_fn(key: jax.Array, Xs: tuple, classes: jnp.ndarray,
                  mask: jnp.ndarray, fit_w: jnp.ndarray) -> FedAvgResult:
        from repro.comm.codecs import channel_apply
        classes = classes.astype(jnp.int32)
        n = classes.shape[0]
        onehot = jax.nn.one_hot(classes, k)
        g0 = fedavg_init_flat(core, feature_shape, key)
        if budget is not None:
            costs = tuple(jnp.asarray(c, jnp.int32) for c in uplink_bits)
            min_cost = min(uplink_bits)
        # the server's raw fp32 broadcast of the new global model
        bcast_bits = rung_bits((None,), (d,))[0]

        def round_body(carry, mask_t):
            key, g, stopped = carry["key"], carry["g"], carry["stopped"]
            executed = jnp.logical_not(stopped)
            # a round all participants churned out of advances nothing —
            # the eager engine never enters run_round for it
            live = executed & jnp.any(mask_t)
            kj = key
            rows, pmask, sent_l, rung_l = [], [], [], []
            for j in range(num):
                kj, sub = jax.random.split(kj)
                part = mask_t[j] & live
                dflat = fedavg_local_delta(core, feature_shape, g, sub,
                                           Xs[j], onehot, fit_w[j])
                if j == 0:
                    # the server's own delta joins the average off-wire
                    rows.append(dflat)
                    pmask.append(part)
                    sent_l.append(jnp.zeros((), bool))
                    rung_l.append(jnp.asarray(-1, jnp.int32))
                    continue
                if not has_channel:
                    rows.append(dflat)
                    pmask.append(part)
                    sent_l.append(part)
                    rung_l.append(jnp.where(part, 0, -1).astype(jnp.int32))
                    continue
                # ---- the wire: budget rung choice, DP noise, codec — the
                # same walk and traced channel the eager Transport.ship runs
                if budget is not None:
                    rem = jnp.asarray(_INT32_MAX, jnp.int32)
                    if budget.session_bits is not None:
                        rem_s = (jnp.asarray(budget.session_bits, jnp.int32)
                                 - carry["spent"])
                        rem = jnp.minimum(rem, rem_s)
                    if budget.link_bits is not None:
                        rem = jnp.minimum(
                            rem, jnp.asarray(budget.link_bits, jnp.int32)
                            - carry["link"][j])
                    rung = ladder_walk(costs, rem)
                    sendable = rung >= 0
                else:
                    rung = jnp.asarray(0, jnp.int32)
                    sendable = jnp.ones((), bool)
                # privacy noise is rung-independent: apply once, then
                # codec-only roundtrips per rung — bit-identical to the
                # eager fused channel (keys fold from `sub` only)
                noised, _ = channel_apply(None, privacy, dflat, sub, None)
                pairs = [channel_apply(c, None, noised, sub, None)[0]
                         for c in ladder]
                d_hat = rung_select(rung, pairs, dflat)
                sent = part & sendable
                rows.append(jnp.where(sent, d_hat, dflat))
                pmask.append(sent)
                sent_l.append(sent)
                rung_l.append(jnp.where(sent, rung, -1))
                if budget is not None:
                    add = jnp.where(sent, rung_price(rung, costs), 0)
                    carry["spent"] = carry["spent"] + add
                    carry["link"] = carry["link"].at[j].add(add)
                    if budget.session_bits is not None:
                        carry["exhausted"] = carry["exhausted"] | (
                            part & (rem_s < min_cost))
            g_new = fedavg_combine(g, jnp.stack(rows), jnp.stack(pmask),
                                   plan.server_lr)
            g = jnp.where(live, g_new, g)
            if budget is not None:
                # raw broadcast to each participating client, counted
                # against the session cap (booked via transport.send in the
                # eager loop; links are never charged for the downlink)
                nb = jnp.sum(jnp.stack([mask_t[j] & live
                                        for j in range(1, num)]
                                       ).astype(jnp.int32))
                carry["spent"] = carry["spent"] + jnp.where(
                    live, nb * jnp.asarray(bcast_bits, jnp.int32), 0)
                if budget.session_bits is not None:
                    # the eager engine notices exhaustion at the *next*
                    # round's entry: this round finishes (broadcast and
                    # all), later ones never start
                    stopped = stopped | carry["exhausted"]
            # freeze the key stream on dead rounds (see module docstring)
            key = jax.random.wrap_key_data(jnp.where(
                live, jax.random.key_data(kj), jax.random.key_data(key)))
            carry = dict(carry, key=key, g=g, stopped=stopped)
            return carry, (g, executed, jnp.stack(sent_l),
                           jnp.stack(rung_l))

        init = {"key": key, "g": g0, "stopped": jnp.zeros((), bool)}
        if budget is not None:
            init["spent"] = jnp.asarray(setup_bits(num, n), jnp.int32)
            init["link"] = jnp.zeros((num,), jnp.int32)
            init["exhausted"] = jnp.zeros((), bool)
        fin, ys = jax.lax.scan(round_body, init,
                               mask.astype(bool), length=plan.max_rounds)
        return FedAvgResult(
            g=fin["g"], g_trace=ys[0], executed=ys[1], sent=ys[2],
            codec_idx=ys[3],
            exhausted=fin.get("exhausted", jnp.zeros((), bool)))

    return fedavg_fn


@functools.lru_cache(maxsize=64)
def _fedavg_program(plan: FedAvgPlan, feature_shape: tuple):
    return jax.jit(make_fedavg_fn(plan, feature_shape))


def fedavg_session(plan: FedAvgPlan, key: jax.Array,
                   Xs: Sequence[jnp.ndarray], classes: jnp.ndarray,
                   mask: jnp.ndarray, fit_w: jnp.ndarray) -> FedAvgResult:
    """Run one FedAvg session as a single compiled program (cached per
    (plan, feature shape)).  ``mask`` is the scenario's [max_rounds, M]
    participation schedule, ``fit_w`` the [M, n] fit-weight table."""
    Xs = tuple(jnp.asarray(x) for x in Xs)
    shapes = {tuple(x.shape[1:]) for x in Xs}
    if len(shapes) != 1:
        raise ValueError(f"fedavg needs one shared feature shape, got "
                         f"{sorted(shapes)}")
    mask = jnp.asarray(mask)
    if mask.shape != (plan.max_rounds, plan.num_agents):
        raise ValueError(
            f"participation mask shape {mask.shape} != "
            f"{(plan.max_rounds, plan.num_agents)}")
    return _fedavg_program(plan, shapes.pop())(key, Xs, classes, mask,
                                               fit_w)


__all__ = ["FedAvgPlan", "FedAvgResult", "fedavg_fit_weights",
           "fedavg_session", "make_fedavg_fn"]

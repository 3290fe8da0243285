"""Compiled interchange rounds: an entire ASCII session as one XLA program.

The eager engine (:mod:`repro.core.engine`) drives Algorithm 1 as a Python
host loop — one dispatch per weighted fit, per reward, per ignorance hop.
That is the right shape for heterogeneous eager learners (trees, forests)
and for transports that must observe every message, but it leaves the
hardware idle between dispatches.  The paper's round recurrence, however,
is fixed-shape:

    Algorithm 1, lines 3-11 (and its Section-IV M-agent chain):
      line 4/9  params_m = WST(X_m, y, w_t)            -> LearnerCore.fit
      line 5/9  r_i      = I{g_m(x_i) = y_i}           -> LearnerCore.predict
      line 5    alpha    = model_weight(w, r[, u])     -> scores.head_agent_
                                                          alpha / assistant_
                                                          alpha (eqs. 9/11/13)
      line 6/10 w_{t+1}  = reweight(w, r, alpha)       -> the fused Pallas
                                                          kernel (eqs. 10/12)

    so ``session_program`` lowers all rounds x all agents of that recurrence
    into a single ``lax.scan`` over rounds (agents unrolled inside the round
    body — their feature widths and learner cores differ, the round shape
    does not), and ``fleet_run`` vmaps the whole program over per-session
    PRNG keys (and optionally per-cohort data) so one compiled program
    serves many concurrent sessions.

The scan replicates the eager engine's semantics exactly — including the
alpha <= 0 early stop (Algorithm 1, line 8), which becomes a ``stopped``
mask that freezes the carried ignorance score — so ``backend="compiled"``
on :class:`repro.core.engine.Protocol` is pinned bit-for-bit against the
eager loop under sequential scheduling (tests/test_compiled.py).

Quickstart::

    cores = tuple(lr.core(num_classes) for lr in learners)
    plan = SessionPlan(cores=cores, num_classes=k, max_rounds=6)
    result = compiled_session(plan, jax.random.key(0), Xs, classes)
    fitted = fitted_from_result(plan, result, learners)    # FittedASCII

    keys = jax.random.split(jax.random.key(0), 32)         # 32 sessions,
    fleet = fleet_run(plan, keys, Xs, classes)             # one program
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scores

PyTree = Any


# ========================================================================= plan
@dataclass(frozen=True)
class SessionPlan:
    """The static half of a session: everything XLA needs at trace time.

    ``cores`` are the agents' :class:`~repro.learners.base.LearnerCore`
    contracts in chain order (hashable frozen dataclasses, so a plan is a
    valid jit static argument and programs cache per plan).  The remaining
    fields mirror :class:`repro.core.engine.SessionConfig`.
    """
    cores: tuple
    num_classes: int
    max_rounds: int = 20
    upstream: bool = True
    stop_on_negative_alpha: bool = True
    alpha_cap: float = 20.0
    exact_reweight: bool = False
    # Run eqs. (10)/(12) through the fused Pallas kernel
    # (kernels.ignorance.ignorance_update_unnormalized) at any score length;
    # False forces the plain jnp formula.  The kernel's lane-tiled
    # partial-sum reduction can differ from jnp.sum in the last ulp, which
    # is why Protocol._fit_compiled derives this flag from the transport
    # (kernel iff MeshRingTransport) instead of taking the default.
    use_kernel: bool = True
    # Pallas interpret-mode override for the kernel (None = resolve by
    # backend, like kernels.ops does) — threaded through from
    # MeshRingTransport.interpret so compiled runs execute the same kernel
    # mode the eager transport would.
    kernel_interpret: bool | None = None
    # The wire channel (repro.comm), all hashable frozen dataclasses:
    # ``codec`` encodes/decodes every shipped ignorance vector (the scan
    # carries per-link error-feedback residuals for stateful codecs),
    # ``privacy`` adds the DP Gaussian mechanism before encoding, and
    # ``budget`` replaces ``codec`` with its degradation ladder plus
    # spent-bit counters carried through the scan — the same
    # degrade-then-skip decision rule the eager BudgetedTransport applies,
    # so both backends pick identical codecs hop for hop.
    codec: Any = None
    privacy: Any = None
    budget: Any = None
    # Serve-path codec override: prediction-time ScoreBlockMsg traffic
    # (the traced serve step below) encodes with this codec when set, else
    # with ``codec`` — mirroring Transport.serve_codec.
    serve_codec: Any = None
    # Adaptive codec controller (repro.control.adaptive): a branchless
    # rung-index policy over its ladder, computed per hop from the carried
    # ignorance vector's entropy EMA (the EMA scalar rides the scan carry).
    # With a budget too, the controller's rung is a floor on the ladder
    # walk — the same composition rule the eager BudgetedTransport applies.
    controller: Any = None
    # Serve-path adaptive policy (repro.control.adaptive.ServeController):
    # picks the serve rung per [n, K] block from its observed statistic
    # (per-row margin or normalized entropy).  Stateless — serve hops are
    # independent, so no EMA rides the carry.  With a budget too, the
    # policy's rung floors the same ladder walk, mirroring the eager
    # BudgetedTransport.serve_block composition.
    serve_controller: Any = None
    # Round-ordering policy (repro.control.scheduler.BudgetAwarePlan): the
    # scan then re-permutes the agents each round by the carried
    # (spent bits, -reward EMA, id) key — the in-program twin of the eager
    # BudgetAwareScheduler.  Homogeneous fleets only (the permutation
    # gathers over stacked agent data).  None = fixed sequential chain.
    # An AsyncStalePlan here instead selects the stale-read barrier
    # lowering (make_async_session_fn).
    scheduler: Any = None

    @property
    def num_agents(self) -> int:
        return len(self.cores)

    @property
    def ladder(self) -> tuple:
        """The codec rungs the scan must evaluate: the budget (or adaptive
        controller) ladder, or the single configured codec (None rung =
        privacy-only channel)."""
        if self.budget is not None:
            return self.budget.ladder
        if self.controller is not None:
            return self.controller.ladder
        return (self.codec,)

    @property
    def serve_ladder(self) -> tuple:
        """The rungs the traced serve step evaluates for [n, K] score
        blocks: the budget ladder (== the serve controller's, when both are
        set), the serve controller's ladder, or the single serve codec
        (falling back to the training codec; a None rung ships raw
        fp32)."""
        if self.budget is not None:
            return self.budget.ladder
        if self.serve_controller is not None:
            return self.serve_controller.ladder
        return (self.serve_codec if self.serve_codec is not None
                else self.codec,)

    @property
    def has_channel(self) -> bool:
        return (self.codec is not None or self.privacy is not None
                or self.budget is not None or self.controller is not None)


class SessionResult(NamedTuple):
    """Fixed-shape output of one compiled session (vmap-friendly).

    ``alphas``/``accs`` are [T, M]; ``executed`` marks (round, agent) slots
    the eager loop would have reached, ``valid`` the subset that produced a
    boosting component (executed and not the alpha<=0 stop trigger);
    ``params`` is a length-M tuple of per-agent param pytrees with a
    leading round axis [T, ...]; ``w_trace`` is the post-hop ignorance
    score per slot [T, M, n] (what each IgnoranceMsg carried); ``w`` is the
    final ignorance score.

    Wire-channel bookkeeping (trivial when the plan has no channel):
    ``sent`` [T, M] marks hops whose score actually crossed the wire
    (``valid`` minus budget skips), ``codec_idx`` [T, M] the ladder rung it
    shipped with (-1 = not sent), and ``exhausted`` whether the session bit
    budget ran dry — together they let ``Protocol._fit_compiled`` replay the
    exact encoded-bit ledger the eager transport would have booked.

    Every per-slot array is *slot*-major: index j is the j-th agent visited
    that round.  ``order`` [T, M] maps slot back to agent id — identity
    rows under sequential plans, the in-scan budget-aware permutation
    otherwise (``agent_major_result`` re-collects a permuted result into
    agent-major order for the serve path).

    ``counts`` is a length-M tuple of per-slot dicts of int32 [T] work
    counts, what each hop's ``LearnerCore.fit_counted`` and
    ``predict_counted`` report (``{}`` for cores that count nothing);
    :func:`work_counts` sums them over the executed slots.
    """
    alphas: jnp.ndarray
    accs: jnp.ndarray
    executed: jnp.ndarray
    valid: jnp.ndarray
    params: tuple
    w_trace: jnp.ndarray
    w: jnp.ndarray
    sent: jnp.ndarray
    codec_idx: jnp.ndarray
    exhausted: jnp.ndarray
    order: jnp.ndarray = None
    counts: tuple = None


def work_counts(result: SessionResult) -> dict:
    """Each work count of a session summed over the (round, slot) hops it
    executed, as host ints (``{}`` where no core counts), with
    ``expert_tokens``, the fits' and predicts' routed pairs together."""
    if not result.counts or not any(result.counts):
        return {}
    executed, counts = jax.device_get((result.executed, result.counts))
    out: dict = {}
    for j, per_slot in enumerate(counts):
        for name, values in per_slot.items():
            out[name] = out.get(name, 0) + int(
                np.sum(np.asarray(values, np.int64)[executed[:, j]]))
    routed = [v for name, v in out.items()
              if name.startswith("expert_tokens_")]
    if routed:
        out["expert_tokens"] = sum(routed)
    return out


def plan_for(learners: Sequence, num_classes: int, *, max_rounds: int = 20,
             upstream: bool = True, stop_on_negative_alpha: bool = True,
             alpha_cap: float = 20.0, exact_reweight: bool = False,
             use_kernel: bool = True,
             kernel_interpret: bool | None = None,
             codec=None, privacy=None, budget=None,
             serve_codec=None, controller=None,
             serve_controller=None, scheduler=None) -> SessionPlan:
    """Build a SessionPlan from eager Learners (they must all be
    ``functional`` — have a LearnerCore)."""
    cores = []
    for m, lr in enumerate(learners):
        core = lr.core(num_classes)
        if core is None:
            raise ValueError(
                f"agent {m}: {type(lr).__name__} has no LearnerCore "
                f"(functional=False) — eager-only learners (tree/forest) "
                f"cannot ride the compiled backend")
        cores.append(core)
    if budget is not None or controller is not None:
        codec = None       # the budget/controller ladder drives codec choice
    if (budget is not None and serve_controller is not None
            and tuple(serve_controller.ladder) != tuple(budget.ladder)):
        raise ValueError(
            "a serve controller on a budgeted plan must share the budget's "
            f"ladder, got {serve_controller.ladder} vs {budget.ladder}")
    return SessionPlan(cores=tuple(cores), num_classes=num_classes,
                       max_rounds=max_rounds, upstream=upstream,
                       stop_on_negative_alpha=stop_on_negative_alpha,
                       alpha_cap=alpha_cap, exact_reweight=exact_reweight,
                       use_kernel=use_kernel,
                       kernel_interpret=kernel_interpret,
                       codec=codec, privacy=privacy, budget=budget,
                       serve_codec=serve_codec, controller=controller,
                       serve_controller=serve_controller,
                       scheduler=scheduler)


# ==================================================================== lowering
#: Trace-entry counters keyed by program family (``session``,
#: ``async_session``, ``serve``, ``serve_batch``, ``fleet``, ``sweep``,
#: ``sweep_serve``, ``control_sweep``, ``extract``, ``replay``; and, for a
#: learner core with a ``trace_family`` such as the neural backbone's,
#: ``<family>_fit`` and ``<family>_predict`` for each session program its
#: hops are traced into): one increment each time a family's program is
#: traced, none per call.  A correctly cached program traces once however
#: often it runs, and a sweep once however many configs it vmaps over;
#: ``Telemetry.sync_gauges`` exports the totals.
TRACE_COUNTS: dict = {}


def _counted(family: str, fn):
    """``fn`` counting its traces under ``family`` in :data:`TRACE_COUNTS`
    (what ``jax.jit`` is handed; its body runs at trace time only)."""
    @functools.wraps(fn)
    def traced(*args):
        TRACE_COUNTS[family] = TRACE_COUNTS.get(family, 0) + 1
        return fn(*args)
    return traced


def _make_reweight(plan: SessionPlan):
    """Pick the eqs.-(10)/(12) implementation: the exact reweight, the
    fused Pallas kernel (interpret mode off-TPU), or the pure-jnp
    formula."""
    if plan.exact_reweight:
        k = plan.num_classes
        return lambda w, r, a: scores.ignorance_update_exact(w, r, a, k)
    if plan.use_kernel:
        from repro.kernels import ops
        return lambda w, r, a: ops.ignorance_update(
            w, r, a, interpret=plan.kernel_interpret)
    return scores.ignorance_update


_INT32_MAX = np.iinfo(np.int32).max


def ladder_walk(costs, rem, floor=None):
    """Branchless degrade-then-skip ladder walk: the traced twin of
    :meth:`repro.comm.budget.BudgetSpec.choose_costs`.  ``costs`` are the
    static per-rung bit costs (ints or int32 scalars, best rung first),
    ``rem`` the remaining-budget int32 scalar, ``floor`` an optional
    controller rung the walk never goes finer than.  Returns the chosen
    rung as int32, -1 = skip.  Shared by the ASCII round body, the traced
    serve step, and the FedAvg lowering (repro.scenarios.compiled) so every
    budgeted program walks the one rule."""
    rung = jnp.asarray(-1, jnp.int32)
    for i in reversed(range(len(costs))):
        ok = jnp.asarray(costs[i], jnp.int32) <= rem
        if floor is not None:
            ok = ok & (jnp.asarray(i, jnp.int32) >= floor)
        rung = jnp.where(ok, jnp.asarray(i, jnp.int32), rung)
    return rung


def rung_price(rung, prices):
    """``prices[rung]`` as an int32 scalar, 0 at rung -1 (nothing shipped):
    the traced read of a per-rung price table
    (:func:`repro.comm.codecs.rung_bits`)."""
    return jnp.select([rung == i for i in range(len(prices))],
                      [jnp.asarray(c, jnp.int32) for c in prices],
                      jnp.asarray(0, jnp.int32))


def check_caps(budget) -> None:
    """Raise unless ``budget``'s caps fit the programs' int32 spent-bit
    counters."""
    for cap in (budget.session_bits, budget.link_bits):
        if cap is not None and cap >= _INT32_MAX:
            raise ValueError(f"budget caps must fit int32 (the scan's "
                             f"spent-bit counters), got {cap}")


def rung_select(rung, values, default):
    """Pick ``values[rung]`` (with ``default`` at rung -1) — the payload
    half of the ladder walk, single-rung ladders short-circuiting exactly
    like the inlined originals."""
    if len(values) == 1:
        return values[0]
    return jnp.select([rung == i for i in range(len(values))], values,
                      default)


def make_session_fn(plan: SessionPlan, feature_shapes: tuple,
                    qmax_arg: bool = False, control_arg: bool = False,
                    live: bool = False):
    """Lower ``plan`` for per-agent feature shapes into a pure callable

        session_fn(key, Xs, classes) -> SessionResult

    — a single ``lax.scan`` over interchange rounds, agents unrolled in the
    round body.  The callable is pure and fixed-shape, so it jits, vmaps
    (``fleet_run``) and shards like any other program.

    With a wire channel on the plan the scan additionally carries the
    per-link codec residuals and (under a budget) the spent-bit counters,
    reproducing the eager transports' channel hop for hop.  With a
    budget-aware ``plan.scheduler`` the scan also carries the per-agent
    spent-bit signal and reward EMAs and re-permutes the agents each round
    in-program (homogeneous fleets only) — the order the eager
    ``BudgetAwareScheduler`` would pick, bit for bit.

    ``qmax_arg`` re-parameterizes a QuantCodec plan's clipping level as a
    *traced* trailing argument ``session_fn(key, Xs, classes, qmax)`` so
    codec sweeps vmap into one program (:func:`quant_sweep_run`).
    ``control_arg`` instead re-parameterizes the *control plane* — adaptive
    controller thresholds/beta and budget session/link caps — as traced
    trailing arguments ``(cuts, beta, session_cap, link_cap)`` so
    controller/budget hyperparameter sweeps vmap into one program too
    (:func:`control_sweep_run`; ``_INT32_MAX`` caps mean "uncapped").

    ``live`` adds one :func:`repro.telemetry.live.emit_round` tap per scan
    step — round index, per-round priced bits (the same formulas the
    post-run replay books), sent/skipped hop counts, an exhaustion edge —
    with an ``active`` flag the host sink uses to drop post-stop rounds
    (`lax.cond` gating would break under vmap).  The tap has no data flow
    back into the program, so live programs stay bit-identical to dark
    ones; dark programs are byte-unchanged (the flag is a cache key).
    """
    if len(feature_shapes) != plan.num_agents:
        raise ValueError(f"{plan.num_agents} cores but "
                         f"{len(feature_shapes)} feature shapes")
    k = plan.num_classes
    cores = plan.cores
    codec, privacy, budget = plan.codec, plan.privacy, plan.budget
    controller = plan.controller
    ladder = plan.ladder
    has_channel = plan.has_channel
    stateful = codec is not None and codec.stateful
    if qmax_arg:
        from repro.comm.codecs import QuantCodec
        if budget is not None or controller is not None \
                or not isinstance(codec, QuantCodec):
            raise ValueError("qmax_arg sweeps need a plain QuantCodec plan")
    if control_arg:
        if qmax_arg:
            raise ValueError("qmax_arg and control_arg are separate sweep "
                             "modes; pick one")
        if budget is None and controller is None:
            raise ValueError("control_arg sweeps trace controller cuts/beta "
                             "and budget caps; the plan has neither")
    scheduler = plan.scheduler
    if scheduler is not None:
        from repro.control.scheduler import BudgetAwarePlan
        if not isinstance(scheduler, BudgetAwarePlan):
            raise ValueError(
                f"SessionPlan.scheduler must be a BudgetAwarePlan for the "
                f"sequential-scan lowering, got {type(scheduler).__name__} "
                f"(stale/async plans lower via make_async_session_fn)")
        if len(set(cores)) != 1 or len(set(feature_shapes)) != 1:
            raise ValueError(
                "budget-aware scheduling lowers into the scan only for "
                "homogeneous fleets (equal learner cores and feature "
                "shapes — the in-program round permutation gathers over "
                f"stacked agent data); got {len(set(cores))} distinct "
                f"cores and shapes {sorted(set(feature_shapes))}")
        if scheduler.spend_signal == "link" and budget is None:
            raise ValueError("spend_signal='link' orders by budgeted link "
                             "spend, but the plan has no budget")
    if budget is not None and not control_arg:
        check_caps(budget)
    num = plan.num_agents

    def session_fn(key: jax.Array, Xs: tuple, classes: jnp.ndarray,
                   qmax=None, cuts=None, beta=None, session_cap=None,
                   link_cap=None) -> SessionResult:
        from repro.comm.codecs import (MODEL_WEIGHT_BITS, channel_apply,
                                       rung_bits)
        from repro.core.engine import setup_bits
        classes = classes.astype(jnp.int32)
        n = classes.shape[0]
        onehot = jax.nn.one_hot(classes, k)
        reweight = _make_reweight(plan)
        w0 = scores.init_ignorance(n)
        ones = jnp.ones((n,), jnp.float32)
        # one shipped hop's bits per rung (the ignorance payload plus the
        # 32-bit ModelWeightMsg), the prices the replay books: the budget
        # walk, the "wire" ordering signal (what TransportLog.bits_by_src
        # tallies per sender) and the live counters all read this table,
        # so none can drift from the eager metered ledger
        hop_bits = rung_bits(ladder, n, MODEL_WEIGHT_BITS)
        if scheduler is not None:
            from repro.control.scheduler import (reward_ema_update,
                                                 traced_round_order)
            Xstack = jnp.stack(Xs)
        if budget is not None:
            costs = tuple(jnp.asarray(c, jnp.int32) for c in hop_bits)
            min_cost = min(hop_bits)
        if live:
            from repro.telemetry.live import emit_round, key_salt

        def round_body(carry, t_idx):
            w, key, stopped = carry["w"], carry["key"], carry["stopped"]
            u = ones
            outs = []
            if live:
                live_active = jnp.logical_not(stopped)
                live_entry_exh = carry.get("exhausted",
                                           jnp.zeros((), bool))
                live_bits = jnp.asarray(0, jnp.int32)
                live_sent = jnp.asarray(0, jnp.int32)
                live_skip = jnp.asarray(0, jnp.int32)
            if scheduler is not None:
                # the round permutation, from the carried signal — computed
                # at round entry exactly when the eager scheduler's
                # round_order reads its live transport state
                if scheduler.spend_signal == "link":
                    spent_sig = carry["link"].sum(axis=1, dtype=jnp.int32)
                elif scheduler.spend_signal == "wire":
                    spent_sig = carry["wire"]
                else:
                    spent_sig = jnp.zeros((num,), jnp.int32)
                ema_sig = (carry["ema"] if scheduler.use_reward
                           else jnp.zeros((num,), jnp.float32))
                perm = traced_round_order(spent_sig, ema_sig)
            # Agents unrolled: heterogeneous feature widths / cores, but a
            # fixed chain shape — exactly Algorithm 1's inner lines 3-11.
            # named_scope tags the HLO so profiler traces group ops by hop
            # (metadata only — the lowered computation is unchanged).
            for j, core in enumerate(cores):
                fit, predict = core.fit_counted, core.predict_counted
                if core.trace_family is not None:
                    fit = _counted(f"{core.trace_family}_fit", fit)
                    predict = _counted(f"{core.trace_family}_predict",
                                       predict)
                if scheduler is None:
                    src = j                       # slot j == agent j
                    X_j, shape_j = Xs[j], feature_shapes[j]
                else:
                    src = perm[j]                 # slot j's agent this round
                    dst_agent = perm[(j + 1) % num]
                    X_j, shape_j = Xstack[src], feature_shapes[0]
                with jax.named_scope(f"ascii_hop_{j}"):
                    key, sub = jax.random.split(key)
                    params, fit_counts = fit(core.init(sub, shape_j), sub,
                                             X_j, onehot, w)
                    pred, pred_counts = predict(params, X_j)
                    r = (pred == classes).astype(jnp.float32)
                    counts = {name: fit_counts.get(name, 0)
                              + pred_counts.get(name, 0)
                              for name in {**fit_counts, **pred_counts}}
                # eq. (13) weight, the stop rule, eqs. (10)/(12): a scope
                # of their own beside the hop's, as is the channel below
                with jax.named_scope(f"ascii_update_{j}"):
                    u_in = ones if (j == 0 or not plan.upstream) else u
                    a, rbar = scores.model_weight(w, r, k, u=u_in,
                                                  alpha_cap=plan.alpha_cap)
                    executed = jnp.logical_not(stopped)
                    if plan.stop_on_negative_alpha:
                        trigger = executed & (a <= 0)   # Algorithm 1, line 8
                    else:
                        trigger = jnp.zeros((), bool)
                    valid = executed & jnp.logical_not(trigger)
                    if scheduler is not None and scheduler.use_reward:
                        # the observed-reward EMA advances on every slot the
                        # eager loop reaches (observe runs before the stop
                        # check), through the shared f32 update
                        prev = carry["ema"][src]
                        upd = reward_ema_update(scheduler.reward_smoothing,
                                                prev, rbar,
                                                ~carry["seen"][src])
                        carry["ema"] = carry["ema"].at[src].set(
                            jnp.where(executed, upd, prev))
                        carry["seen"] = carry["seen"].at[src].set(
                            carry["seen"][src] | executed)
                    # Only a component-producing slot advances u and w — the
                    # eager loop breaks before touching them on a stop trigger,
                    # and never reaches them once stopped.
                    u = jnp.where(valid,
                                  scores.upstream_factor_update(u, a, r, k), u)
                    w_upd = reweight(w, r, a)

                if not has_channel:
                    sent = valid
                    rung = jnp.where(sent, 0, -1).astype(jnp.int32)
                    w = jnp.where(valid, w_upd, w)
                else:
                    with jax.named_scope(f"ascii_channel_{j}"):
                        # ---- the wire: controller/budget rung choice, DP
                        # noise, codec — the same decision rule and traced
                        # channel the eager transports run
                        # (Transport._controller_rung,
                        # BudgetSpec.choose_costs, channel_apply)
                        if controller is not None:
                            # branchless adaptive rung from (receiver's stale
                            # vector, outgoing vector); the EMA advances on
                            # every slot the eager loop reaches an interchange
                            # for.  cuts/beta are None outside control_arg
                            # sweeps — the controller then uses its static
                            # thresholds, unchanged bit for bit.
                            c_rung, ctrl_new = controller.step(w, w_upd,
                                                               carry["ctrl"],
                                                               cuts=cuts,
                                                               beta=beta)
                            carry["ctrl"] = jnp.where(valid, ctrl_new,
                                                      carry["ctrl"])
                        if budget is not None:
                            cap_session = (session_cap if control_arg
                                           else budget.session_bits)
                            cap_link = (link_cap if control_arg
                                        else budget.link_bits)
                            rem = jnp.asarray(_INT32_MAX, jnp.int32)
                            if cap_session is not None:
                                rem_s = (jnp.asarray(cap_session, jnp.int32)
                                         - carry["spent"])
                                rem = jnp.minimum(rem, rem_s)
                            if cap_link is not None:
                                link_spent_j = (carry["link"][src, dst_agent]
                                                if scheduler is not None
                                                else carry["link"][j])
                                rem = jnp.minimum(
                                    rem, jnp.asarray(cap_link, jnp.int32)
                                    - link_spent_j)
                            # the controller rung is a floor on the walk:
                            # never finer, budget may go coarser
                            rung = ladder_walk(
                                costs, rem, floor=(c_rung if controller
                                                   is not None else None))
                            sendable = rung >= 0
                        elif controller is not None:
                            rung = c_rung
                            sendable = jnp.ones((), bool)
                        else:
                            rung = jnp.asarray(0, jnp.int32)
                            sendable = jnp.ones((), bool)
                        state_j = carry["resid"][src] if stateful else None
                        # privacy noise is rung-independent (same key, same
                        # input): apply it once, then codec-only roundtrips per
                        # rung — the per-stage key folds inside channel_apply
                        # depend only on `sub`, so this decomposition is
                        # bit-identical to the eager fused channel
                        w_noised, _ = channel_apply(None, privacy, w_upd, sub,
                                                    None)
                        pairs = [channel_apply(c, None, w_noised, sub, state_j,
                                               qmax=qmax) for c in ladder]
                        w_chan = rung_select(rung, [p[0] for p in pairs],
                                             w_upd)
                        sent = valid & sendable
                        w = jnp.where(sent, w_chan, w)
                        if stateful:
                            # error-feedback residuals are per *sender* (the
                            # eager engine keys codec_state by src name)
                            carry["resid"] = carry["resid"].at[src].set(
                                jnp.where(sent, pairs[0][1], state_j))
                        if budget is not None:
                            add = jnp.where(sent, rung_price(rung, costs), 0)
                            carry["spent"] = carry["spent"] + add
                            if scheduler is not None:
                                carry["link"] = carry["link"].at[
                                    src, dst_agent].add(add)
                            else:
                                carry["link"] = carry["link"].at[j].add(add)
                            if cap_session is not None:
                                carry["exhausted"] = carry["exhausted"] | (
                                    valid & (rem_s < min_cost))
                        rung = jnp.where(sent, rung, -1)
                if scheduler is not None \
                        and scheduler.spend_signal == "wire":
                    # per-sender metered-ledger tally (ignorance wire bits
                    # + the 32-bit alpha message) for next round's ordering
                    carry["wire"] = carry["wire"].at[src].add(
                        jnp.where(sent, rung_price(rung, hop_bits), 0))
                if live:
                    live_sent = live_sent + jnp.where(sent, 1, 0)
                    live_skip = live_skip + jnp.where(
                        valid & jnp.logical_not(sent), 1, 0)
                    live_bits = live_bits + rung_price(rung, hop_bits)
                stopped = stopped | trigger
                outs.append((params, a, rbar, executed, valid, w, sent,
                             rung, jnp.asarray(src, jnp.int32), counts))
            if budget is not None \
                    and (control_arg or budget.session_bits is not None):
                # the eager engine notices exhaustion at the *next* round's
                # entry: the current round finishes, later ones never start
                stopped = stopped | carry["exhausted"]
            if live:
                new_exh = jnp.where(
                    carry.get("exhausted", jnp.zeros((), bool))
                    & jnp.logical_not(live_entry_exh), 1, 0)
                emit_round(t_idx, live_active,
                           live_bits + jnp.where(t_idx == 0,
                                                 setup_bits(num, n), 0)
                           + key_salt(key),
                           live_sent, live_skip, new_exh)
            carry = dict(carry, w=w, key=key, stopped=stopped)
            return carry, tuple(outs)

        init = {"w": w0, "key": key, "stopped": jnp.zeros((), bool)}
        if stateful:
            init["resid"] = jnp.zeros((num, n), jnp.float32)
        if controller is not None:
            init["ctrl"] = controller.init_state()
        if budget is not None:
            init["spent"] = jnp.asarray(setup_bits(num, n), jnp.int32)
            # per directed link under a permuting scheduler (any src->dst
            # pair can carry a hop), per chain slot otherwise
            init["link"] = (jnp.zeros((num, num), jnp.int32)
                            if scheduler is not None
                            else jnp.zeros((num,), jnp.int32))
            init["exhausted"] = jnp.zeros((), bool)
        if scheduler is not None:
            if scheduler.use_reward:
                init["ema"] = jnp.zeros((num,), jnp.float32)
                init["seen"] = jnp.zeros((num,), bool)
            if scheduler.spend_signal == "wire":
                init["wire"] = jnp.zeros((num,), jnp.int32)
        if live:
            # round indices as scan xs feed the taps; the dark program
            # keeps its byte-identical no-xs scan
            fin, ys = jax.lax.scan(round_body, init,
                                   jnp.arange(plan.max_rounds))
        else:
            fin, ys = jax.lax.scan(round_body, init, None,
                                   length=plan.max_rounds)
        return SessionResult(
            alphas=jnp.stack([y[1] for y in ys], axis=1),
            accs=jnp.stack([y[2] for y in ys], axis=1),
            executed=jnp.stack([y[3] for y in ys], axis=1),
            valid=jnp.stack([y[4] for y in ys], axis=1),
            params=tuple(y[0] for y in ys),
            w_trace=jnp.stack([y[5] for y in ys], axis=1),
            w=fin["w"],
            sent=jnp.stack([y[6] for y in ys], axis=1),
            codec_idx=jnp.stack([y[7] for y in ys], axis=1),
            exhausted=fin.get("exhausted", jnp.zeros((), bool)),
            order=jnp.stack([y[8] for y in ys], axis=1),
            counts=tuple(y[9] for y in ys))

    if control_arg:
        return (lambda key, Xs, classes, cuts, beta, session_cap, link_cap:
                session_fn(key, Xs, classes, None, cuts, beta, session_cap,
                           link_cap))
    if not qmax_arg:
        return lambda key, Xs, classes: session_fn(key, Xs, classes)
    return session_fn


@functools.lru_cache(maxsize=64)
def _session_program(plan: SessionPlan, feature_shapes: tuple,
                     live: bool = False):
    return jax.jit(_counted("session",
                            make_session_fn(plan, feature_shapes, live=live)))


def compiled_session(plan: SessionPlan, key: jax.Array,
                     Xs: Sequence[jnp.ndarray],
                     classes: jnp.ndarray, *,
                     live: bool = False) -> SessionResult:
    """Run one ASCII session as a single compiled program (cached per
    (plan, feature shapes, live))."""
    Xs = tuple(jnp.asarray(x) for x in Xs)
    shapes = tuple(x.shape[1:] for x in Xs)
    return _session_program(plan, shapes, live)(key, Xs, classes)


# ================================================================ async barrier
@dataclass(frozen=True)
class AsyncStalePlan:
    """Static (hashable) marker selecting the stale-read asynchronous
    lowering: rides ``SessionPlan.scheduler`` the way
    :class:`repro.control.scheduler.BudgetAwarePlan` does, and routes
    ``make_async_session_fn`` instead of the sequential scan.  Carries no
    knobs — clock skew comes from scenarios, which the compiled backend
    rejects."""


class AsyncSessionResult(NamedTuple):
    """Fixed-shape output of one compiled *asynchronous* session.

    ``alphas``/``accs``/``executed``/``valid``/``params`` are the async
    twins of :class:`SessionResult`'s fields, in agent-id order (the async
    barrier has no chain order; ``executed`` rows are all-True or
    all-False).  ``w_trace`` [T, M, n] holds the mid-merge snapshots the
    channel-less barrier's per-agent IgnoranceMsgs carry; ``w_bar`` [T, n]
    the per-round barrier release *as published* (post DP noise + codec —
    what the single barrier IgnoranceMsg ships when the plan has a
    channel); ``sent`` [T] whether the barrier actually released (budget
    skips False), ``codec_idx`` [T] the ladder rung it shipped at (-1 =
    raw / skipped), ``exhausted`` whether the session bit budget ran dry.
    """
    alphas: jnp.ndarray
    accs: jnp.ndarray
    executed: jnp.ndarray
    valid: jnp.ndarray
    params: tuple
    w_trace: jnp.ndarray
    w_bar: jnp.ndarray
    w: jnp.ndarray
    sent: jnp.ndarray
    codec_idx: jnp.ndarray
    exhausted: jnp.ndarray


def make_async_session_fn(plan: SessionPlan, feature_shapes: tuple,
                          live: bool = False):
    """Lower the stale-read asynchronous barrier (``AsyncStaleScheduler``)
    into a pure callable ``session_fn(key, Xs, classes) ->
    AsyncSessionResult`` — one ``lax.scan`` over barrier rounds.

    Each round replicates ``Session._step_stale`` exactly: every agent
    fits against the same round-t score (per-agent PRNG splits in id
    order), positive updates merge multiplicatively with 1/M damping in id
    order, and the merged score normalizes at the barrier.  With a wire
    channel the *release* is the channel point: one DP noise draw + codec
    encode per barrier (key split after the per-agent splits), and under a
    budget one session-level ladder walk over the bare payload costs —
    per-barrier metering, one ledger, instead of the per-hop fiction the
    eager path used to reject.  A skipped release leaves the published
    score stale, exactly like a skipped sequential hop.
    """
    if len(feature_shapes) != plan.num_agents:
        raise ValueError(f"{plan.num_agents} cores but "
                         f"{len(feature_shapes)} feature shapes")
    if plan.controller is not None:
        raise ValueError("adaptive controllers do not apply to the async "
                         "barrier (its EMA statistic is defined on per-hop "
                         "interchange, which the barrier path has none of)")
    k = plan.num_classes
    cores = plan.cores
    codec, privacy, budget = plan.codec, plan.privacy, plan.budget
    ladder = plan.ladder
    has_channel = plan.has_channel
    stateful = codec is not None and codec.stateful
    if budget is not None:
        check_caps(budget)
    num = plan.num_agents

    def session_fn(key: jax.Array, Xs: tuple,
                   classes: jnp.ndarray) -> AsyncSessionResult:
        from repro.comm.codecs import (MODEL_WEIGHT_BITS, channel_apply,
                                       rung_bits)
        from repro.core.engine import setup_bits
        classes = classes.astype(jnp.int32)
        n = classes.shape[0]
        onehot = jax.nn.one_hot(classes, k)
        w0 = scores.init_ignorance(n)
        # the barrier release's bits per rung (a channel-less plan's ladder
        # is the one raw rung): what the replay books for its IgnoranceMsg
        bar_bits = rung_bits(ladder, n)
        if budget is not None:
            costs = tuple(jnp.asarray(c, jnp.int32) for c in bar_bits)
            min_cost = min(bar_bits)
        if live:
            from repro.telemetry.live import emit_round, key_salt

        def round_body(carry, t_idx):
            w, key, stopped = carry["w"], carry["key"], carry["stopped"]
            executed = jnp.logical_not(stopped)
            if live:
                live_entry_exh = carry.get("exhausted",
                                           jnp.zeros((), bool))
            fits = []
            # stale reads: every agent fits against the same round-t score,
            # per-agent key splits in id order (the eager fits loop)
            for j, core in enumerate(cores):
                with jax.named_scope(f"ascii_async_fit_{j}"):
                    key, sub = jax.random.split(key)
                    params = core.fit(core.init(sub, feature_shapes[j]),
                                      sub, Xs[j], onehot, w)
                    r = (core.predict(params, Xs[j]) == classes
                         ).astype(jnp.float32)
                a, rbar = scores.model_weight(w, r, k,
                                              alpha_cap=plan.alpha_cap)
                fits.append((params, r, a, rbar))
            # damped multiplicative merge at the barrier, agent-id order
            w_next = w
            any_pos = jnp.zeros((), bool)
            pos_count = jnp.asarray(0, jnp.int32)
            snaps = []
            for params, r, a, rbar in fits:
                use = executed & (a > 0)
                any_pos = any_pos | use
                pos_count = pos_count + jnp.where(use, 1, 0)
                w_next = jnp.where(use,
                                   w_next * jnp.exp((a / num) * (1.0 - r)),
                                   w_next)
                snaps.append(w_next)
            w_bar = w_next / jnp.maximum(jnp.sum(w_next), 1e-12)
            if not has_channel:
                released = w_bar
                sent = executed
                rung = jnp.asarray(-1, jnp.int32)
                w = jnp.where(executed, w_bar, w)
            else:
                # per-barrier release: DP noise + codec encode happen at
                # merge time, once per round — key split *after* the
                # per-agent fit splits, like the eager barrier
                key, kbar = jax.random.split(key)
                if budget is not None:
                    # the raw alpha messages book before the walk reads
                    # the ledger (the eager merge loop sends them first);
                    # link caps don't apply — the barrier is session-level
                    carry["spent"] = (carry["spent"]
                                      + MODEL_WEIGHT_BITS * pos_count)
                    rem_s = jnp.asarray(_INT32_MAX, jnp.int32)
                    if budget.session_bits is not None:
                        rem_s = (jnp.asarray(budget.session_bits, jnp.int32)
                                 - carry["spent"])
                    rung = ladder_walk(costs, rem_s)
                    sendable = rung >= 0
                    if budget.session_bits is not None:
                        carry["exhausted"] = carry["exhausted"] | (
                            executed & (rem_s < min_cost))
                else:
                    rung = jnp.asarray(0, jnp.int32)
                    sendable = jnp.ones((), bool)
                state = carry["resid"] if stateful else None
                # noise once (rung-independent), then codec-only
                # roundtrips per rung — bit-identical to the eager fused
                # channel (see the sequential round_body note)
                noised, _ = channel_apply(None, privacy, w_bar, kbar, None)
                pairs = [channel_apply(c, None, noised, kbar, state)
                         for c in ladder]
                released = rung_select(rung, [p[0] for p in pairs], w_bar)
                sent = executed & sendable
                w = jnp.where(sent, released, w)
                if stateful:
                    carry["resid"] = jnp.where(sent, pairs[0][1], state)
                if budget is not None:
                    carry["spent"] = carry["spent"] + jnp.where(
                        sent, rung_price(rung, costs), 0)
                rung = jnp.where(sent, rung, -1)
            if plan.stop_on_negative_alpha:
                stopped = stopped | (executed & jnp.logical_not(any_pos))
            if budget is not None and budget.session_bits is not None:
                stopped = stopped | carry["exhausted"]
            if live:
                if not has_channel:
                    # per positive agent: raw IgnoranceMsg + alpha message
                    live_bits = pos_count * jnp.asarray(
                        bar_bits[0] + MODEL_WEIGHT_BITS, jnp.int32)
                    live_ign = pos_count
                    live_skip = jnp.asarray(0, jnp.int32)
                else:
                    # raw alpha messages per positive agent + the single
                    # barrier release at its priced rung
                    live_bits = (MODEL_WEIGHT_BITS * pos_count
                                 + rung_price(rung, bar_bits))
                    live_ign = jnp.where(sent, 1, 0)
                    live_skip = (jnp.where(executed
                                           & jnp.logical_not(sent), 1, 0)
                                 if budget is not None
                                 else jnp.asarray(0, jnp.int32))
                new_exh = jnp.where(
                    carry.get("exhausted", jnp.zeros((), bool))
                    & jnp.logical_not(live_entry_exh), 1, 0)
                emit_round(t_idx, executed,
                           live_bits + jnp.where(t_idx == 0,
                                                 setup_bits(num, n), 0)
                           + key_salt(key),
                           live_ign, live_skip, new_exh)
            carry = dict(carry, w=w, key=key, stopped=stopped)
            outs = tuple(
                (params, a, rbar, executed, executed & (a > 0), snaps[j])
                for j, (params, r, a, rbar) in enumerate(fits))
            return carry, (outs, released, sent, rung)

        init = {"w": w0, "key": key, "stopped": jnp.zeros((), bool)}
        if stateful:
            init["resid"] = jnp.zeros((n,), jnp.float32)
        if budget is not None:
            init["spent"] = jnp.asarray(setup_bits(num, n), jnp.int32)
            init["exhausted"] = jnp.zeros((), bool)
        if live:
            fin, (ys, w_bars, sents, rungs) = jax.lax.scan(
                round_body, init, jnp.arange(plan.max_rounds))
        else:
            fin, (ys, w_bars, sents, rungs) = jax.lax.scan(
                round_body, init, None, length=plan.max_rounds)
        return AsyncSessionResult(
            alphas=jnp.stack([y[1] for y in ys], axis=1),
            accs=jnp.stack([y[2] for y in ys], axis=1),
            executed=jnp.stack([y[3] for y in ys], axis=1),
            valid=jnp.stack([y[4] for y in ys], axis=1),
            params=tuple(y[0] for y in ys),
            w_trace=jnp.stack([y[5] for y in ys], axis=1),
            w_bar=w_bars,
            w=fin["w"],
            sent=sents,
            codec_idx=rungs,
            exhausted=fin.get("exhausted", jnp.zeros((), bool)))

    return session_fn


@functools.lru_cache(maxsize=64)
def _async_session_program(plan: SessionPlan, feature_shapes: tuple,
                           live: bool = False):
    return jax.jit(_counted("async_session", make_async_session_fn(
        plan, feature_shapes, live=live)))


def async_session(plan: SessionPlan, key: jax.Array,
                  Xs: Sequence[jnp.ndarray],
                  classes: jnp.ndarray, *,
                  live: bool = False) -> AsyncSessionResult:
    """Run one stale-read asynchronous session as a single compiled program
    (cached per (plan, feature shapes, live))."""
    Xs = tuple(jnp.asarray(x) for x in Xs)
    shapes = tuple(x.shape[1:] for x in Xs)
    return _async_session_program(plan, shapes, live)(key, Xs, classes)


def fitted_from_async_result(plan: SessionPlan, result: AsyncSessionResult,
                             learners: Sequence):
    """Rebuild the eager engine's result objects from a compiled async run
    — byte-compatible with the eager ``_step_stale`` session's
    ``fitted()``.  Agent-major throughout (the barrier has no chain order);
    every executed round records all M alphas/accs, components come from
    the positive-alpha subset in id order, their parameters from one
    launch of the extraction program (:func:`extract_params`)."""
    from repro.core.engine import Component, FittedASCII

    alphas, accs, executed, valid = jax.device_get(
        (result.alphas, result.accs, result.executed, result.valid))
    params = extract_params(result.params)
    components, history = [], []
    for t in range(plan.max_rounds):
        if not executed[t].any():
            break                        # the eager loop stopped before t
        rec = {"round": t,
               "alphas": [float(a) for a in alphas[t]],
               "accs": [float(a) for a in accs[t]]}
        for m in range(plan.num_agents):
            if valid[t, m]:
                components.append(Component(m, t, float(alphas[t, m]),
                                            params[m][t]))
        history.append(rec)
    return FittedASCII(components, list(learners), plan.num_classes, history)


# ======================================================================== fleet
@functools.lru_cache(maxsize=64)
def _fleet_program(plan: SessionPlan, feature_shapes: tuple,
                   data_batched: bool, axis_name: str | None,
                   live: bool = False):
    fn = make_session_fn(plan, feature_shapes, live=live)
    data_ax = 0 if data_batched else None
    vf = jax.vmap(fn, in_axes=(0, data_ax, data_ax))
    if axis_name is None:
        return jax.jit(_counted("fleet", vf))

    P = jax.sharding.PartitionSpec

    def sharded(keys, Xs, classes):
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()), (axis_name,))
        spec_b = P(axis_name)
        spec_data = spec_b if data_batched else P()
        in_specs = (spec_b, tuple(spec_data for _ in Xs), spec_data)
        out_specs = jax.tree.map(lambda _: spec_b,
                                 jax.eval_shape(vf, keys, Xs, classes))
        return jax.shard_map(vf, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs,
                             check_vma=False)(keys, Xs, classes)

    return jax.jit(_counted("fleet", sharded))


def fleet_run(plan: SessionPlan, keys: jax.Array, Xs: Sequence[jnp.ndarray],
              classes: jnp.ndarray, *, data_batched: bool = False,
              shard_axis: str | None = None,
              live: bool = False) -> SessionResult:
    """Run a whole fleet of sessions as one vmapped compiled program.

    ``keys`` is [S] session PRNG keys.  With ``data_batched=False`` every
    session sees the same (Xs, classes) cohort (seed fleets, e.g. paper
    replication sweeps); with True, ``Xs[m]`` is [S, n, p_m] and ``classes``
    [S, n] — one cohort per session.  ``shard_axis`` optionally shard_maps
    the session axis across all local devices (the engine mesh's data axis)
    so fleets scale past one chip; the device count must then divide S
    evenly.  Returns a SessionResult with a leading session axis.

    ``live`` streams one progress tap per (session, round) to the
    installed :class:`~repro.telemetry.live.LiveSink` while the fleet
    executes — the vmap unrolls the callback per session, each tap
    carrying that session's unbatched scalars.  Local fleets only
    (``shard_axis`` callbacks are not supported).
    """
    if live and shard_axis is not None:
        raise ValueError("live emission does not compose with shard_map "
                         "fleets — run --watch fleets unsharded")
    Xs = tuple(jnp.asarray(x) for x in Xs)
    shapes = tuple(x.shape[2:] if data_batched else x.shape[1:] for x in Xs)
    return _fleet_program(plan, shapes, data_batched, shard_axis, live)(
        keys, Xs, classes)


# =================================================================== serve step
class ServeResult(NamedTuple):
    """Fixed-shape output of the traced distributed-prediction step.

    ``preds`` [n] is the head agent's argmax; ``blocks`` [M, n, K] the
    decoded per-agent score blocks as shipped (slot 0 = the head's own raw
    block, which never crosses the wire); ``sent`` [M] marks blocks that
    actually shipped (head False; budget skips False), ``codec_idx`` [M]
    the serve-ladder rung each shipped with (-1 = raw / not sent), and
    ``exhausted`` whether the session bit budget died mid-predict —
    together they let ``Protocol._replay_serve`` book a byte-identical
    serve ledger.
    """
    preds: jnp.ndarray
    blocks: jnp.ndarray
    sent: jnp.ndarray
    codec_idx: jnp.ndarray
    exhausted: jnp.ndarray


def make_serve_fn(plan: SessionPlan, feature_shapes: tuple,
                  qmax_arg: bool = False, live: bool = False):
    """Lower ``plan``'s serve path into a pure callable

        serve_fn(key, Xs, params, alphas, valid, rem_session, rem_link,
                 deliver) -> ServeResult

    — the traced twin of ``Session.predict_distributed``.  Each agent's
    [n, K] block is its alpha-weighted coded votes over its own components,
    accumulated by a ``lax.scan`` over rounds so float addition order
    matches the eager ``AgentEndpoint.score_block`` bit for bit; non-head
    blocks then cross the serve channel — DP noise, adaptive/budget rung
    choice via the same rules the eager transports apply, codec roundtrip —
    before the head sums and argmaxes.  ``rem_session`` / ``rem_link`` [M]
    are the remaining-budget counters (int32) the walk starts from; ignored
    by unbudgeted plans.  ``deliver`` [M] bool gates which non-head blocks
    cross the wire at all: a False slot contributes nothing, books no bits
    and records no release — the serve engine's degrade-to-head-only
    admission outcome (``deliver = [True, False, ...]``); all-True is a
    normal serve.  ``qmax_arg`` re-parameterizes a QuantCodec serve
    channel's clipping level as a traced trailing argument for codec sweeps
    (:func:`quant_sweep_run`).
    """
    if len(feature_shapes) != plan.num_agents:
        raise ValueError(f"{plan.num_agents} cores but "
                         f"{len(feature_shapes)} feature shapes")
    from repro.core.encoding import encode_labels
    k = plan.num_classes
    cores = plan.cores
    privacy, budget = plan.privacy, plan.budget
    serve_controller = plan.serve_controller
    ladder = plan.serve_ladder
    if qmax_arg:
        from repro.comm.codecs import QuantCodec
        if budget is not None or serve_controller is not None \
                or not isinstance(ladder[0], QuantCodec):
            raise ValueError("qmax_arg sweeps need a plain QuantCodec plan")

    def serve_fn(key, Xs, params, alphas, valid, rem_session, rem_link,
                 deliver, qmax=None) -> ServeResult:
        from repro.comm.codecs import channel_apply, rung_bits
        n = int(Xs[0].shape[0])
        shape = (n, k)
        # one shipped block's bits per rung: what the replay books for its
        # ScoreBlockMsg, and what the budget walk and live counters read
        costs = rung_bits(ladder, shape)
        if budget is not None:
            if max(costs) >= _INT32_MAX:
                raise ValueError(f"serve block costs must fit int32 (the "
                                 f"budget counters), got {max(costs)}")
            min_cost = min(costs)
            rem_s = jnp.asarray(rem_session, jnp.int32)
        deliver = jnp.asarray(deliver, bool)
        if live:
            from repro.telemetry.live import emit_serve, key_salt
            live_bits = jnp.asarray(0, jnp.int32)
            live_sent = jnp.asarray(0, jnp.int32)
            live_skip = jnp.asarray(0, jnp.int32)
        total = None
        blocks, sent_l, rung_l = [], [], []
        exhausted = jnp.zeros((), bool)
        for j, core in enumerate(cores):
            X = Xs[j]
            a_j = alphas[:, j].astype(jnp.float32)
            v_j = valid[:, j]

            def body(acc, sl, _core=core, _X=X):
                p, a, v = sl
                pred = _core.predict(p, _X)
                return acc + jnp.where(v, a, 0.0) * encode_labels(pred, k), None

            # named_scope tags the HLO per serve block for profiler traces
            # (metadata only — the lowered computation is unchanged)
            with jax.named_scope(f"serve_block_{j}"):
                block, _ = jax.lax.scan(
                    body, jnp.zeros((n, k), jnp.float32),
                    (params[j], a_j, v_j))
            if j == 0:
                # the head agent's own block never crosses the wire
                blocks.append(block)
                sent_l.append(jnp.zeros((), bool))
                rung_l.append(jnp.asarray(-1, jnp.int32))
                total = block
                continue
            d_j = deliver[j]
            sub = jax.random.fold_in(key, j)
            if serve_controller is not None:
                # the policy reads the *raw* pre-noise block, exactly like
                # the eager transports (serve_block observes before the
                # channel applies)
                c_rung = serve_controller.rung_for(block)
            if budget is not None:
                # privacy noise is rung-independent: apply once, then
                # codec-only roundtrips per rung — bit-identical to the
                # eager fused channel (see the round_body note above)
                noised, _ = channel_apply(None, privacy, block, sub, None)
                rem = jnp.minimum(rem_s, rem_link[j])
                # the policy rung floors the walk (budget may still degrade
                # coarser, never finer) — same composition as
                # BudgetedTransport.serve_block
                rung = ladder_walk(
                    costs, rem,
                    floor=c_rung if serve_controller is not None else None)
                sendable = (rung >= 0) & d_j
                # an undelivered block never consults the budget, so it
                # cannot flip exhaustion (eager head-only degrade skips the
                # serve hop entirely)
                exhausted = exhausted | (d_j & (rung < 0)
                                         & (rem_s < min_cost))
                pairs = [channel_apply(c, None, noised, sub, None)[0]
                         for c in ladder]
                blk = rung_select(rung, pairs, block)
                rem_s = rem_s - jnp.where(sendable, rung_price(rung, costs),
                                          0)
                contrib = jnp.where(sendable, blk, jnp.zeros_like(blk))
            elif serve_controller is not None:
                # unbudgeted adaptive serve: noise once, per-rung
                # codec-only roundtrips, select by the policy rung — the
                # decomposition the eager fused channel matches bit for bit
                noised, _ = channel_apply(None, privacy, block, sub, None)
                pairs = [channel_apply(c, None, noised, sub, None)[0]
                         for c in ladder]
                blk = rung_select(c_rung, pairs, noised)
                sendable = d_j
                rung = c_rung
                contrib = jnp.where(d_j, blk, jnp.zeros_like(blk))
            else:
                blk, _ = channel_apply(ladder[0], privacy, block, sub, None,
                                       qmax=qmax)
                sendable = d_j
                rung = jnp.asarray(0 if ladder[0] is not None else -1,
                                   jnp.int32)
                contrib = jnp.where(d_j, blk, jnp.zeros_like(blk))
            if live:
                live_sent = live_sent + jnp.where(sendable, 1, 0)
                if budget is not None:
                    # only budgeted serves record skips, and only for
                    # blocks admission actually asked to deliver
                    live_skip = live_skip + jnp.where(
                        d_j & jnp.logical_not(sendable), 1, 0)
                if budget is None and serve_controller is None:
                    hop_cost = jnp.asarray(costs[0], jnp.int32)
                else:
                    hop_cost = rung_price(rung, costs)
                live_bits = live_bits + jnp.where(sendable, hop_cost, 0)
            blocks.append(blk)
            sent_l.append(sendable)
            rung_l.append(jnp.where(sendable, rung, -1))
            total = total + contrib
        if live:
            # one tap per request; batch-pad filler slots carry deliver
            # all-False, so active == deliver[0] drops them host-side
            emit_serve(deliver[0], live_bits + key_salt(key),
                       live_sent, live_skip)
        return ServeResult(preds=jnp.argmax(total, axis=-1),
                           blocks=jnp.stack(blocks, axis=0),
                           sent=jnp.stack(sent_l),
                           codec_idx=jnp.stack(rung_l),
                           exhausted=exhausted)

    if not qmax_arg:
        return (lambda key, Xs, params, alphas, valid, rem_s, rem_l, deliver:
                serve_fn(key, Xs, params, alphas, valid, rem_s, rem_l,
                         deliver))
    return serve_fn


@functools.lru_cache(maxsize=64)
def _serve_program(plan: SessionPlan, feature_shapes: tuple,
                   live: bool = False):
    return jax.jit(_counted("serve",
                            make_serve_fn(plan, feature_shapes, live=live)))


def serve_session(plan: SessionPlan, result: SessionResult, key,
                  Xs: Sequence[jnp.ndarray], *, valid=None,
                  rem_session=None, rem_link=None,
                  deliver=None, live: bool = False) -> ServeResult:
    """Run the traced serve step for one completed compiled session: the
    one-program distributed prediction over ``Xs`` (per-agent serve-time
    feature blocks).  ``valid`` optionally overrides ``result.valid`` (e.g.
    masked by ``max_round``); ``rem_session``/``rem_link`` seed the budget
    counters from the live transport state (None = uncapped); ``deliver``
    [M] bool gates which non-head blocks ship (None = all)."""
    Xs = tuple(jnp.asarray(x) for x in Xs)
    shapes = tuple(x.shape[1:] for x in Xs)
    num = plan.num_agents
    valid = result.valid if valid is None else valid
    if rem_session is None:
        rem_session = _INT32_MAX
    if rem_link is None:
        rem_link = (_INT32_MAX,) * num
    if key is None:
        key = jax.random.key(0)        # unused by a channel-less serve
    rem_s = jnp.asarray(min(int(rem_session), _INT32_MAX), jnp.int32)
    rem_l = jnp.asarray([min(int(r), _INT32_MAX) for r in rem_link],
                        jnp.int32)
    if deliver is None:
        deliver = jnp.ones((num,), bool)
    return _serve_program(plan, shapes, live)(
        key, Xs, result.params, result.alphas, jnp.asarray(valid),
        rem_s, rem_l, jnp.asarray(deliver, bool))


# ================================================================ batched serve
@functools.lru_cache(maxsize=64)
def _serve_batch_program(plan: SessionPlan, feature_shapes: tuple,
                         width: int, live: bool = False):
    fn = make_serve_fn(plan, feature_shapes, live=live)
    num = plan.num_agents

    from repro.comm.codecs import serve_key

    def run(slots):
        # the per-slot -> batch stacking happens INSIDE the jitted program:
        # a flush costs one XLA dispatch per bucket, not O(leaves) host
        # dispatches (host-side jnp.stack was the serve loop's bottleneck)
        if "request" in slots[0]:
            # slot carries (evolved session key, request id); the
            # request-keyed serve key folds in-program — two eager fold_in
            # dispatches per request otherwise
            keys = jnp.stack([serve_key(s["key"], s["request"])
                              for s in slots])
        else:
            keys = jnp.stack([s["key"] for s in slots])
        Xs = tuple(jnp.stack([s["Xs"][m] for s in slots])
                   for m in range(num))
        params = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[s["params"] for s in slots])
        alphas = jnp.stack([s["alphas"] for s in slots])
        valid = jnp.stack([s["valid"] for s in slots])
        rem_s = jnp.stack([jnp.asarray(s["rem_session"], jnp.int32)
                           for s in slots])
        rem_l = jnp.stack([jnp.asarray(s["rem_link"], jnp.int32)
                           for s in slots])
        deliver = jnp.stack([jnp.asarray(s["deliver"], bool)
                             for s in slots])
        return jax.vmap(fn, in_axes=(0, 0, 0, 0, 0, 0, 0, 0))(
            keys, Xs, params, alphas, valid, rem_s, rem_l, deliver)

    return jax.jit(_counted("serve_batch", run))


def serve_batch(plan: SessionPlan, slots, *,
                live: bool = False) -> ServeResult:
    """Run one traced serve step for a whole *batch* of slots in ONE XLA
    program — the continuous-batching primitive behind
    :mod:`repro.serve.batcher`.

    ``slots`` is a sequence of per-slot dicts, each holding what one
    ``serve_session`` call would consume: ``key`` (the request-keyed serve
    key), ``Xs`` (length-M tuple of [n, p_m] feature blocks), ``params`` /
    ``alphas`` / ``valid`` (the fitted session's ``SessionResult`` fields),
    ``rem_session`` / ``rem_link`` (int32 budget counters), and ``deliver``
    ([M] bool admission mask).  A slot may carry ``request`` (an int
    request id) alongside the *evolved session* key instead of a
    pre-derived serve key — the ``serve_key`` fold then happens inside the
    program.  Returns a ServeResult with a leading slot axis.  Slot b computes exactly what ``serve_session`` would for that
    session and request alone — the vmap axis never mixes slots, so batched
    serving is bit-identical to per-request serving (the pin
    ``tests/test_serve_engine.py`` holds).  Programs cache per
    (plan, feature_shapes, batch width): one bucket = one compile.
    """
    slots = tuple(dict(s) for s in slots)
    shapes = tuple(tuple(np.shape(x)[1:]) for x in slots[0]["Xs"])
    return _serve_batch_program(plan, shapes, len(slots), live)(slots)


# ================================================================= codec sweep
@functools.lru_cache(maxsize=64)
def _sweep_program(plan: SessionPlan, feature_shapes: tuple):
    fn = make_session_fn(plan, feature_shapes, qmax_arg=True)
    return jax.jit(_counted("sweep",
                            jax.vmap(fn, in_axes=(0, None, None, 0))))


@functools.lru_cache(maxsize=64)
def _sweep_serve_program(plan: SessionPlan, feature_shapes: tuple):
    sess = make_session_fn(plan, feature_shapes, qmax_arg=True)
    srv = make_serve_fn(plan, feature_shapes, qmax_arg=True)
    num = plan.num_agents

    def run_one(key, Xs, classes, qmax, serve_Xs):
        from repro.comm.codecs import SERVE_FOLD
        res = sess(key, Xs, classes, qmax)
        serve = srv(jax.random.fold_in(key, SERVE_FOLD), serve_Xs,
                    res.params, res.alphas, res.valid,
                    jnp.asarray(_INT32_MAX, jnp.int32),
                    jnp.full((num,), _INT32_MAX, jnp.int32),
                    jnp.ones((num,), bool), qmax)
        return res, serve

    return jax.jit(_counted("sweep_serve", jax.vmap(
        run_one, in_axes=(0, None, None, 0, None))))


def quant_sweep_run(plan: SessionPlan, keys: jax.Array,
                    Xs: Sequence[jnp.ndarray], classes: jnp.ndarray,
                    qmaxes: jnp.ndarray, serve_Xs=None):
    """Sweep quantization levels across a session fleet in ONE XLA program.

    The plan's :class:`~repro.comm.codecs.QuantCodec` clipping level becomes
    a traced per-session scalar: session s runs with PRNG key ``keys[s]``
    and integer range [-qmaxes[s], qmaxes[s]] (e.g. ``[127, 31, 7]`` for an
    int8/int6/int4 frontier — pass identical keys to isolate the codec
    axis).  This is the codec analogue of :func:`fleet_run`: because codecs
    are fixed-shape pure functions, the whole accuracy-vs-precision frontier
    vmaps instead of re-running per config.  Wire bits per session follow
    from :func:`repro.comm.codecs.quant_bits_per_element`.

    With ``serve_Xs`` (per-agent serve-time feature blocks) the sweep gains
    a serve axis: each swept session also runs the traced serve step at its
    qmax (serve key folded off the session key with the SERVE tag, matching
    ``Protocol.predict_distributed``) and the call returns a
    ``(SessionResult, ServeResult)`` pair, both with a leading sweep axis —
    train-bits vs serve-bits vs accuracy from one XLA program.
    """
    Xs = tuple(jnp.asarray(x) for x in Xs)
    shapes = tuple(x.shape[1:] for x in Xs)
    if serve_Xs is None:
        return _sweep_program(plan, shapes)(
            keys, Xs, classes, jnp.asarray(qmaxes, jnp.float32))
    serve_Xs = tuple(jnp.asarray(x) for x in serve_Xs)
    return _sweep_serve_program(plan, shapes)(
        keys, Xs, classes, jnp.asarray(qmaxes, jnp.float32), serve_Xs)


# ============================================================== control sweep
@functools.lru_cache(maxsize=64)
def _control_sweep_program(plan: SessionPlan, feature_shapes: tuple,
                           live: bool = False):
    fn = make_session_fn(plan, feature_shapes, control_arg=True, live=live)
    return jax.jit(_counted("control_sweep", jax.vmap(
        fn, in_axes=(0, None, None, 0, 0, 0, 0))))


def control_sweep_run(plan: SessionPlan, keys: jax.Array,
                      Xs: Sequence[jnp.ndarray], classes: jnp.ndarray, *,
                      cuts=None, betas=None, session_bits=None,
                      link_bits=None, live: bool = False) -> SessionResult:
    """Sweep the *control plane* across a session fleet in ONE XLA program.

    The plan's adaptive-controller thresholds (``cuts`` [S, R-1]) and EMA
    coefficient (``betas`` [S]) and/or its budget caps (``session_bits`` /
    ``link_bits``, [S] sequences with ``None`` entries = uncapped) become
    traced per-session operands: config s runs with PRNG key ``keys[s]``
    under its own controller/budget hyperparameters — the control-plane
    analogue of :func:`quant_sweep_run`, replacing one re-trace per
    hyperparameter with a single compile (``TRACE_COUNTS['control_sweep']``
    counts the traces; CI asserts it stays at one across a sweep).  Any
    axis left ``None`` is filled from the plan's static values, so a sweep
    can vary thresholds alone, caps alone, or both.  Returns a
    :class:`SessionResult` with a leading config axis, each row bit-equal
    to a static plan compiled with that config's values.
    """
    if plan.budget is None and plan.controller is None:
        raise ValueError("control_sweep_run sweeps controller thresholds "
                         "and budget caps; the plan has neither")
    Xs = tuple(jnp.asarray(x) for x in Xs)
    shapes = tuple(x.shape[1:] for x in Xs)
    S = int(jnp.shape(keys)[0])
    if cuts is None:
        base = (plan.controller.thresholds if plan.controller is not None
                else ())
        cuts = jnp.tile(jnp.asarray(base, jnp.float32)[None, :], (S, 1))
    else:
        cuts = jnp.asarray(cuts, jnp.float32)
    if betas is None:
        b = plan.controller.beta if plan.controller is not None else 0.0
        betas = jnp.full((S,), b, jnp.float32)
    else:
        betas = jnp.asarray(betas, jnp.float32)

    def cap_axis(vals, static):
        clip = lambda v: min(int(v), _INT32_MAX) if v is not None \
            else _INT32_MAX
        if vals is None:
            return jnp.full((S,), clip(static), jnp.int32)
        return jnp.asarray([clip(v) for v in vals], jnp.int32)

    sb = cap_axis(session_bits,
                  plan.budget.session_bits if plan.budget else None)
    lb = cap_axis(link_bits, plan.budget.link_bits if plan.budget else None)
    return _control_sweep_program(plan, shapes, live)(keys, Xs, classes,
                                                      cuts, betas, sb, lb)


# ============================================================= host extraction
def _slot_slices(params):
    """Every (slot, round) slice of stacked per-slot params: a tuple over
    slots, then rounds, of the slot's pytree."""
    return tuple(
        tuple(jax.tree.map(lambda x, _t=t: x[_t], p)
              for t in range(jax.tree.leaves(p)[0].shape[0]))
        for p in params)


@functools.lru_cache(maxsize=64)
def _extract_program(treedef, avals: tuple):
    return jax.jit(_counted("extract", _slot_slices))


def extract_params(params: tuple) -> tuple:
    """Split a result's stacked per-slot params (M pytrees with leading
    round axis T) into ``out[j][t]``, slot ``j``'s params of round ``t``,
    each leaf its own device array, bit-identical to ``x[t]``.  One
    program launch for all T x M slots (one eager slice per leaf and slot
    costs a host dispatch each); cached per (treedef, leaf shapes and
    dtypes), so a configuration traces it once."""
    leaves, treedef = jax.tree.flatten(params)
    avals = tuple((x.shape, x.dtype) for x in leaves)
    return _extract_program(treedef, avals)(params)


def _row_slices(x):
    """Every length-n row of ``x`` [..., n], nested over its leading axes."""
    if x.ndim == 1:
        return x
    return tuple(_row_slices(x[i]) for i in range(x.shape[0]))


@functools.lru_cache(maxsize=64)
def _split_program(shape: tuple, dtype):
    return jax.jit(_counted("replay", _row_slices))


def split_rows(x: jnp.ndarray) -> tuple:
    """Split a result's stacked score vectors (``w_trace`` [T, M, n],
    ``w_bar`` [T, n]) into nested tuples over the leading axes, so
    ``split_rows(w_trace)[t][j]`` is its own device array, bit-identical to
    ``w_trace[t, j]``.  One program launch for every row (an eager slice
    per row costs a host dispatch each); every row, not the sent ones only,
    so the output count is static and the program does not retrace per
    send pattern; cached per (shape, dtype), so a configuration traces it
    once."""
    return _split_program(tuple(x.shape), x.dtype)(x)


def agent_major_result(result: SessionResult) -> SessionResult:
    """Re-collect a slot-major :class:`SessionResult` to agent-major.

    Under a permuting scheduler, slot ``j`` of round ``t`` holds whichever
    agent ``result.order[t, j]`` names, so consumers that index per-agent
    state positionally (the serve paths read ``params[m]``) need the
    inverse permutation applied first.  Host-side and cheap (numpy gathers
    plus one params re-stack); identity plans short-circuit.
    """
    order = getattr(result, "order", None)
    if order is None:
        return result
    order = np.asarray(order)
    T, M = order.shape
    if np.array_equal(order, np.tile(np.arange(M), (T, 1))):
        return result
    inv = np.argsort(order, axis=1)      # inv[t, m] = slot agent m ran in

    def collect(a):
        if a is None:
            return None
        return jnp.asarray(np.take_along_axis(np.asarray(a), inv, axis=1))

    params = tuple(
        jax.tree.map(
            lambda *xs, _m=m: jnp.stack(
                [xs[int(inv[t, _m])][t] for t in range(T)]),
            *result.params)
        for m in range(M))
    return result._replace(
        alphas=collect(result.alphas), accs=collect(result.accs),
        executed=collect(result.executed), valid=collect(result.valid),
        params=params,
        sent=collect(result.sent), codec_idx=collect(result.codec_idx),
        order=jnp.tile(jnp.arange(M, dtype=jnp.int32), (T, 1)))


def fitted_from_result(plan: SessionPlan, result: SessionResult,
                       learners: Sequence):
    """Rebuild the eager engine's result objects from a compiled run: the
    component list (valid slots in chain order), the round history, and a
    :class:`repro.core.engine.FittedASCII` — byte-compatible with what
    ``Protocol.fit`` returns on the eager path.  Slot-major input: under a
    permuting scheduler the component agent ids come from ``result.order``
    (slot ``j`` holds agent ``order[t, j]``), matching the eager visit
    order exactly.  Component parameters come from one launch of the
    extraction program (:func:`extract_params`); the result's scalars reach
    the host in one batched fetch, whose host copies the ledger replay then
    reads again for free.  The replay's ``IgnoranceMsg`` payloads come from
    one launch of the row-split program (:func:`split_rows`), not from
    here."""
    from repro.core.engine import Component, FittedASCII

    # the fetch waits for the session to finish, so the copies the launch
    # allocates never sit beside the session's own working memory
    alphas, accs, executed, valid, order = jax.device_get(
        (result.alphas, result.accs, result.executed, result.valid,
         getattr(result, "order", None)))
    params = extract_params(result.params)
    components, history = [], []
    for t in range(plan.max_rounds):
        if not executed[t].any():
            break                        # the eager loop stopped before t
        rec = {"round": t, "alphas": [], "accs": []}
        for j in range(plan.num_agents):
            if not executed[t, j]:
                break                    # mid-round alpha<=0 stop
            rec["alphas"].append(float(alphas[t, j]))
            rec["accs"].append(float(accs[t, j]))
            if valid[t, j]:
                agent = j if order is None else int(order[t, j])
                components.append(Component(agent, t, float(alphas[t, j]),
                                            params[j][t]))
        history.append(rec)
    return FittedASCII(components, list(learners), plan.num_classes, history)

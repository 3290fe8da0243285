"""Session driver: run an ASCII engine session from the command line.

Wires a dataset, a scheduler (via the variant name), and a transport into
``core.engine.Protocol``, with optional mid-run checkpointing and resume —
the launch-layer entry point for protocol runs, the way ``launch/train.py``
is for LM training.

  PYTHONPATH=src python -m repro.launch.session --dataset blob3 \
      --variant ascii --rounds 6 --transport metered
  PYTHONPATH=src python -m repro.launch.session --ckpt-dir /tmp/sess \
      --stop-after 2                       # save mid-run ...
  PYTHONPATH=src python -m repro.launch.session --ckpt-dir /tmp/sess \
      --resume                             # ... and pick the run back up
  PYTHONPATH=src python -m repro.launch.session --dataset notes \
      --learner backbone --arch deepseek-v2-lite --reduced --steps 8 \
      --backend compiled                   # a text agent beside a chart MLP
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

from repro.comm import (BudgetSpec, BudgetedTransport, GaussianMechanism,
                        make_codec)
from repro.control import (AdaptiveController, BudgetAwareScheduler,
                           ServeController, make_accountant)
from repro.control.adaptive import SERVE_STATS
from repro.control.adaptive import STATS as CONTROLLER_STATS
from repro.core.engine import (InProcessTransport, MeshRingTransport,
                               MeteredTransport, Protocol, SessionConfig,
                               endpoints_for, variant_setup)
from repro.data.partition import train_test_split, vertical_split
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.learners.logistic import LogisticRegression
from repro.learners.mlp import MLP
from repro.learners.neural import NeuralBackbone
from repro.learners.tree import DecisionTree
from repro.scenarios import PARTITIONS, PRESETS, PROTOCOLS, Scenario, \
    make_variant
from repro.telemetry import Telemetry

DATASETS = {
    "blob3": lambda key, n: synthetic.blob_fig3(key, n=n),
    "blob4": lambda key, n: synthetic.blob_fig4(key, n=n),
    "blob6": lambda key, n: synthetic.blob_fig6(key, n=n),
    "wine": lambda key, n: synthetic.wine_surrogate(key),
}

TRANSPORTS = {
    "inprocess": InProcessTransport,
    "metered": MeteredTransport,
    "meshring": MeshRingTransport,
}

LEARNERS = {
    # tree is eager-only; logistic/mlp carry a LearnerCore and can ride
    # --backend compiled; backbone is a --arch sequence classifier reading
    # the notes of --dataset notes, beside an mlp reading the chart
    "tree": lambda args: DecisionTree(depth=args.depth, num_thresholds=8),
    "logistic": lambda args: LogisticRegression(steps=args.steps),
    "mlp": lambda args: MLP(hidden=(32, 16), steps=args.steps),
    "backbone": lambda args: NeuralBackbone(cfg=_arch(args), steps=args.steps,
                                            batch_size=16),
}


def _arch(args):
    from repro.configs.registry import get_arch
    cfg = get_arch(args.arch)
    return (cfg.reduced() if args.reduced else cfg).with_overrides(
        dtype="float32")


NOTE_LENGTH = 64           # tokens a note of --dataset notes holds


def _dataset(args, key):
    """(agent blocks, classes, number of classes): the dataset's vertical
    split, or for ``notes`` each subject's note (token ids of the
    backbone's vocabulary) and chart."""
    if args.dataset == "notes":
        notes, chart, classes = synthetic.mimic_notes(
            key, args.n, length=NOTE_LENGTH, vocab=_arch(args).vocab_size)
        return [notes, chart], classes, 2
    ds = DATASETS[args.dataset](key, args.n)
    return vertical_split(ds.X, ds.splits), ds.classes, ds.num_classes


def _print_comm(transport, show_ema=True):
    """Wire-channel summary lines (codec ledger, budget state, DP spend)."""
    if transport.controller is not None:
        line = (f"controller: stat={transport.controller.stat},"
                f"rungs={len(transport.controller.ladder)}")
        if show_ema:        # compiled runs keep the EMA in the scan carry
            line += f",ema={float(transport.ctrl_state):.4f}"
        print(line)
    if transport.codec is not None:
        line = f"codec={type(transport.codec).__name__}"
        if isinstance(transport, MeteredTransport):
            line += (f",ignorance_bits="
                     f"{transport.bits_by_kind().get('ignorance', 0)}")
        print(line)
    if transport.serve_codec is not None:
        print(f"serve_codec={type(transport.serve_codec).__name__}")
    if transport.serve_controller is not None:
        print(f"serve_controller: stat={transport.serve_controller.stat},"
              f"rungs={len(transport.serve_controller.ladder)}")
    if hasattr(transport, "budget"):
        print(f"budget: spent={transport.total_bits}b,"
              f"skipped_hops={len(transport.skipped)},"
              f"exhausted={transport.exhausted}")
    if getattr(transport, "privacy", None) is not None:
        print(f"dp: {json.dumps(transport.accountant.report(transport.privacy))}")


def _print_serve(transport, preds, cte, before_bits):
    """Serve-path summary: distributed-prediction accuracy and the encoded
    ScoreBlockMsg bits this predict call booked."""
    line = f"serve: acc={float(jnp.mean(preds == cte)):.3f}"
    if isinstance(transport, MeteredTransport):
        bits = transport.bits_by_kind().get("score_block", 0) - before_bits
        line += f",score_block_bits={bits}"
    if hasattr(transport, "budget"):
        line += f",skipped_hops={len(transport.skipped)}"
    print(line)


def _finish_telemetry(args, telemetry, transport, dash=None):
    """Stop the profiler (if running), settle the dashboard's last frame,
    and write the trace/metrics artifacts; called at both backends'
    exits, after all traffic."""
    if args.profile_dir:
        jax.profiler.stop_trace()
        print(f"profile: wrote {args.profile_dir}")
    if dash is not None:
        dash.final()
    if telemetry is not None:
        telemetry.write_artifacts(trace=args.trace or None,
                                  metrics_out=args.metrics_out or None,
                                  transport=transport)
        for path in (args.trace, args.metrics_out):
            if path:
                print(f"telemetry: wrote {path}")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dataset", default="blob3",
                    choices=sorted(DATASETS) + ["notes"])
    ap.add_argument("--n", type=int, default=600)
    ap.add_argument("--variant", default="ascii",
                    choices=["ascii", "simple", "random", "async"])
    ap.add_argument("--protocol", default="ascii",
                    choices=sorted(PROTOCOLS),
                    help="protocol variant (repro.scenarios): ascii = the "
                         "paper's ignorance interchange; fedavg = federated "
                         "averaging over a homogeneous functional roster "
                         "(GradientMsg uplinks through the same codec/"
                         "budget/DP channel); al = assisted-learning "
                         "residual-fitting rounds (ResidualMsg around the "
                         "ring, eager only)")
    ap.add_argument("--scenario", default="",
                    choices=[""] + sorted(PRESETS),
                    help="adversarial-reality preset (repro.scenarios): "
                         "clean/noniid/churn/subsample; fixes the knob "
                         "flags below")
    ap.add_argument("--subsample", type=float, default=0.0,
                    help="per-round client subsampling fraction in (0, 1] "
                         "(FedAvg's C; unlocks --accountant subsampled-rdp)")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-round permanent-departure probability")
    ap.add_argument("--straggle", type=float, default=0.0,
                    help="per-(round, agent) transient-miss probability")
    ap.add_argument("--partition", default="iid",
                    choices=sorted(PARTITIONS),
                    help="non-IID horizontal shards: dirichlet label skew "
                         "or power-law quantity skew (agents fit only on "
                         "their shard's rows)")
    ap.add_argument("--skew", type=float, default=0.5,
                    help="partition skew: dirichlet alpha / quantity "
                         "exponent")
    ap.add_argument("--clock-skew", default="",
                    help="comma-separated per-agent barrier lags (ASCII "
                         "--variant async only), e.g. 0,0,2,1")
    ap.add_argument("--scenario-seed", type=int, default=0,
                    help="seed of the scenario's churn/partition draws")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--transport", default="metered",
                    choices=sorted(TRANSPORTS))
    ap.add_argument("--learner", default="tree", choices=sorted(LEARNERS))
    ap.add_argument("--depth", type=int, default=3,
                    help="tree depth (tree learner only)")
    ap.add_argument("--steps", type=int, default=150,
                    help="optimizer steps (logistic/mlp/backbone learners)")
    ap.add_argument("--arch", default="",
                    help="backbone learner's architecture (configs/"
                         "registry.py), e.g. deepseek-v2-lite")
    ap.add_argument("--reduced", action="store_true",
                    help="the --arch smoke-test size (CPU)")
    ap.add_argument("--backend", default="eager",
                    choices=["eager", "compiled"],
                    help="compiled lowers the whole run into one lax.scan "
                         "program (ascii/simple/async variants, functional "
                         "learners; budget-aware scheduling lowers too)")
    ap.add_argument("--codec", default="",
                    choices=["", "fp32", "fp16", "int8", "int4", "topk"],
                    help="wire codec for outgoing ignorance scores "
                         "(repro.comm.codecs; the ledger books encoded "
                         "bits; empty = raw fp32 messages)")
    ap.add_argument("--serve-codec", default="",
                    choices=["", "fp32", "fp16", "int8", "int4", "topk"],
                    help="wire codec for prediction-time ScoreBlockMsg "
                         "traffic (defaults to --codec when that is set; "
                         "serve blocks are DP-noised, encoded, and booked "
                         "at their encoded size like training hops)")
    ap.add_argument("--byte-budget", type=int, default=0,
                    help="session byte budget: the transport degrades down "
                         "the fp32>fp16>int8>int4 codec ladder, then skips "
                         "hops and stops scheduling rounds (uses the "
                         "budgeted metered transport; incompatible with an "
                         "explicit --transport or --codec)")
    ap.add_argument("--dp-epsilon", type=float, default=0.0,
                    help="per-release DP epsilon: Gaussian-mechanism noise "
                         "on every outgoing ignorance vector, per-agent "
                         "epsilon accounting printed after the run")
    ap.add_argument("--controller", default="",
                    choices=[""] + list(CONTROLLER_STATS),
                    help="adaptive codec controller (repro.control): pick "
                         "the codec rung per hop from this statistic of "
                         "the outgoing ignorance vector (resid = hop "
                         "innovation, entropy/l2 = concentration), "
                         "front-loading precision while the signal is "
                         "high; replaces a fixed --codec, and floors the "
                         "--byte-budget ladder walk when both are set")
    ap.add_argument("--serve-controller", default="",
                    choices=[""] + list(SERVE_STATS),
                    help="serve-path adaptive policy (repro.control): pick "
                         "the ScoreBlockMsg codec rung per block from this "
                         "statistic of the outgoing [n, K] scores (margin = "
                         "mean top1-top2 gap, entropy = normalized row "
                         "entropy) — coarse rungs for confident blocks, "
                         "fine for uncertain ones; replaces a fixed "
                         "--serve-codec, and floors the --byte-budget serve "
                         "ladder walk when both are set")
    ap.add_argument("--accountant", default="basic",
                    choices=["basic", "rdp", "subsampled-rdp"],
                    help="privacy accountant for --dp-epsilon releases: "
                         "basic additive composition, Renyi-DP (moments) "
                         "composition converted to (eps, delta) on read — "
                         "tighter for long sessions, never looser — or "
                         "subsampled-rdp, RDP with privacy amplification "
                         "by the scenario's --subsample client-sampling "
                         "rate (capped at the full-batch bound)")
    ap.add_argument("--scheduler", default="",
                    choices=["", "budget-aware"],
                    help="round-order override (repro.control.scheduler): "
                         "budget-aware reorders agents each round by "
                         "remaining link budget so degradation rotates "
                         "instead of starving a fixed tail (sequential "
                         "variants; both backends — compiled lowers the "
                         "permutation into the scan)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint SessionState here after the run "
                         "(or after --stop-after rounds)")
    ap.add_argument("--stop-after", type=int, default=0,
                    help="pause after this many rounds (with --ckpt-dir: "
                         "save a resumable checkpoint and exit)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from --ckpt-dir instead of starting fresh")
    ap.add_argument("--trace", default="",
                    help="stream a JSONL telemetry trace (repro.telemetry "
                         "schema) here: spans append as they close, final "
                         "metric values seal the file after the run — a "
                         "killed session leaves a truncated prefix "
                         "`python -m repro.telemetry.check --allow-partial` "
                         "accepts")
    ap.add_argument("--metrics-out", default="",
                    help="write the final metrics registry here after the "
                         "run (.prom = Prometheus text exposition, "
                         "anything else = JSON snapshot)")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler trace of the run into "
                         "this directory (view in TensorBoard/Perfetto); "
                         "session/round/hop spans show up as trace "
                         "annotations on the profiler timeline")
    ap.add_argument("--watch", action="store_true",
                    help="render the live dashboard (stderr) while the "
                         "session runs: per-round wire bits, budget "
                         "skips, exhaustion — streamed from inside the "
                         "compiled program via in-flight taps (eager "
                         "rounds tap at round end); metered transports "
                         "only")
    args = ap.parse_args()

    if (args.learner == "backbone") != (args.dataset == "notes"):
        ap.error("--learner backbone reads the notes of --dataset notes, "
                 "and only it does")
    if args.learner == "backbone" and not args.arch:
        ap.error("--learner backbone needs --arch (e.g. deepseek-v2-lite)")
    key = jax.random.key(args.seed)
    Xs, classes, num_classes = _dataset(args, key)
    tr, te = train_test_split(args.seed, classes.shape[0])
    Xtr, Xte = [x[tr] for x in Xs], [x[te] for x in Xs]
    ctr, cte = classes[tr], classes[te]

    if args.backend == "compiled":
        if args.resume or args.stop_after or args.ckpt_dir:
            ap.error("--backend compiled runs fit-to-completion with no "
                     "SessionState; checkpointing/pause/resume need the "
                     "eager backend")
        if args.learner == "tree":
            ap.error("--backend compiled needs a functional learner "
                     "(--learner logistic|mlp); tree is eager-only")
        if args.variant not in ("ascii", "simple", "async"):
            ap.error("--backend compiled supports sequential, budget-aware "
                     "and async-stale scheduling (--variant ascii|simple|"
                     "async)")
    if args.variant == "async" and args.controller:
        ap.error("adaptive controllers are per-hop rung policies with no "
                 "async analogue; --variant async releases its barrier "
                 "merge once per round (--codec/--byte-budget/--dp-epsilon "
                 "apply per barrier and are supported)")
    if args.byte_budget > 0:
        if args.codec:
            ap.error("--byte-budget drives codec choice through its "
                     "degradation ladder; drop --codec")
        if args.serve_codec:
            ap.error("--byte-budget drives the serve codec through the "
                     "same degradation ladder; drop --serve-codec")
        if args.transport != "metered":
            ap.error("--byte-budget needs the (budgeted) metered "
                     "transport; drop --transport")
    if args.controller and args.codec:
        ap.error("--controller drives codec choice through its ladder; "
                 "drop --codec")
    if args.serve_controller and args.serve_codec:
        ap.error("--serve-controller drives serve codec choice through "
                 "its ladder; drop --serve-codec")
    if args.accountant != "basic" and args.dp_epsilon <= 0:
        ap.error(f"--accountant {args.accountant} accounts --dp-epsilon "
                 f"releases; set --dp-epsilon too")
    if args.scheduler == "budget-aware" \
            and args.variant not in ("ascii", "simple"):
        ap.error("--scheduler budget-aware replaces the round order; "
                 "use a sequential variant (ascii|simple)")
    if args.protocol != "ascii":
        if args.variant in ("simple", "async"):
            ap.error(f"--variant {args.variant} is an ASCII scheduling "
                     f"mode; --protocol {args.protocol} runs its own round "
                     f"rule over an ordered roster (--variant ascii|random)")
        if args.controller or args.serve_controller:
            ap.error("adaptive controllers read ignorance-vector "
                     f"statistics; they do not apply to --protocol "
                     f"{args.protocol} traffic")
    if args.protocol == "fedavg" and args.learner == "tree":
        ap.error("--protocol fedavg averages flat parameter deltas from a "
                 "functional learner core; --learner tree has none "
                 "(use logistic|mlp)")
    if args.protocol == "al" and args.backend == "compiled":
        ap.error("--protocol al is eager-only: its ring of closed-form "
                 "ridge hops has no compiled lowering")
    if args.scenario and (args.subsample or args.dropout or args.straggle
                          or args.partition != "iid" or args.clock_skew):
        ap.error("--scenario presets fix the scenario knobs; drop the "
                 "individual --subsample/--dropout/--straggle/--partition/"
                 "--clock-skew flags (or drop --scenario)")
    if args.clock_skew and args.variant != "async":
        # hoisted from Scenario.validate so the explicit flag path errors
        # at argparse time with a message that names the flags
        ap.error("--clock-skew lags agents behind the stale-read barrier; "
                 "it needs --variant async")
    if args.scenario:
        scenario = PRESETS[args.scenario]
    else:
        try:
            clock = (tuple(int(s) for s in args.clock_skew.split(","))
                     if args.clock_skew else ())
        except ValueError:
            ap.error(f"--clock-skew wants comma-separated non-negative "
                     f"ints, got {args.clock_skew!r}")
        try:
            scenario = Scenario("cli", subsample=args.subsample or None,
                                dropout=args.dropout,
                                straggle=args.straggle,
                                partition=args.partition, skew=args.skew,
                                clock_skew=clock, seed=args.scenario_seed)
        except ValueError as e:
            ap.error(str(e))
    if args.accountant == "subsampled-rdp" and scenario.subsample is None:
        ap.error("--accountant subsampled-rdp amplifies privacy by the "
                 "client-sampling rate; set --subsample (or a subsampling "
                 "--scenario) so there is a rate to amplify by")
    if args.backend == "compiled" and args.protocol == "ascii" \
            and not scenario.trivial:
        ap.error("--backend compiled does not lower ASCII scenario knobs "
                 "(churn changes the chain's shape per round); use the "
                 "eager backend — fedavg scenarios do compile")
    variant_obj = make_variant(args.protocol)
    scheduler, upstream = variant_setup(args.variant, args.seed)
    if args.scheduler == "budget-aware":
        scheduler = BudgetAwareScheduler()
    try:
        scenario.validate(len(Xs), scheduler, variant_obj)
    except ValueError as e:
        ap.error(str(e))
    privacy = (GaussianMechanism(epsilon=args.dp_epsilon,
                                 nonneg=(args.protocol == "ascii"))
               if args.dp_epsilon > 0 else None)
    accountant = (make_accountant(args.accountant, q=scenario.subsample)
                  if privacy is not None else None)
    controller = (AdaptiveController(stat=args.controller)
                  if args.controller else None)
    serve_controller = (ServeController(stat=args.serve_controller)
                        if args.serve_controller else None)
    if args.byte_budget > 0:
        transport = BudgetedTransport(
            BudgetSpec(session_bits=args.byte_budget * 8), privacy=privacy,
            controller=controller, accountant=accountant,
            serve_controller=serve_controller)
    else:
        codec = make_codec(args.codec) if args.codec else None
        serve_codec = (make_codec(args.serve_codec) if args.serve_codec
                       else None)
        transport = TRANSPORTS[args.transport](codec=codec, privacy=privacy,
                                               serve_codec=serve_codec,
                                               controller=controller,
                                               accountant=accountant,
                                               serve_controller=serve_controller)
    telemetry = (Telemetry(profile=bool(args.profile_dir),
                           live=args.watch)
                 if (args.trace or args.metrics_out or args.profile_dir
                     or args.watch)
                 else None)
    if telemetry is not None and args.trace:
        # crash-durable: spans stream to the trace file as they close;
        # _finish_telemetry seals it with the final metric events (with
        # --watch, live round taps stream into it too, as they fire)
        telemetry.stream_trace(args.trace)
    dash = None
    if args.watch:
        from repro.telemetry.dash import Dashboard
        dash = Dashboard(telemetry.registry,
                         title=f"session:{args.dataset}"
                         ).attach(telemetry.live)
    engine = Protocol(SessionConfig(num_classes=num_classes,
                                    max_rounds=args.rounds,
                                    upstream=upstream),
                      scheduler=scheduler, transport=transport,
                      backend=args.backend, variant=variant_obj,
                      scenario=None if scenario.trivial else scenario,
                      telemetry=telemetry)
    learners = [LEARNERS[args.learner](args) for _ in Xs]
    if args.learner == "backbone":
        learners[1] = LEARNERS["mlp"](args)     # the chart's agent
    endpoints = endpoints_for(learners, Xtr)
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)

    # FedAvg's fitted object carries flat global params, not a component
    # ensemble; everything else (ascii, al) reports its ensemble size
    tag = "" if args.protocol == "ascii" else f"{args.protocol},"

    def _size(fitted):
        if args.protocol == "fedavg":
            return f"params={fitted.g.size}"
        return f"components={len(fitted.components)}"

    if args.backend == "compiled":
        fitted = engine.fit(jax.random.fold_in(key, 1), endpoints, ctr)
        acc = float(jnp.mean(fitted.predict(Xte) == cte))
        line = (f"{args.dataset},{tag}{args.variant},{args.transport},"
                f"compiled,rounds={fitted.num_rounds},"
                f"{_size(fitted)},acc={acc:.3f}")
        if isinstance(transport, MeteredTransport):
            line += f",bits={transport.total_bits}"
        print(line)
        if args.protocol == "ascii":
            # only ASCII has a serve path (chained ScoreBlockMsg traffic)
            before = (transport.bits_by_kind().get("score_block", 0)
                      if isinstance(transport, MeteredTransport) else 0)
            preds = engine.predict_distributed(Xte)
            _print_serve(transport, preds, cte, before)
        _print_comm(transport, show_ema=False)
        _finish_telemetry(args, telemetry, transport, dash)
        return

    # the run config that must match across pause/resume: a different
    # variant/seed/dataset would silently corrupt the resumed trajectory
    run_cfg = {k: getattr(args, k)
               for k in ("dataset", "n", "variant", "learner", "depth",
                         "steps", "seed", "codec", "serve_codec",
                         "byte_budget", "dp_epsilon", "controller",
                         "accountant", "scheduler", "serve_controller",
                         "protocol", "scenario", "subsample", "dropout",
                         "straggle", "partition", "skew", "clock_skew",
                         "scenario_seed")}
    cfg_path = os.path.join(args.ckpt_dir or ".", "cli_config.json")
    if args.resume:
        if not args.ckpt_dir:
            ap.error("--resume needs --ckpt-dir")
        if os.path.exists(cfg_path):
            with open(cfg_path) as f:
                saved = json.load(f)
            # manifests written before the learner/steps (PR 2), comm
            # (PR 3), or control-plane (PR 5) flags existed imply the old
            # defaults — fill, don't reject
            saved = {"learner": "tree", "steps": 150, "codec": "",
                     "serve_codec": "", "byte_budget": 0, "dp_epsilon": 0.0,
                     "controller": "", "accountant": "basic",
                     "scheduler": "", "serve_controller": "",
                     "protocol": "ascii", "scenario": "", "subsample": 0.0,
                     "dropout": 0.0, "straggle": 0.0, "partition": "iid",
                     "skew": 0.5, "clock_skew": "", "scenario_seed": 0,
                     **saved}
            if saved != run_cfg:
                ap.error(f"--resume config mismatch: checkpoint was written "
                         f"with {saved}, this run is {run_cfg}")
        else:
            print(f"warning: no {cfg_path} manifest (checkpoint written "
                  f"outside this CLI?) — cannot verify dataset/variant/seed "
                  f"match the saved session")
        session = engine.resume(args.ckpt_dir, endpoints, ctr)
        print(f"resumed {args.ckpt_dir} at round {session.state.round}")
    else:
        session = engine.start(jax.random.fold_in(key, 1), endpoints, ctr)

    session.run(max_rounds=args.stop_after or None)
    paused = (args.stop_after and not session.state.stopped
              and session.state.round < args.rounds)
    if args.ckpt_dir:
        path = session.checkpoint(args.ckpt_dir)
        with open(cfg_path, "w") as f:
            json.dump(run_cfg, f)
        print(f"checkpointed round {session.state.round} -> {path}")

    fitted = session.fitted()
    acc = float(jnp.mean(fitted.predict(Xte) == cte))
    line = (f"{args.dataset},{tag}{args.variant},{args.transport},"
            f"rounds={fitted.num_rounds},{_size(fitted)},"
            f"acc={acc:.3f}")
    if isinstance(transport, MeteredTransport):
        line += f",bits={transport.total_bits}"
    print(line)
    if not paused and args.protocol == "ascii":
        # serve only on the terminal run: the checkpoint above snapshots
        # comm state *before* this point, so a paused process serving here
        # would book budget spend and DP releases the snapshot misses —
        # free bits and an undercounted epsilon ledger after --resume
        before = (transport.bits_by_kind().get("score_block", 0)
                  if isinstance(transport, MeteredTransport) else 0)
        preds = session.predict_distributed(Xte)
        _print_serve(transport, preds, cte, before)
    _print_comm(transport)
    _finish_telemetry(args, telemetry, transport, dash)
    if paused:
        if args.ckpt_dir:
            print(f"paused after {session.state.round} rounds; rerun with "
                  f"--resume to continue")
        else:
            print(f"paused after {session.state.round} rounds; nothing was "
                  f"saved (pass --ckpt-dir to make the pause resumable)")


if __name__ == "__main__":
    main()

"""Serve-fleet driver: a synthetic multi-tenant prediction workload against
the continuous-batching serve engine (:mod:`repro.serve`).

Fits a fleet of compiled protocol sessions, registers them as servable, and
replays a randomized request stream — tenants drawn round-robin, sessions
and serve-time rows drawn at random — through
``ServeEngine.submit``/``flush``.  Prints the per-tenant
denied/degraded/served counters, the cache and batcher stats, and the
sustained request throughput.

  PYTHONPATH=src python -m repro.launch.serve_fleet --sessions 6 \
      --tenants 3 --requests 40 --serve-codec int8 --cache-capacity 4
  PYTHONPATH=src python -m repro.launch.serve_fleet --serve-controller \
      margin --dp-epsilon 1.0 --epsilon-cap 8 --tenant-kb 4
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import (BudgetSpec, BudgetedTransport, GaussianMechanism,
                        make_codec)
from repro.control import ServeController
from repro.control.adaptive import SERVE_STATS
from repro.core.engine import (MeteredTransport, Protocol, SessionConfig,
                               endpoints_for)
from repro.data import synthetic
from repro.data.partition import train_test_split, vertical_split
from repro.launch.compile_cache import enable_compile_cache
from repro.learners.logistic import LogisticRegression
from repro.serve import AdmissionController, AdmissionPolicy, ServeEngine
from repro.telemetry import Telemetry


def fit_fleet(args, key, Xtr, ctr, num_classes, telemetry=None):
    """Fit ``--sessions`` compiled protocols (distinct fold keys, one shared
    plan, so the session program compiles once)."""
    protos = {}
    for s in range(args.sessions):
        privacy = (GaussianMechanism(epsilon=args.dp_epsilon)
                   if args.dp_epsilon > 0 else None)
        serve_controller = (ServeController(stat=args.serve_controller)
                            if args.serve_controller else None)
        if args.byte_budget > 0:
            transport = BudgetedTransport(
                BudgetSpec(session_bits=args.byte_budget * 8),
                privacy=privacy, serve_controller=serve_controller)
        else:
            transport = MeteredTransport(
                privacy=privacy, serve_controller=serve_controller,
                serve_codec=(make_codec(args.serve_codec)
                             if args.serve_codec else None))
        proto = Protocol(SessionConfig(num_classes=num_classes,
                                       max_rounds=args.rounds),
                         transport=transport, backend="compiled",
                         telemetry=telemetry)
        endpoints = endpoints_for(
            [LogisticRegression(steps=args.steps) for _ in Xtr], Xtr)
        proto.fit(jax.random.fold_in(key, s), endpoints, ctr)
        protos[f"s{s}"] = proto
    return protos


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dataset", default="blob3",
                    choices=["blob3", "blob4", "blob6"])
    ap.add_argument("--n", type=int, default=400)
    ap.add_argument("--sessions", type=int, default=4)
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--block-n", type=int, default=32,
                    help="serve-time rows per request (one bucket shape)")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--cache-capacity", type=int, default=4,
                    help="resident sessions; the rest spill to checkpoint "
                         "and restore bit-exact on next touch")
    ap.add_argument("--flush-every", type=int, default=8,
                    help="drain the batch queue after this many submits")
    ap.add_argument("--serve-codec", default="",
                    choices=["", "fp32", "fp16", "int8", "int4"])
    ap.add_argument("--serve-controller", default="",
                    choices=[""] + list(SERVE_STATS))
    ap.add_argument("--byte-budget", type=int, default=0,
                    help="per-session byte budget (serve blocks walk the "
                         "degradation ladder against it)")
    ap.add_argument("--dp-epsilon", type=float, default=0.0)
    ap.add_argument("--tenant-kb", type=int, default=0,
                    help="per-tenant serve byte cap in KB (0 = uncapped); "
                         "requests a tenant cannot afford degrade to "
                         "head-only (or are denied with --no-degrade)")
    ap.add_argument("--epsilon-cap", type=float, default=0.0,
                    help="per-tenant total DP epsilon cap (0 = no gate)")
    ap.add_argument("--no-degrade", action="store_true",
                    help="deny over-budget requests instead of degrading "
                         "them to head-only")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="per-tenant latency SLO threshold in ms (0 = no "
                         "SLO tracking); admission denials count as "
                         "violations")
    ap.add_argument("--slo-objective", type=float, default=0.99,
                    help="fraction of a tenant's requests that must land "
                         "under --slo-ms")
    ap.add_argument("--watch", action="store_true",
                    help="render the live fleet dashboard (stderr) while "
                         "the workload runs: per-round wire taps, tenant "
                         "p50/p99, SLO burn, admission/cache counters")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default="",
                    help="write a JSONL telemetry trace (flush/flush_wave/"
                         "bucket_dispatch spans + final metric values) "
                         "here after the workload; with --watch the live "
                         "events stream into it as they happen")
    ap.add_argument("--metrics-out", default="",
                    help="write the fleet metrics registry here (.prom = "
                         "Prometheus text exposition, else JSON snapshot)")
    args = ap.parse_args()
    if args.serve_controller and args.serve_codec:
        ap.error("--serve-controller drives serve codec choice through "
                 "its ladder; drop --serve-codec")

    key = jax.random.key(args.seed)
    ds = {"blob3": synthetic.blob_fig3, "blob4": synthetic.blob_fig4,
          "blob6": synthetic.blob_fig6}[args.dataset](key, n=args.n)
    tr, te = train_test_split(args.seed, ds.X.shape[0])
    Xs = vertical_split(ds.X, ds.splits)
    Xtr, Xte = [x[tr] for x in Xs], [x[te] for x in Xs]
    ctr = ds.classes[tr]

    telemetry = (Telemetry(live=args.watch)
                 if (args.trace or args.metrics_out or args.watch)
                 else None)
    if args.trace and telemetry is not None:
        telemetry.stream_trace(args.trace)
    dash = None
    if args.watch:
        from repro.telemetry.dash import Dashboard
        dash = Dashboard(telemetry.registry,
                         title="serve fleet").attach(telemetry.live)
    t0 = time.time()
    protos = fit_fleet(args, jax.random.fold_in(key, 1), Xtr, ctr,
                       ds.num_classes, telemetry=telemetry)
    print(f"fitted {args.sessions} sessions in {time.time() - t0:.2f}s")

    mechanism = (GaussianMechanism(epsilon=args.dp_epsilon)
                 if args.dp_epsilon > 0 else None)
    slo = None
    if args.slo_ms > 0:
        from repro.telemetry.slo import SLOConfig
        slo = SLOConfig(threshold_s=args.slo_ms / 1e3,
                        objective=args.slo_objective)
    engine = ServeEngine(
        cache_capacity=args.cache_capacity, max_batch=args.max_batch,
        admission=AdmissionController(
            AdmissionPolicy(allow_degrade=not args.no_degrade,
                            epsilon_cap=args.epsilon_cap or None),
            tenant_bits=args.tenant_kb * 8 * 1024 or None,
            mechanism=mechanism),
        telemetry=telemetry, slo=slo)
    for sid, proto in protos.items():
        engine.add_session(sid, proto)

    rng = np.random.default_rng(args.seed)
    n_te = int(Xte[0].shape[0])
    t0 = time.time()
    for i in range(args.requests):
        tenant = f"t{i % args.tenants}"
        sid = f"s{rng.integers(args.sessions)}"
        rows = rng.choice(n_te, size=min(args.block_n, n_te), replace=False)
        engine.submit(tenant, sid, [jnp.asarray(np.asarray(x)[rows])
                                    for x in Xte])
        if (i + 1) % args.flush_every == 0:
            engine.flush()
    engine.flush()
    dt = time.time() - t0

    summary = engine.summary()
    summary["elapsed_s"] = round(dt, 4)
    summary["qps"] = round(args.requests / max(dt, 1e-9), 2)
    if dash is not None:
        dash.final()
    print(json.dumps(summary, indent=2))
    if telemetry is not None:
        # fleet-wide: link gauges are per-transport, so skip the gauge
        # sync and export the shared counter registry + serve spans
        telemetry.write_artifacts(trace=args.trace or None,
                                  metrics_out=args.metrics_out or None)
        for path in (args.trace, args.metrics_out):
            if path:
                print(f"telemetry: wrote {path}")
    engine.close()


if __name__ == "__main__":
    main()

"""Serve-path throughput: continuous batching vs per-request dispatch.

The serve engine's claim is that prediction traffic against S concurrent
sessions should ride ONE vmapped compiled serve program per bucket instead
of one XLA dispatch per request.  This bench measures both sides on the
same request stream:

  * ``sequential`` — one ``core.compiled.serve_session`` dispatch per
    request (the strongest per-request baseline: already traced/jitted,
    no engine overhead at all).
  * ``batched``    — the full ``repro.serve.ServeEngine`` path: admission,
    cache, bucketed ``serve_batch`` programs, ledger bookkeeping.

Emits ``BENCH_serve.json`` with sustained QPS and p50/p99 request latency
for both modes (batched latency counts submit -> flush-complete).  With
``verify=True`` every batched prediction is checked bit-equal against the
standalone ``Protocol.predict_distributed(request=rid)`` path — the CI
bench-smoke gate.

  PYTHONPATH=src python benchmarks/serve_bench.py --sessions 8 --requests 64
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.fleet_bench import make_cohort
from repro.comm.codecs import make_codec
from repro.core import compiled
from repro.core.engine import (MeteredTransport, Protocol, SessionConfig,
                               endpoints_for)
from repro.launch.compile_cache import enable_compile_cache
from repro.learners.logistic import LogisticRegression
from repro.serve import ServeEngine
from repro.telemetry.registry import MetricsRegistry


def _fit_sessions(sessions, Xs, classes, *, num_classes, rounds, steps,
                  serve_codec):
    protos = {}
    for s in range(sessions):
        proto = Protocol(
            SessionConfig(num_classes=num_classes, max_rounds=rounds),
            transport=MeteredTransport(serve_codec=make_codec(serve_codec)),
            backend="compiled")
        proto.fit(jax.random.key(1000 + s),
                  endpoints_for([LogisticRegression(steps=steps)
                                 for _ in Xs], Xs), classes)
        protos[f"s{s}"] = proto
    return protos


def _pcts(reg):
    """p50/p99 request latency (ms) off the ``request_seconds`` bucketed
    histogram — the same estimator the live dashboard and the SLO layer
    read, exercised here instead of a hand-rolled percentile."""
    return (reg.quantile_all("request_seconds", 0.5) * 1e3,
            reg.quantile_all("request_seconds", 0.99) * 1e3)


def run(*, sessions: int = 8, requests: int = 64, agents: int = 3,
        rounds: int = 2, steps: int = 60, n: int = 256, block_n: int = 32,
        num_classes: int = 5, serve_codec: str = "int8",
        max_batch: int = 8, verify: bool = False,
        out: str | None = "BENCH_serve.json") -> dict:
    Xs, classes = make_cohort(0, n=n, agents=agents, feats=3,
                              num_classes=num_classes)
    protos = _fit_sessions(sessions, Xs, classes, num_classes=num_classes,
                           rounds=rounds, steps=steps,
                           serve_codec=serve_codec)
    rng = np.random.default_rng(7)
    reqs = []                  # (session_id, Xs_block) per request
    for _ in range(requests):
        sid = f"s{rng.integers(sessions)}"
        rows = rng.choice(n, size=block_n, replace=False)
        reqs.append((sid, tuple(jnp.asarray(np.asarray(x)[rows])
                                for x in Xs)))

    # --- sequential: one traced serve dispatch per request, request-keyed
    # exactly like the engine (so both sides run the same programs)
    from repro.comm.codecs import serve_key
    ctxs = {sid: p._compiled_ctx for sid, p in protos.items()}
    evolved = {sid: p._evolved_key(ctxs[sid][2]) for sid, p in protos.items()}

    def serve_one(rid, sid, Xblk):
        _, plan, result = ctxs[sid]
        return compiled.serve_session(plan, result,
                                      serve_key(evolved[sid], rid), Xblk)

    serve_one(0, *reqs[0]).preds.block_until_ready()      # warm compile
    seq_reg = MetricsRegistry()
    t0 = time.perf_counter()
    for rid, (sid, Xblk) in enumerate(reqs):
        t1 = time.perf_counter()
        serve_one(rid, sid, Xblk).preds.block_until_ready()
        seq_reg.observe("request_seconds", time.perf_counter() - t1,
                        tenant="seq")
    seq_s = time.perf_counter() - t0
    p50_seq, p99_seq = _pcts(seq_reg)

    # --- batched: the full serve engine, one flush per max_batch submits;
    # latency comes from the engine's own submit -> settle histogram
    def run_engine(record):
        engine = ServeEngine(cache_capacity=sessions, max_batch=max_batch)
        for sid, proto in protos.items():
            engine.add_session(sid, proto)
        t0 = time.perf_counter()
        for rid, (sid, Xblk) in enumerate(reqs):
            engine.submit(f"t{rid % 2}", sid, Xblk, request=rid)
            if (rid + 1) % max_batch == 0:
                engine.flush()
        engine.flush()
        total = time.perf_counter() - t0
        if record:
            return engine, total
        engine.close()
        return None

    run_engine(record=False)                              # warm compile
    engine, bat_s = run_engine(record=True)
    p50_bat, p99_bat = _pcts(engine.registry)

    verified = None
    if verify:
        for rid, (sid, Xblk) in enumerate(reqs):
            base = protos[sid].predict_distributed(Xblk, request=rid)
            np.testing.assert_array_equal(
                engine.outcomes[rid].preds, np.asarray(base),
                err_msg=f"request {rid} (session {sid}): batched != "
                        f"per-request predictions")
        verified = True

    stats = engine.summary()
    engine.close()
    result = {
        "config": {"sessions": sessions, "requests": requests,
                   "agents": agents, "rounds": rounds, "steps": steps,
                   "n": n, "block_n": block_n, "num_classes": num_classes,
                   "serve_codec": serve_codec, "max_batch": max_batch,
                   "backend": jax.default_backend(),
                   "target": "batched >= 3x sequential QPS at >= 8 "
                             "concurrent sessions"},
        "sequential": {"seconds": seq_s, "qps": requests / seq_s,
                       "p50_ms": p50_seq, "p99_ms": p99_seq},
        "batched": {"seconds": bat_s, "qps": requests / bat_s,
                    "p50_ms": p50_bat, "p99_ms": p99_bat,
                    "batches_run": stats["batcher"]["batches_run"],
                    "padded_slots": stats["batcher"]["padded_slots"]},
        "speedup_batched_vs_sequential": seq_s / bat_s,
        "verified_bit_identical": verified,
    }
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
    return result


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--agents", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--block-n", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--serve-codec", default="int8",
                    choices=["fp32", "fp16", "int8", "int4"])
    ap.add_argument("--verify", action="store_true",
                    help="check every batched prediction bit-equal to the "
                         "standalone per-request path")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    res = run(sessions=args.sessions, requests=args.requests,
              agents=args.agents, rounds=args.rounds, steps=args.steps,
              n=args.n, block_n=args.block_n, max_batch=args.max_batch,
              serve_codec=args.serve_codec, verify=args.verify,
              out=args.out)
    for mode in ("sequential", "batched"):
        r = res[mode]
        print(f"{mode}: {r['seconds']:.2f}s ({r['qps']:.1f} qps, "
              f"p50 {r['p50_ms']:.1f}ms, p99 {r['p99_ms']:.1f}ms)")
    print(f"batched vs sequential: "
          f"{res['speedup_batched_vs_sequential']:.2f}x "
          f"(written to {args.out})")


if __name__ == "__main__":
    main()

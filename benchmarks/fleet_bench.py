"""Session-fleet throughput: eager engine loop vs compiled program vs
vmapped fleet.

Three ways to run S independent ASCII sessions (same cohort, different
session seeds — the shape of every replication sweep and of concurrent
multi-tenant serving):

  * ``eager``    — the host-loop engine, one session at a time (PR-1 path).
  * ``compiled`` — ``core.compiled.compiled_session``: each session is one
    lax.scan program, still dispatched sequentially from the host.
  * ``fleet``    — ``core.compiled.fleet_run``: all S sessions inside one
    vmapped program; the weighted fits batch across sessions on-device.

Emits ``BENCH_fleet.json`` (sessions/sec for each mode + speedups) so the
perf trajectory is tracked from PR 2 onward.

  PYTHONPATH=src python benchmarks/fleet_bench.py --sessions 8
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.core.compiled import compiled_session, fleet_run, plan_for
from repro.core.engine import Protocol, SessionConfig, endpoints_for
from repro.data.synthetic import gaussian_blobs
from repro.launch.compile_cache import enable_compile_cache
from repro.learners.logistic import LogisticRegression
from repro.learners.mlp import MLP


def make_cohort(seed: int, *, n: int, agents: int, feats: int,
                num_classes: int):
    """One collated cohort, split vertically into `agents` feature blocks."""
    X, classes = gaussian_blobs(jax.random.key(seed), n=n,
                                num_features=agents * feats,
                                num_classes=num_classes, cluster_std=1.5)
    Xs = [X[:, m * feats:(m + 1) * feats] for m in range(agents)]
    return Xs, classes


def _learners(name: str, agents: int, steps: int):
    if name == "mlp":
        return [MLP(hidden=(16,), steps=steps) for _ in range(agents)]
    return [LogisticRegression(steps=steps) for _ in range(agents)]


def run(*, sessions: int = 8, agents: int = 3, rounds: int = 4,
        steps: int = 100, n: int = 256, num_classes: int = 5,
        learner: str = "logistic", out: str | None = "BENCH_fleet.json"
        ) -> dict:
    Xs, classes = make_cohort(0, n=n, agents=agents, feats=3,
                              num_classes=num_classes)
    learners = _learners(learner, agents, steps)
    cfg = SessionConfig(num_classes=num_classes, max_rounds=rounds)
    plan = plan_for(learners, num_classes, max_rounds=rounds)
    keys = jax.random.split(jax.random.key(42), sessions)

    # --- eager engine loop (warm one session first: fit/predict caches)
    def eager_one(key):
        return Protocol(cfg).fit(key, endpoints_for(learners, Xs), classes)

    eager_one(keys[0])
    t0 = time.perf_counter()
    for s in range(sessions):
        eager_one(keys[s])
    eager_s = time.perf_counter() - t0

    # --- compiled program, sessions dispatched one by one
    compiled_session(plan, keys[0], Xs, classes).w.block_until_ready()
    t0 = time.perf_counter()
    for s in range(sessions):
        r = compiled_session(plan, keys[s], Xs, classes)
    r.w.block_until_ready()
    compiled_s = time.perf_counter() - t0

    # --- one vmapped fleet program for all sessions
    fleet_run(plan, keys, Xs, classes).w.block_until_ready()
    t0 = time.perf_counter()
    fleet = fleet_run(plan, keys, Xs, classes)
    fleet.w.block_until_ready()
    fleet_s = time.perf_counter() - t0

    result = {
        "config": {"sessions": sessions, "agents": agents, "rounds": rounds,
                   "steps": steps, "n": n, "num_classes": num_classes,
                   "learner": learner, "backend": jax.default_backend()},
        "eager": {"seconds": eager_s,
                  "sessions_per_sec": sessions / eager_s},
        "compiled": {"seconds": compiled_s,
                     "sessions_per_sec": sessions / compiled_s},
        "fleet": {"seconds": fleet_s,
                  "sessions_per_sec": sessions / fleet_s},
        "speedup_compiled_vs_eager": eager_s / compiled_s,
        "speedup_fleet_vs_eager": eager_s / fleet_s,
    }
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
    return result


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--agents", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--learner", default="logistic",
                    choices=["logistic", "mlp"])
    ap.add_argument("--out", default="BENCH_fleet.json")
    args = ap.parse_args()
    res = run(sessions=args.sessions, agents=args.agents, rounds=args.rounds,
              steps=args.steps, n=args.n, learner=args.learner, out=args.out)
    for mode in ("eager", "compiled", "fleet"):
        print(f"{mode}: {res[mode]['seconds']:.2f}s "
              f"({res[mode]['sessions_per_sec']:.2f} sessions/s)")
    print(f"fleet vs eager: {res['speedup_fleet_vs_eager']:.1f}x "
          f"(written to {args.out})")


if __name__ == "__main__":
    main()

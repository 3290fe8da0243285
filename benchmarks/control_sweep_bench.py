"""Control-plane sweep throughput: per-config recompiles vs ONE program.

Before PR 9, sweeping an :class:`AdaptiveController`'s thresholds or a
:class:`BudgetSpec`'s caps meant one XLA trace *per configuration* — the
values were baked into the jit-static :class:`SessionPlan`.  They are
traced operands now, so ``core.compiled.control_sweep_run`` runs N
configs inside one vmapped program with one compile.  This benchmark
times both paths over the same config grid and **asserts the compile
counter**: the sweep must trace exactly once no matter how many configs
ride it (``core.compiled.TRACE_COUNTS``) — the regression CI bench-smoke
guards.

Emits ``BENCH_control_sweep.json`` (seconds + traces per path, speedup).

  PYTHONPATH=src python benchmarks/control_sweep_bench.py --configs 4
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from benchmarks.fleet_bench import make_cohort
from repro.comm import BudgetSpec
from repro.comm.codecs import QuantCodec
from repro.core import compiled
from repro.core.compiled import compiled_session, control_sweep_run, plan_for
from repro.launch.compile_cache import enable_compile_cache
from repro.learners.logistic import LogisticRegression


def _caps(configs: int) -> list[int | None]:
    """A session-cap grid: tightening caps plus one uncapped config."""
    caps: list[int | None] = [None]
    caps += [60_000 - 12_000 * i for i in range(configs - 1)]
    return caps[:configs]


def run(*, configs: int = 4, agents: int = 3, rounds: int = 3,
        steps: int = 60, n: int = 256, num_classes: int = 5,
        out: str | None = "BENCH_control_sweep.json") -> dict:
    Xs, classes = make_cohort(0, n=n, agents=agents, feats=3,
                              num_classes=num_classes)
    learners = [LogisticRegression(steps=steps) for _ in range(agents)]
    ladder = (QuantCodec(bits=8), QuantCodec(bits=4))
    caps = _caps(configs)
    mk = lambda cap: plan_for(learners, num_classes, max_rounds=rounds,
                              budget=BudgetSpec(session_bits=cap,
                                                ladder=ladder))
    key = jax.random.key(7)
    keys = jnp.stack([key] * configs)

    # --- per-config static compiles: one trace per cap value
    for cap in caps:                                     # warm every cache
        compiled_session(mk(cap), key, Xs, classes).w.block_until_ready()
    t0 = time.perf_counter()
    singles = [compiled_session(mk(cap), key, Xs, classes) for cap in caps]
    singles[-1].w.block_until_ready()
    static_s = time.perf_counter() - t0

    # --- one vmapped sweep program: must trace exactly once
    compiled.TRACE_COUNTS.clear()
    control_sweep_run(mk(caps[0]), keys, Xs, classes,
                      session_bits=caps).w.block_until_ready()
    traces = dict(compiled.TRACE_COUNTS)
    assert traces == {"control_sweep": 1}, (
        f"control sweep re-traced: {traces} over {configs} configs")
    t0 = time.perf_counter()
    sweep = control_sweep_run(mk(caps[0]), keys, Xs, classes,
                              session_bits=caps)
    sweep.w.block_until_ready()
    sweep_s = time.perf_counter() - t0
    # the sweep stayed cached across the timed re-run too
    assert compiled.TRACE_COUNTS == {"control_sweep": 1}

    result = {
        "config": {"configs": configs, "agents": agents, "rounds": rounds,
                   "steps": steps, "n": n, "num_classes": num_classes,
                   "backend": jax.default_backend()},
        "static": {"seconds": static_s, "traces": configs},
        "sweep": {"seconds": sweep_s, "traces": traces["control_sweep"]},
        "speedup_sweep_vs_static": static_s / sweep_s,
    }
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
    return result


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--configs", type=int, default=4)
    ap.add_argument("--agents", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--out", default="BENCH_control_sweep.json")
    args = ap.parse_args()
    res = run(configs=args.configs, agents=args.agents, rounds=args.rounds,
              steps=args.steps, n=args.n, out=args.out)
    print(f"static: {res['static']['seconds']:.2f}s "
          f"({res['static']['traces']} traces)")
    print(f"sweep:  {res['sweep']['seconds']:.2f}s "
          f"({res['sweep']['traces']} trace)")
    print(f"sweep vs static: {res['speedup_sweep_vs_static']:.1f}x "
          f"(written to {args.out})")


if __name__ == "__main__":
    main()

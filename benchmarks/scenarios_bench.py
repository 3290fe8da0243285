"""Protocol x scenario frontier: ASCII vs FedAvg vs Assisted Learning
under adversarial-reality knobs, on the same wire.

Every (protocol, scenario) cell runs through the identical eager engine
loop and MeteredTransport ledger — GradientMsg / ResidualMsg / ignorance
traffic all priced by the same ``wire_bits`` rule — so the accuracy,
byte, and epsilon columns are directly comparable across protocols:

  * protocols — ``ascii`` (the paper's ignorance interchange), ``fedavg``
    (federated averaging over a homogeneous roster), ``al`` (assisted
    residual-fitting rounds).  All via :mod:`repro.scenarios.protocols`.
  * scenarios — ``clean``, ``noniid`` (Dirichlet label skew), ``churn``
    (stragglers + permanent dropout): the :data:`repro.scenarios.PRESETS`
    entries the CLI shares.
  * dp rows   — the same grid under per-release Gaussian DP, composed by
    the RDP accountant (subsampled-RDP amplification on the ``subsample``
    scenario) — the epsilon column of the frontier.

Emits ``BENCH_scenarios.json`` with one row per cell.  ``--check``
asserts the schema plus two invariants the CI bench-smoke gates on:
every protocol books nonzero training bits through the shared ledger,
and ASCII beats (or ties) FedAvg on the clean vertical-partition cell —
feature-split data is exactly where logit-averaged local models lose to
the interchange.

  PYTHONPATH=src python benchmarks/scenarios_bench.py --rounds 4 --check
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.comm import GaussianMechanism
from repro.control import make_accountant
from repro.core.engine import (MeteredTransport, Protocol, SessionConfig,
                               endpoints_for)
from repro.data import synthetic
from repro.data.partition import train_test_split, vertical_split
from repro.launch.compile_cache import enable_compile_cache
from repro.learners.logistic import LogisticRegression
from repro.scenarios import PRESETS, make_variant

SCENARIOS = ("clean", "noniid", "churn")
PROTOCOL_NAMES = ("ascii", "fedavg", "al")


def _cohort(seed: int, n: int):
    """The Fig. 3 vertical partition (4 agents x 2 features, 10 classes):
    homogeneous blocks, so every protocol — including FedAvg's shared-shape
    roster — runs on the identical split."""
    ds = synthetic.blob_fig3(jax.random.key(seed), n=n)
    tr, te = train_test_split(seed, ds.X.shape[0])
    Xs = vertical_split(ds.X, ds.splits)
    return ([x[tr] for x in Xs], [x[te] for x in Xs],
            ds.classes[tr], ds.classes[te], ds.num_classes)


def run_cell(protocol: str, scenario_name: str, *, rounds: int, steps: int,
             n: int, dp_epsilon: float = 0.0, seed: int = 0) -> dict:
    """One frontier cell: fit `protocol` under `scenario_name`, return the
    accuracy / train-bits / epsilon row."""
    Xtr, Xte, ctr, cte, k = _cohort(seed, n)
    scenario = PRESETS[scenario_name]
    privacy = (GaussianMechanism(epsilon=dp_epsilon,
                                 nonneg=(protocol == "ascii"))
               if dp_epsilon > 0 else None)
    accountant = (make_accountant("rdp", q=scenario.subsample)
                  if privacy is not None else None)
    transport = MeteredTransport(privacy=privacy, accountant=accountant)
    engine = Protocol(SessionConfig(num_classes=k, max_rounds=rounds),
                      transport=transport, variant=make_variant(protocol),
                      scenario=None if scenario.trivial else scenario)
    endpoints = endpoints_for([LogisticRegression(steps=steps)
                               for _ in Xtr], Xtr)
    t0 = time.perf_counter()
    fitted = engine.fit(jax.random.key(seed + 1), endpoints, ctr)
    seconds = time.perf_counter() - t0
    report = (transport.accountant.report(privacy)
              if accountant is not None else {})
    return {
        "protocol": protocol,
        "scenario": scenario_name,
        "dp_epsilon": dp_epsilon,
        "acc": float(jnp.mean(fitted.predict(Xte) == cte)),
        "train_bits": int(transport.total_bits),
        # worst-case agent under composition; 0.0 when the channel is clean
        "epsilon": max((float(v["epsilon"]) for v in report.values()),
                       default=0.0),
        "rounds_run": int(fitted.num_rounds),
        "seconds": seconds,
    }


def check(result: dict) -> None:
    """Schema + invariant gate (the CI bench-smoke assertions)."""
    rows = result["rows"]
    keys = {"protocol", "scenario", "dp_epsilon", "acc", "train_bits",
            "epsilon", "rounds_run", "seconds"}
    for r in rows:
        missing = keys - set(r)
        assert not missing, f"row {r} missing {sorted(missing)}"
    cells = {(r["protocol"], r["scenario"], r["dp_epsilon"] > 0): r
             for r in rows}
    for p in PROTOCOL_NAMES:
        for s in SCENARIOS:
            assert (p, s, False) in cells, f"missing cell ({p}, {s})"
            assert cells[p, s, False]["train_bits"] > 0, \
                f"({p}, {s}) booked no wire bits through the shared ledger"
    # equal (uncapped fp32) wire rules, vertically split features: the
    # interchange must not lose to logit-averaged local models
    assert cells["ascii", "clean", False]["acc"] + 1e-9 >= \
        cells["fedavg", "clean", False]["acc"], \
        (f"ascii clean acc {cells['ascii', 'clean', False]['acc']:.3f} < "
         f"fedavg clean acc {cells['fedavg', 'clean', False]['acc']:.3f}")
    for r in rows:
        if r["dp_epsilon"] > 0:
            assert r["epsilon"] > 0.0, \
                f"DP row ({r['protocol']}, {r['scenario']}) composed eps=0"


def run(*, rounds: int = 4, steps: int = 80, n: int = 240,
        dp_epsilon: float = 2.0, out: str | None = "BENCH_scenarios.json"
        ) -> dict:
    rows = []
    for p in PROTOCOL_NAMES:
        for s in SCENARIOS:
            rows.append(run_cell(p, s, rounds=rounds, steps=steps, n=n))
    if dp_epsilon > 0:
        # the epsilon column: clean-channel DP plus the subsampled-RDP
        # amplification cell (q = 0.5 participation per round)
        for p in PROTOCOL_NAMES:
            for s in ("clean", "subsample"):
                rows.append(run_cell(p, s, rounds=rounds, steps=steps, n=n,
                                     dp_epsilon=dp_epsilon))
    result = {
        "config": {"rounds": rounds, "steps": steps, "n": n,
                   "dp_epsilon": dp_epsilon, "dataset": "blob3",
                   "learner": "logistic",
                   "backend": jax.default_backend()},
        "rows": rows,
    }
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
    return result


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--n", type=int, default=240)
    ap.add_argument("--dp-epsilon", type=float, default=2.0,
                    help="per-release epsilon for the DP rows (0 = skip)")
    ap.add_argument("--out", default="BENCH_scenarios.json")
    ap.add_argument("--check", action="store_true",
                    help="assert schema + ledger/accuracy invariants "
                         "(the CI bench-smoke gate)")
    args = ap.parse_args()
    res = run(rounds=args.rounds, steps=args.steps, n=args.n,
              dp_epsilon=args.dp_epsilon, out=args.out)
    for r in res["rows"]:
        dp = f",eps={r['epsilon']:.3f}" if r["dp_epsilon"] > 0 else ""
        print(f"{r['protocol']},{r['scenario']},acc={r['acc']:.3f},"
              f"bits={r['train_bits']}{dp}")
    if args.check:
        check(res)
        print(f"check: ok ({len(res['rows'])} rows)")
    print(f"written to {args.out}")


if __name__ == "__main__":
    main()

"""Paper Fig. 6: ASCII vs ASCII-Random vs ASCII-Simple vs Ensemble-AdaBoost.

(a) 20-class blobs, 20 agents x 1 feature, logistic regression;
(b) wine(-surrogate), 11 agents x 1 feature, decision trees.
Also runs the beyond-paper ASCII-Async variant (the paper's open problem on
asynchronous interchange) for comparison."""
from __future__ import annotations

import argparse

import jax
import numpy as np

from benchmarks.common import acc, curve_vs_rounds, split_dataset
from repro.core.engine import (Protocol, SessionConfig, endpoints_for,
                               variant_setup)
from repro.core.protocol import ASCIIConfig, fit_ensemble_adaboost
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.learners.logistic import LogisticRegression
from repro.learners.tree import DecisionTree


def run(reps: int = 2, rounds: int = 6, quick: bool = True) -> list[dict]:
    key = jax.random.key(13)
    wine = synthetic.wine_surrogate(jax.random.fold_in(key, 1))
    wine = synthetic.Dataset("wine", wine.X, wine.classes, wine.num_classes,
                             tuple([1] * 11))
    cases = {
        "blob20": (synthetic.blob_fig6(jax.random.fold_in(key, 0),
                                       n=600 if quick else 1000),
                   lambda: LogisticRegression(steps=150)),
        "wine": (wine, lambda: DecisionTree(depth=3, num_thresholds=8)),
    }
    variants = ["ascii", "simple", "random", "async"]
    rows = []
    for name, (ds, mk) in cases.items():
        for variant in variants + ["ensemble_ada"]:
            finals, curves = [], []
            for rep in range(reps):
                Xtr, ctr, Xte, cte = split_dataset(ds, rep)
                k = jax.random.fold_in(key, hash((name, variant, rep)) % 2**31)
                learners = [mk() for _ in ds.splits]
                if variant == "ensemble_ada":
                    cfg = ASCIIConfig(num_classes=ds.num_classes,
                                      max_rounds=rounds)
                    fitted = fit_ensemble_adaboost(k, Xtr, ctr, learners, cfg)
                    finals.append(acc(fitted.predict(Xte), cte))
                    curves.append([acc(fitted.predict(Xte, max_round=t), cte)
                                   for t in range(rounds)])
                else:
                    # engine API: the variant string is just a scheduler +
                    # alpha-policy pair
                    scheduler, upstream = variant_setup(variant)
                    cfg6 = SessionConfig(num_classes=ds.num_classes,
                                         max_rounds=rounds, upstream=upstream)
                    fitted = Protocol(cfg6, scheduler=scheduler).fit(
                        k, endpoints_for(learners, Xtr), ctr)
                    finals.append(acc(fitted.predict(Xte), cte))
                    curves.append(curve_vs_rounds(fitted, Xte, cte, rounds))
            arr = np.asarray(curves, np.float64)
            rows.append({"figure": "fig6", "dataset": name, "method": variant,
                         "final_acc": float(np.nanmean(finals)),
                         "curve": [round(float(x), 4)
                                   for x in np.nanmean(arr, 0)]})
    return rows


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    for r in run(args.reps, args.rounds, quick=not args.full):
        print(f"{r['dataset']},{r['method']},{r['final_acc']:.4f},{r['curve']}")


if __name__ == "__main__":
    main()

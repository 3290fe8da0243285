"""Paper Fig. 3: out-sample accuracy vs assistance rounds for ASCII /
Single / Oracle on Blob, MIMIC(-surrogate), QSAR(-surrogate),
Wine(-surrogate).  Models per the paper: random forest on Blob, decision
trees elsewhere."""
from __future__ import annotations

import argparse

import jax
import numpy as np

from benchmarks.common import run_three_way
from repro.core.protocol import ASCIIConfig
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.learners.forest import RandomForest
from repro.learners.tree import DecisionTree


def datasets(key, quick: bool):
    n_mimic = 2000 if quick else 15000
    return {
        "blob": (synthetic.blob_fig3(jax.random.fold_in(key, 0)),
                 lambda: RandomForest(num_trees=8, depth=4)),
        "mimic": (synthetic.mimic_surrogate(jax.random.fold_in(key, 1),
                                            n=n_mimic),
                  lambda: DecisionTree(depth=4)),
        "qsar": (synthetic.qsar_surrogate(jax.random.fold_in(key, 2)),
                 lambda: DecisionTree(depth=4)),
        "wine": (synthetic.wine_surrogate(jax.random.fold_in(key, 3)),
                 lambda: DecisionTree(depth=4)),
    }


def run(reps: int = 3, rounds: int = 8, quick: bool = True) -> list[dict]:
    key = jax.random.key(42)
    rows = []
    for name, (ds, mk) in datasets(key, quick).items():
        cfg = ASCIIConfig(num_classes=ds.num_classes, max_rounds=rounds)
        curves = {"ascii": [], "single": [], "oracle": []}
        for rep in range(reps):
            out = run_three_way(jax.random.fold_in(key, 100 + rep), ds,
                                [mk() for _ in ds.splits], cfg, seed=rep)
            for k in curves:
                curves[k].append(out[k])
        for method, cs in curves.items():
            arr = np.asarray(cs, dtype=np.float64)
            final = arr[:, -1]
            rows.append({"figure": "fig3", "dataset": name, "method": method,
                         "final_acc": float(np.nanmean(final)),
                         "stderr": float(np.nanstd(final) / max(len(final), 1) ** 0.5),
                         "curve": [round(float(x), 4)
                                   for x in np.nanmean(arr, axis=0)]})
    return rows


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    for r in run(args.reps, args.rounds, quick=not args.full):
        print(f"{r['dataset']},{r['method']},{r['final_acc']:.4f},"
              f"{r['stderr']:.4f},{r['curve']}")


if __name__ == "__main__":
    main()

"""Readings that set the limits of a backbone cell's ``correct``, and the
witnesses behind them.  Run on the chip, not by the benchmark's own runs.

    python3 bench/calibrate_backbone.py --seeds 2147485001

Per seed, one session of ``dsv2lite.session.notes`` (the key of the cell's
first session) through the timed path, then one JSON line per reading:

- ``session``: its wall time and the device's memory peak;
- ``fit``, one per side: the first hop's fit from the same initial weights
  and draws against the reference's (float32, ``HIGHEST``): the RMSNorm
  gains' ``scale_update_gap``, the whole move's relative gap, each leaf's
  ``update_gap``, and the mean cross-entropy of the first ``LOSS_ROWS``
  subjects (the reference fit's on a line of its own).  Sides: ``program`` (the
  session's first hop), ``witness`` (the reference itself with float32
  parameters and products at JAX's default precision, the program's
  precision) and ``control`` (the reference wholly in bfloat16);
- ``check``, for the program and the control: every number the cell's
  check reads, beside its limit, and ``correct``;
- ``fault``: ``fit_gap`` with a fault planted in the program's fit (its
  AdamW step on the negated gradient; half of each minibatch).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)

from bench import ref_backbone  # noqa: E402

CELL = "dsv2lite.session.notes"
LOSS_ROWS = 128
FAULTS = ("update_sign_flipped", "minibatch_halved")


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


def fit_readings(got, want, sub, ref, tokens, onehot):
    """``got`` against the reference fit ``want`` from the key ``sub``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def moves(got, want, sub):
        start = ref_backbone.init(sub, ref.shape, jnp.float32)

        def norms(g, w, s):
            g, w, s = (x.astype(jnp.float32) for x in (g, w, s))
            return jnp.stack([jnp.sum(jnp.square(g - w)),
                              jnp.sum(jnp.square(w - s))])
        per_leaf = jax.tree.map(norms, got, want, start)
        return (ref_backbone.scale_update_gap(got, want, start), per_leaf)

    scale, per_leaf = jax.device_get(moves(got, want, sub))
    leaves = jax.tree_util.tree_leaves_with_path(per_leaf)
    leaf_gap = {jax.tree_util.keystr(p): float((v[0] / max(v[1], 1e-30))
                                              ** 0.5)
                for p, v in leaves}
    whole = float((sum(v[0] for _, v in leaves)
                   / sum(v[1] for _, v in leaves)) ** 0.5)
    return {"scale_update_gap": float(scale), "whole_update_gap": whole,
            "update_gap_worst_leaf": max(leaf_gap.values()),
            "update_gap_by_leaf": leaf_gap,
            "loss": loss(got, ref, tokens, onehot)}


def loss(params, ref, tokens, onehot) -> float:
    """Mean cross-entropy over the first ``LOSS_ROWS`` subjects, by the
    float32 reference's forward at ``HIGHEST``."""
    import jax
    z = ref.scores(params, tokens[:LOSS_ROWS])
    ll = (jax.numpy.sum(onehot[:LOSS_ROWS] * z, -1)
          - jax.nn.logsumexp(z, -1))
    return float(-jax.numpy.mean(ll))


def session(cell):
    """One session on the cell's first session key: what its check
    keeps, and the data."""
    import jax
    from bench import data_backbone, program, run
    from bench.traffic import backbone_session_queue as bq
    from bench.traffic.session_queue import session_key
    from repro.core.engine import endpoints_for

    config = cell.config
    blocks, classes = data_backbone.make(config,
                                         jax.random.fold_in(cell.key, 0))
    key = session_key(cell.key, 0)
    t = time.perf_counter()
    proto = program.protocol(config)
    fitted = proto.fit(key, endpoints_for(bq.learners(config), list(blocks)),
                       classes)
    got = bq.kept_session(key, proto, fitted)
    del proto, fitted
    jax.block_until_ready(got["first"])
    emit(seed=cell.seed, line="session", wall_s=time.perf_counter() - t,
         memory_peak_bytes=run.memory_peak_bytes())
    return got, blocks, classes


def calibrate(cell) -> None:
    import jax
    import jax.numpy as jnp
    import pytest
    from bench.traffic import backbone_session_queue as bq
    from bench.traffic.session_queue import with_limits

    sys.path.insert(0, str(ROOT / "tests" / "bench"))
    import bench_small_backbone as small

    got, blocks, classes = session(cell)

    config, seed = cell.config, cell.seed
    limits = config["limits"]["session"]
    agent = config["agents"][0]
    k = int(config["num_classes"])
    n = int(classes.shape[0])
    tokens = blocks[0]
    onehot = jax.nn.one_hot(classes, k)
    uniform = jnp.full((n,), 1.0 / n, jnp.float32)
    f32 = ref_backbone.backbone_from(config, agent)
    ctl = ref_backbone.backbone_from(config, agent, "bfloat16")
    witness = Witness(f32.shape, f32.steps, f32.batch, f32.lr)
    sub = ref_backbone.first_sub(got["key"])

    want = f32.fit(sub, tokens, onehot, uniform)
    first = got.pop("first")
    emit(seed=seed, line="fit", side="reference",
         loss=loss(want, f32, tokens, onehot))
    prog_fit = fit_readings(first, want, sub, f32, tokens, onehot)
    emit(seed=seed, line="fit", side="program", **prog_fit)
    moved = witness.fit(sub, tokens, onehot, uniform)
    emit(seed=seed, line="fit", side="witness",
         **fit_readings(moved, want, sub, f32, tokens, onehot))
    del moved
    ctl_first = ctl.fit(sub, tokens, onehot, uniform)
    ctl_fit = fit_readings(ctl_first, want, sub, f32, tokens, onehot)
    emit(seed=seed, line="fit", side="control", **ctl_fit)
    del want

    # the control's check: its own round 0 from its first fit
    r = ref_backbone.round_zero(got["key"], blocks, classes, config,
                                "bfloat16", first=ctl_first)
    c_got = {"key": got["key"], "ledger": r.ledger, "alphas": r.alphas,
             "components": [c[:3] for c in r.components]}
    del r
    cond = ref_backbone.round_zero(got["key"], blocks, classes, config,
                                   first=ctl_first)
    del ctl_first
    numbers = bq.round_numbers(c_got, cond, ctl_fit["scale_update_gap"])
    nxt = ref_backbone.first_sub(cond.key)
    w = cond.w.astype(jnp.float32)
    del cond
    numbers["fit_gap"] = float(ref_backbone.update_gap(
        ctl.fit(nxt, tokens, onehot, w, bq.FIT_GAP_STEPS),
        f32.fit(nxt, tokens, onehot, w, bq.FIT_GAP_STEPS), f32.init(nxt)))
    checks = with_limits(numbers, limits)
    emit(seed=seed, line="check", side="control",
         correct=all(v <= lim for _, v, lim in checks), checks=checks)

    # the program's check, as the cell computes it, and planted faults
    cond = ref_backbone.round_zero(got["key"], blocks, classes, config,
                                   first=first)
    del first
    numbers = bq.round_numbers(got, cond, prog_fit["scale_update_gap"])
    nxt = ref_backbone.first_sub(cond.key)
    w = cond.w.astype(jnp.float32)
    del cond                          # it holds the first hop's parameters
    fit = bq.program_fit(config, nxt, tokens, classes, w)
    short = f32.fit(nxt, tokens, onehot, w, bq.FIT_GAP_STEPS)
    start = f32.init(nxt)
    numbers["fit_gap"] = float(ref_backbone.update_gap(fit, short, start))
    del fit
    checks = with_limits(numbers, limits)
    emit(seed=seed, line="check", side="program", w_max_over_mean=float(
        jnp.max(w) * n), correct=all(v <= lim for _, v, lim in checks),
        checks=checks)
    for fault in FAULTS:
        mp = pytest.MonkeyPatch()
        small.plant(mp, fault)
        gap = float(ref_backbone.update_gap(
            bq.program_fit(config, nxt, tokens, classes, w), short, start))
        mp.undo()
        small.clear_programs()
        emit(seed=seed, line="fault", fault=fault, fit_gap=gap,
             limit=limits["fit_gap"], correct=gap <= limits["fit_gap"])


@dataclasses.dataclass(frozen=True)
class Witness(ref_backbone.Backbone):
    """The reference with float32 parameters and products at JAX's default
    precision (the program's)."""

    @property
    def precision(self):
        return "default"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from bench import program, run
    from bench.calibrate import seeds
    program.ensure_importable()
    import jax
    bench = run.load_benchmark()
    for seed in seeds(args.seeds):
        cell = run.load_cell(bench, CELL, seed, 0.0, False)
        cell.key = jax.random.key(seed)
        calibrate(cell)
    return 0


if __name__ == "__main__":
    sys.exit(main())

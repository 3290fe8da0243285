"""Cells of the chip benchmark cut to a size the CPU test run holds, and
the faults a run's check has to catch, planted in the timed path."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import program, run  # noqa: E402

program.ensure_importable()

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
SMALL = {
    "fashion_halves_mlp": {"n": 1200, "hidden": [32, 16], "steps": 30},
    "blob20_logistic": {"n": 1100, "n_train": 1000, "steps": 40,
                        "rounds": 2},
}


def small_cell(workload: str, seed: int, seconds: float = 0.5):
    """The cell at test size: fewer rows, steps and rounds, narrower hidden
    layers; every limit as the configuration sets it."""
    bench = run.load_benchmark()
    cell = run.load_cell(bench, workload, seed, seconds, False)
    cfg, cut = cell.config, SMALL[cell.config["name"]]
    cfg["dataset"]["n"] = cut["n"]
    if "n_train" in cut:
        cfg["dataset"]["n_train"] = cut["n_train"]
    cfg["learner"]["steps"] = cut["steps"]
    if "hidden" in cut:
        cfg["learner"]["hidden"] = cut["hidden"]
    cfg["rounds"] = cut.get("rounds", cfg["rounds"])
    return bench, cell


def clear_programs():
    """Forget every compiled program, so the next run traces the program
    as it stands (planted faults included)."""
    from repro.core import compiled
    from repro.learners import base
    for fn in (compiled._session_program, base.jitted_fresh_fit):
        fn.cache_clear()


def plant(monkeypatch, fault: str) -> None:
    """Break the timed path underneath the harness:

    - ``state_unchanged``: every fit returns its initial parameters;
    - ``half_batch``: every fit sees only the first half of the rows, its
      weighted mean taken over them;
    - ``reweight_skipped``: the ignorance vector is left unchanged between
      hops;
    - ``reweight_flipped``: the ignorance update runs with the model
      weight's sign flipped;
    - ``answer_altered``: each session's alphas are shifted where the
      fitted ensemble is built.
    """
    from repro.core import compiled
    from repro.learners.logistic import LogisticCore
    from repro.learners.mlp import MLPCore

    for core in (MLPCore, LogisticCore):
        fit = core.fit
        if fault == "state_unchanged":
            monkeypatch.setattr(core, "fit",
                                lambda self, params, key, X, onehot, w:
                                params)
        elif fault == "half_batch":
            def half(self, params, key, X, onehot, w, _fit=fit):
                h = X.shape[0] // 2
                return _fit(self, params, key, X[:h], onehot[:h], w[:h])
            monkeypatch.setattr(core, "fit", half)
    if fault.startswith("reweight_"):
        make = compiled._make_reweight

        def broken(plan, _make=make):
            update = _make(plan)
            if fault == "reweight_skipped":
                return lambda w, r, a: w
            return lambda w, r, a: update(w, r, -a)
        monkeypatch.setattr(compiled, "_make_reweight", broken)
    if fault == "answer_altered":
        build = compiled.fitted_from_result

        def shifted(*args, **kw):
            fitted = build(*args, **kw)
            for c in fitted.components:
                c.alpha += 0.5
            return fitted
        monkeypatch.setattr(compiled, "fitted_from_result", shifted)
    clear_programs()


FAULTS = ("state_unchanged", "half_batch", "reweight_skipped",
          "reweight_flipped", "answer_altered")


def sound_result(workload: str, seed: int) -> dict:
    bench, cell = small_cell(workload, seed)
    return run.run_cell(cell, bench, DEVICE)


def faulty_result(workload: str, seed: int, fault: str, monkeypatch) -> dict:
    bench, cell = small_cell(workload, seed)
    plant(monkeypatch, fault)
    return run.run_cell(cell, bench, DEVICE)


def session_control_checks(workload: str, seed: int) -> list:
    """The session check with the reference in bfloat16 put in the
    program's place: ``[(name, value, limit)]``."""
    import jax
    from bench import data, ref
    from bench.traffic import session_queue as sq
    _, cell = small_cell(workload, seed)
    cfg = cell.config
    key = jax.random.key(cell.seed)
    Xtr, ctr, _, _ = data.make(cfg, jax.random.fold_in(key, 0), cell.seed)
    skey = sq.session_key(key, 0)
    want = sq.reference_result(ref.session(skey, Xtr, ctr, cfg))
    got = sq.reference_result(ref.session(skey, Xtr, ctr, cfg, "bfloat16"))
    return sq.with_limits(
        sq.session_numbers(got, want, cfg, sq.first_loss(cfg, Xtr, ctr)),
        cfg["limits"]["session"])

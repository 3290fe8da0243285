"""Readings that set the limits of ``correct``: the program's on many seeds,
and the control's.  Run on the chip, not by the benchmark's own runs.

    python3 bench/calibrate.py --workload <cell> --seeds 101-112 \
        --control-seeds 101-103

Prints one JSON line per seed and side: every number ``session_numbers``
reads, and both sides' alphas:

- ``program``: one session through the timed path
  (``Protocol.fit(backend="compiled")`` at the cell's sizes) against the
  reference;
- ``control``: the reference computed in bfloat16 put in the program's
  place, against the float32 reference, on the same numbers.

The lower reading of a number is the largest the program gives, the upper
the smallest the control gives; ``PERF.md`` records both and the limit set
between them.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def readings(got, want, config, loss) -> dict:
    from bench.traffic.session_queue import session_numbers
    return dict(session_numbers(got, want, config, loss),
                alphas=[c[2] for c in got["components"]],
                ref_alphas=[c[2] for c in want["components"]])


def session_cell(cell, program_seeds, control_seeds) -> None:
    import jax
    from bench import data, ref
    from bench.traffic import session_queue as sq

    for seed in sorted(set(program_seeds) | set(control_seeds)):
        cell.seed, cell.key = seed, jax.random.key(seed)
        traffic = sq.Traffic(cell)
        Xtr, ctr, _, _ = data.make(cell.config,
                                   jax.random.fold_in(cell.key, 0), seed)
        traffic.Xtr, traffic.ctr = Xtr, ctr
        key = sq.session_key(cell.key, 0)
        want = sq.reference_result(ref.session(key, Xtr, ctr, cell.config))
        loss = sq.first_loss(cell.config, Xtr, ctr)
        if seed in program_seeds:
            proto, fitted = traffic._fit(key, None)
            got = sq.program_result({"fitted": fitted,
                                     "ledger": sq.ledger(proto)})
            emit({"seed": seed, "side": "program",
                  **readings(got, want, cell.config, loss)})
        if seed in control_seeds:
            got = sq.reference_result(
                ref.session(key, Xtr, ctr, cell.config, "bfloat16"))
            emit({"seed": seed, "side": "control",
                  **readings(got, want, cell.config, loss)})


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    from bench import program, run
    bench = run.load_benchmark()
    cell = run.load_cell(bench, args.workload, 0, 0.0, False)
    program.ensure_importable()
    run.require_chips(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    session_cell(cell, seeds(args.seeds), seeds(args.control_seeds))
    return 0


if __name__ == "__main__":
    sys.exit(main())

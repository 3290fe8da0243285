"""The chip benchmark's traffic (bench/traffic): session keys fixed by the
seed, and the harness refusing to run without a TPU or without the
program."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def test_bench_traffic_session_keys_are_fixed_by_the_seed():
    import jax
    from bench.traffic import session_queue as sq
    k = jax.random.key(2**31 + 5)
    a = jax.random.key_data(sq.session_key(k, 3))
    b = jax.random.key_data(sq.session_key(jax.random.key(2**31 + 5), 3))
    c = jax.random.key_data(sq.session_key(k, 4))
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def _run_bench(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "fashion.session.int8", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_bench_run_refuses_to_run_without_a_tpu():
    done = _run_bench(ROOT)
    assert done.returncode != 0
    assert "no TPU" in done.stderr
    assert '"correct"' not in done.stdout


def test_bench_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    done = _run_bench(tmp_path)
    assert done.returncode != 0
    assert '"correct"' not in done.stdout

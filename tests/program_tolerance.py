"""Comparing float results of two separately compiled XLA programs.

Eager and compiled sessions, and a traced-qmax sweep against static-qmax
runs, are different XLA programs.  The compiler may fuse, reorder or
constant-fold their float reductions differently, so the same float
result can differ in its last bits: up to 4.8e-7 absolute on alphas of
about 3.5 (1.5e-7 relative, 1-2 ulp of float32) in the pins below, and a
fit of a few dozen steps can carry such a difference on into its params.
The chip's compiler makes other choices again, so no bit pin can hold
across programs.  Float results are therefore compared to ``RTOL``/``ATOL``,
a few hundred ulp of headroom and far below any difference a protocol bug
makes (a wrong alpha or reweight moves results at the 1e-2 level).
Integers, ledgers, bit counts, component lists and predictions stay exact.
"""
import numpy as np

RTOL = 1e-5
ATOL = 1e-7


def assert_floats_close(actual, desired) -> None:
    np.testing.assert_allclose(np.asarray(actual), np.asarray(desired),
                               rtol=RTOL, atol=ATOL)


def assert_history_close(actual: list, desired: list) -> None:
    """Per-round history dicts: the same keys and integer entries, float
    entries (alphas, accuracies) within tolerance."""
    assert len(actual) == len(desired)
    for ra, rd in zip(actual, desired):
        assert ra.keys() == rd.keys()
        for name in ra:
            if isinstance(ra[name], (float, list)):
                assert_floats_close(ra[name], rd[name])
            else:
                assert ra[name] == rd[name], name

"""What decides ``correct`` in the blob20.session.adaptive cell of the
chip benchmark, at a size the CPU test run holds: a sound run passes; the
control (the plain reference in bfloat16 put in the program's place) and
every fault planted in the timed path fail."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_small  # noqa: E402

CELL = "blob20.session.adaptive"


@pytest.fixture(autouse=True)
def fresh_programs():
    bench_small.clear_programs()
    yield
    bench_small.clear_programs()


def test_bench_check_blob20_sound_run_is_correct():
    result = bench_small.sound_result(CELL, 2**31 + 3)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"


def test_bench_check_blob20_control_fails():
    checks = bench_small.session_control_checks(CELL, 2**31 + 4)
    assert any(value > limit for _, value, limit in checks), checks


@pytest.mark.parametrize("fault", bench_small.FAULTS)
def test_bench_check_blob20_fault_fails(fault, monkeypatch):
    result = bench_small.faulty_result(CELL, 2**31 + 5, fault, monkeypatch)
    assert not result["correct"], result["checks"]

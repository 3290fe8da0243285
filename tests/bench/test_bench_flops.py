"""Operation and byte counts of the chip benchmark (bench/flops.py) and its
table of peaks (bench/peaks.json), against small cases worked by hand."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops  # noqa: E402


def test_bench_flops_mlp_step_by_hand():
    # n = 3 rows through 4 -> 5 -> 2: forward 2*3*(4*5 + 5*2) = 180, weight
    # gradients as many, input gradients of the second layer 2*3*5*2 = 60
    dims = flops.mlp_dims(4, (5,), 2)
    assert dims == (4, 5, 2)
    assert flops.mlp_forward_flops(3, dims) == 180
    assert flops.mlp_step_flops(3, dims) == 180 + 180 + 60


def test_bench_flops_fashion_step_matches_the_published_shapes():
    # 49,000 rows, 392 -> 128 -> 64 -> 10: forward 5.78 GFLOP, the same for
    # the weight gradients, 0.87 GFLOP of input gradients for layers 2-3
    dims = flops.mlp_dims(392, (128, 64), 10)
    assert flops.mlp_forward_flops(49_000, dims) == 2 * 49_000 * 59_008
    assert flops.mlp_step_flops(49_000, dims) == pytest.approx(12.43e9,
                                                               rel=1e-3)


def test_bench_flops_logistic_and_hop_by_hand():
    assert flops.logistic_forward_flops(10, 3, 4) == 240
    assert flops.logistic_step_flops(10, 3, 4) == 480
    hop = flops.hop_flops({"kind": "logistic", "steps": 5}, 10, 3, 4)
    assert hop == 5 * 480 + 240
    mlp = flops.hop_flops({"kind": "mlp", "steps": 2, "hidden": [5]}, 3, 4, 2)
    assert mlp == 2 * 420 + 180
    with pytest.raises(ValueError):
        flops.hop_flops({"kind": "tree", "steps": 1}, 1, 1, 1)


@pytest.mark.parametrize("n,tiles", [(2048, 2), (1024, 1), (49_000, 1),
                                     (100, 1)])
def test_bench_flops_quantize_dequant_bytes_by_hand(n, tiles):
    # read x and u (f32), write the dequantized f32 vector, the int8 values
    # and one f32 scale per tile
    f, b = flops.quantize_dequant_cost(n)
    assert flops.quantize_tiles(n) == tiles
    assert b == 4 * n + 4 * n + 4 * n + n + 4 * tiles
    assert f == 8 * n


def test_bench_flops_peaks_of_the_v5e_and_unknown_kinds():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_bench_flops_roofline_share_is_the_larger_bound_over_time():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # bytes bound: 50 bytes take 5 s; measured 10 s -> 50 %
    assert flops.roofline_share(100.0, 50.0, 10.0, peak) == pytest.approx(50)
    # flops bound: 1000 FLOPs take 10 s; measured 20 s -> 50 %
    assert flops.roofline_share(1000.0, 5.0, 20.0, peak) == pytest.approx(50)
    with pytest.raises(ValueError):
        flops.roofline_share(1.0, 1.0, 0.0, peak)

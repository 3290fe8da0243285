"""Operations and bytes the algorithm needs, computed from shapes.

Every count here is the work the ASCII session or a kernel call requires,
not what a compiler happens to emit: matrix multiplications count 2 FLOPs
per multiply-add, elementwise work is left out of the model FLOPs, and
bytes are the unpadded arrays a call must read and write once.  The peaks
come from ``peaks.json``, keyed by the device kind JAX reports.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown
    device is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


# ------------------------------------------------------------------ learners
def mlp_dims(p: int, hidden, k: int) -> tuple:
    return (int(p),) + tuple(int(h) for h in hidden) + (int(k),)


def mlp_forward_flops(n: int, dims) -> int:
    """One forward pass of the MLP over n rows."""
    return 2 * n * sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def mlp_step_flops(n: int, dims) -> int:
    """One full-batch training step: the forward pass, the weight gradients
    of every layer, and the input gradients of every layer but the first
    (nothing upstream of the features needs them)."""
    layers = list(zip(dims[:-1], dims[1:]))
    fwd = 2 * n * sum(a * b for a, b in layers)
    wgrad = fwd
    igrad = 2 * n * sum(a * b for a, b in layers[1:])
    return fwd + wgrad + igrad


def logistic_forward_flops(n: int, p: int, k: int) -> int:
    return 2 * n * p * k


def logistic_step_flops(n: int, p: int, k: int) -> int:
    """Forward logits and the weight gradient; the features need none."""
    return 4 * n * p * k


def hop_flops(learner: dict, n: int, p: int, k: int) -> int:
    """One hop of the session: the weighted fit (``steps`` full-batch
    steps) and the predict that scores its reward."""
    steps = int(learner["steps"])
    if learner["kind"] == "mlp":
        dims = mlp_dims(p, learner["hidden"], k)
        return steps * mlp_step_flops(n, dims) + mlp_forward_flops(n, dims)
    if learner["kind"] == "logistic":
        return (steps * logistic_step_flops(n, p, k)
                + logistic_forward_flops(n, p, k))
    raise ValueError(f"unknown learner kind {learner['kind']!r}")


# ------------------------------------------------------------------- kernels
def quantize_tiles(n: int, bn: int = 1024) -> int:
    """Scale tiles of a length-n vector: ``bn`` per tile when it divides n,
    else one tile (the wire format's rule)."""
    return n // bn if (n >= bn and n % bn == 0) else 1


def quantize_dequant_cost(n: int, bn: int = 1024) -> tuple[int, int]:
    """(FLOPs, bytes) of one fused quantize-dequant of a length-n f32
    vector: read x and the rounding draws u (f32 each); write the
    dequantized f32 vector, the int8 values and one f32 scale per tile.
    FLOPs: abs and max for the scale, then divide, add, floor, two clips
    and the multiply back, per element."""
    flops = 8 * n
    nbytes = 4 * n + 4 * n + 4 * n + n + 4 * quantize_tiles(n, bn)
    return flops, nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """The least time the chip could take for the work, the larger of FLOPs
    over the bf16 peak and bytes over HBM bandwidth, as a share (in %) of
    the measured ``seconds``."""
    if seconds <= 0:
        raise ValueError(f"measured time must be positive, got {seconds}")
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds

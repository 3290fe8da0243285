"""Fused ignorance-score update (paper eqs. 10/12) — Pallas TPU kernel.

The interchange hot-path op: w * exp(alpha * (1 - r)) fused with the
partial-sum reduction for the renormalization, one VMEM pass over the
length-n score vector instead of three HBM round-trips (mul, exp, sum).
The final scalar divide happens in the jitted wrapper (ops.py) after the
cross-device psum — the normalizer must be global across the data-sharded
score anyway, so the kernel emits per-tile partial sums.

TPU layout: the score is zero-padded to whole ``(rows, 128)`` lane tiles
(padded slots carry w = 0 and r = 1, so they add exactly 0 to every sum)
and streamed in blocks of up to ``BLOCK`` elements.  alpha rides in SMEM; each grid
step emits one lane-wide ``(1, 128)`` row of partial sums, reduced in the
wrapper — no scalar is ever stored to VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK = 64 * 1024               # elements per grid step (256 KiB of f32)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _lane_rows(n: int) -> tuple[int, int]:
    """(rows per block, padded row count) for a length-n vector laid out as
    ``(rows, 128)``: blocks of at most ``BLOCK`` elements, a multiple of 8
    rows each, and the row count padded to whole blocks."""
    rows = _round_up(max(n, 1), LANES) // LANES
    tr = min(BLOCK // LANES, _round_up(rows, 8))
    return tr, _round_up(rows, tr)


def _kernel(alpha_ref, w_ref, r_ref, out_ref, psum_ref):
    w_new = w_ref[...] * jnp.exp(alpha_ref[0, 0] * (1.0 - r_ref[...]))
    out_ref[...] = w_new
    psum_ref[...] = jnp.sum(w_new, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ignorance_update_unnormalized(w: jnp.ndarray, r: jnp.ndarray,
                                  alpha: jnp.ndarray, *,
                                  interpret: bool = False):
    """Returns (w * exp(alpha(1-r)) [n], partial sums [blocks, 1, 128]);
    the partial sums add up to the sum of the first output."""
    n = w.shape[0]
    tr, rows = _lane_rows(n)
    pad = rows * LANES - n
    w2 = jnp.pad(w.astype(jnp.float32), (0, pad)).reshape(rows, LANES)
    r2 = jnp.pad(r.astype(jnp.float32), (0, pad),
                 constant_values=1.0).reshape(rows, LANES)
    alpha_arr = jnp.reshape(alpha.astype(jnp.float32), (1, 1))
    nt = rows // tr
    w_new, psums = pl.pallas_call(
        _kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # alpha
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
            pl.BlockSpec((None, 1, LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((nt, 1, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="ignorance_update",
    )(alpha_arr, w2, r2)
    return w_new.reshape(-1)[:n], psums

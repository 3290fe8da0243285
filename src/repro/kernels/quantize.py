"""Fused quantize-dequant for wire codecs — Pallas TPU kernel.

The comm subsystem (`repro.comm.codecs`) ships ignorance scores as int8/int4
integers with one fp32 scale per tile.  What the protocol trajectory sees is
the *dequantized* vector — quantize and dequantize back-to-back — so the two
halves fuse into one VMEM pass: per-tile absmax, scale, stochastic round,
clip, and the dequantized product, without materializing the integer wire
array in HBM first.  The integer values and per-tile scales are emitted too
(they ARE the wire format, and the byte ledger prices them).

Stochastic rounding takes the uniform draws as an *input* (``u`` in [0, 1),
``floor(x/scale + u)``) instead of an in-kernel PRNG: the same draws feed the
host reference (`kernels.ref.quantize_dequant`), which keeps kernel-vs-host
bit-identical on every backend and keeps the codec a pure function of its
PRNG key — the property the eager/compiled engine pin rests on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BN = 1024
_EPS = 1e-12
_LANES = 128
_STEP = 64 * 1024          # elements per grid step


def tile_for(n: int, bn: int = DEFAULT_BN) -> int:
    """The tile size actually used for a length-n vector: ``bn`` when it
    divides evenly, else one global tile (ragged tails would complicate the
    grid for no win at protocol sizes).  The host reference uses the same
    rule, so kernel and reference always agree on the scale granularity."""
    return bn if (n >= bn and n % bn == 0) else n


def rows_for(n: int, k: int, bn: int = DEFAULT_BN) -> int:
    """Row tile for an [n, k] row-major block (ScoreBlockMsg payloads):
    keep the per-scale granularity at ~``bn`` elements by tiling
    ``bn // k`` rows when that divides n evenly, else one global tile —
    the same degenerate rule as :func:`tile_for`, shared with the host
    reference so kernel and reference agree on scale boundaries."""
    return tile_for(n, max(1, bn // k))


def _kernel(qmax_ref, x_ref, u_ref, xhat_ref, q_ref, scale_ref):
    # x_ref: (tiles, R, 128), one scale tile per leading index; padded slots
    # hold x = 0 and cannot raise the absmax
    qmax = qmax_ref[0, 0]
    x = x_ref[...]
    amax = jnp.max(jnp.max(jnp.abs(x), axis=2, keepdims=True), axis=1,
                   keepdims=True)
    scale = jnp.maximum(amax, _EPS) / qmax
    q = jnp.clip(jnp.floor(x / scale + u_ref[...]), -qmax, qmax)
    xhat_ref[...] = q * scale
    q_ref[...] = q.astype(jnp.int32).astype(jnp.int8)
    scale_ref[...] = jnp.broadcast_to(scale, scale_ref.shape)


def _quantize_tile_rows(x: jnp.ndarray, u: jnp.ndarray, qmax,
                        interpret: bool):
    """Quantize-dequant ``[nt, L]`` where each row is one scale tile.

    The wrapper zero-pads every tile to whole 128-lane rows, ``(nt, R,
    128)``, and streams ``tb`` tiles per grid step (about ``_STEP``
    elements); qmax rides in SMEM and the scales leave as lane-wide rows.
    Returns ``(xhat [nt, L] f32, q [nt, L] int8, scales [nt] f32)``."""
    nt, length = x.shape
    r = -(-length // _LANES)
    tb = max(1, min(nt, _STEP // (r * _LANES)))
    ntp = -(-nt // tb) * tb
    pad = ((0, ntp - nt), (0, r * _LANES - length))
    x3 = jnp.pad(x.astype(jnp.float32), pad).reshape(ntp, r, _LANES)
    u3 = jnp.pad(u.astype(jnp.float32), pad).reshape(ntp, r, _LANES)
    qmax_arr = jnp.reshape(jnp.asarray(qmax, jnp.float32), (1, 1))
    tile = pl.BlockSpec((tb, r, _LANES), lambda i: (i, 0, 0))
    xhat, q, scales = pl.pallas_call(
        _kernel,
        grid=(ntp // tb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile, tile],
        out_specs=[tile, tile,
                   pl.BlockSpec((tb, 1, _LANES), lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((ntp, r, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((ntp, r, _LANES), jnp.int8),
            jax.ShapeDtypeStruct((ntp, 1, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="quantize_dequant",
    )(qmax_arr, x3, u3)

    def unpad(a):
        return a.reshape(ntp, r * _LANES)[:nt, :length]

    return unpad(xhat), unpad(q), scales[:nt, 0, 0]


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def quantize_dequant_tiles(x: jnp.ndarray, u: jnp.ndarray,
                           qmax: jnp.ndarray, *, bn: int = DEFAULT_BN,
                           interpret: bool = False):
    """Per-tile symmetric quantization of a length-n vector.

    Returns ``(xhat [n] f32, q [n] int8, scales [n/bn] f32)`` where
    ``xhat = q * scale`` and ``q = clip(floor(x/scale + u), -qmax, qmax)``
    with ``scale = max(|x_tile|)/qmax``.  ``u`` in [0, 1) selects the
    rounding mode: uniform draws give unbiased stochastic rounding, a
    constant 0.5 gives round-half-up.  ``qmax`` may be a traced scalar
    (e.g. 127 for int8, 7 for int4) so codec sweeps can vmap over it.
    """
    n = x.shape[0]
    bn = tile_for(n, bn)
    xhat, q, scales = _quantize_tile_rows(
        x.reshape(n // bn, bn), u.reshape(n // bn, bn), qmax, interpret)
    return xhat.reshape(n), q.reshape(n), scales


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def quantize_dequant_block(x: jnp.ndarray, u: jnp.ndarray,
                           qmax: jnp.ndarray, *, bn: int = DEFAULT_BN,
                           interpret: bool = False):
    """Row-major tiled quantization of an [n, k] score block.

    The 2-D sibling of :func:`quantize_dequant_tiles` for prediction-time
    ScoreBlockMsg payloads: tiles of ``rows_for(n, k, bn)`` rows share one
    fp32 scale (per-tile absmax over the whole [rows, k] slab).  Each tile
    is one row-major run of ``rows * k`` elements, so the same kernel body
    serves both payloads.  Returns
    ``(xhat [n, k] f32, q [n, k] int8, scales [n/rows] f32)``.
    """
    n, k = x.shape
    nt = n // rows_for(n, k, bn)
    xhat, q, scales = _quantize_tile_rows(
        x.reshape(nt, -1), u.reshape(nt, -1), qmax, interpret)
    return xhat.reshape(n, k), q.reshape(n, k), scales


# ------------------------------------------------------------- int4 packing
# Wire bytes travel as (rows, 128) int8 and their int4 values as (rows, 256):
# row r holds bytes 128r.. and values 256r.., both contiguous in the flat
# arrays, so the wrapper only pads and reshapes.  Inside the kernel the
# even/odd split (pack) and the interleave (unpack) are products with 0/1
# selection matrices on the MXU: every output picks exactly one small
# integer, so the float products are exact, and nothing is reshaped or
# shuffled across lanes.  (Mosaic refuses an in-kernel int8 reshape, and
# the same interleave as an XLA stack+reshape takes the TPU compiler about
# 25 s at 10^5 bytes.)
def _selectors():
    """(E, O): [128, 256] f32 with E[j, 2j] = O[j, 2j+1] = 1."""
    byte = jax.lax.broadcasted_iota(jnp.int32, (_LANES, 2 * _LANES), 0)
    elem = jax.lax.broadcasted_iota(jnp.int32, (_LANES, 2 * _LANES), 1)
    return ((elem == 2 * byte).astype(jnp.float32),
            (elem == 2 * byte + 1).astype(jnp.float32))


def _pack_kernel(q_ref, p_ref):
    # element 2i in the low nibble of byte i, 2i+1 in the high nibble:
    # od * 16 + (ev & 15) lies in [-128, 127], the signed byte itself
    ev, od = _selectors()
    q = q_ref[...].astype(jnp.int32).astype(jnp.float32)
    dims = (((1,), (1,)), ((), ()))
    lo = jax.lax.dot_general(q, ev, dims, preferred_element_type=jnp.float32)
    hi = jax.lax.dot_general(q, od, dims, preferred_element_type=jnp.float32)
    byte = (hi.astype(jnp.int32) << 4) | (lo.astype(jnp.int32) & 0x0F)
    p_ref[...] = byte.astype(jnp.int8)


def _unpack_kernel(p_ref, q_ref):
    ev, od = _selectors()
    p = p_ref[...].astype(jnp.int32)                # sign-extended byte
    lo = ((p << 28) >> 28).astype(jnp.float32)      # arithmetic shifts
    hi = (p >> 4).astype(jnp.float32)               # sign-extend nibbles
    q = (jnp.dot(lo, ev, preferred_element_type=jnp.float32)
         + jnp.dot(hi, od, preferred_element_type=jnp.float32))
    q_ref[...] = q.astype(jnp.int32).astype(jnp.int8)


def _int4_call(kernel, x, mp: int, width_in: int, width_out: int,
               interpret: bool):
    """Run an int4 kernel over mp wire bytes at 128 per row: blocks a
    multiple of 32 rows (int8's native sublane tile), at most ``_STEP``
    bytes each.  Returns the flat output, padded."""
    rows = -(-max(mp, 1) // _LANES)
    tr = min(_STEP // _LANES, -(-rows // 32) * 32)
    rows = -(-rows // tr) * tr
    x = jnp.pad(x, (0, rows * width_in - x.shape[0])).reshape(rows, width_in)
    out = pl.pallas_call(
        kernel,
        grid=(rows // tr,),
        in_specs=[pl.BlockSpec((tr, width_in), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, width_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, width_out), jnp.int8),
        interpret=interpret,
        name=kernel.__name__.strip("_"),
    )(x)
    return out.reshape(-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_int4(q: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Pack int4 values carried in an int8 array into real 4-bit wire bytes.

    ``q`` is any-shape int8 holding values in [-8, 7] (the int4 codec emits
    [-7, 7]); the result is a flat int8 array of ``ceil(numel/2)`` bytes,
    two sign-extended nibbles per byte in row-major element order (odd
    element counts pad the trailing high nibble with 0).  The inverse is
    :func:`unpack_int4`; the pair is pinned bit-identical to the host
    reference (`kernels.ref.pack_int4`/``unpack_int4``) and exactly
    round-trips every carrier value.
    """
    flat = q.reshape(-1).astype(jnp.int8)
    mp = (flat.shape[0] + 1) // 2
    return _int4_call(_pack_kernel, flat, mp, 2 * _LANES, _LANES,
                      interpret)[:mp]


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def unpack_int4(packed: jnp.ndarray, n: int, *,
                interpret: bool = False) -> jnp.ndarray:
    """Unpack :func:`pack_int4` wire bytes back to ``n`` int8-carried int4
    values (flat; callers reshape)."""
    mp = packed.shape[0]
    if mp != (n + 1) // 2:
        raise ValueError(f"{mp} packed bytes cannot hold {n} int4 values")
    return _int4_call(_unpack_kernel, packed.astype(jnp.int8), mp, _LANES,
                      2 * _LANES, interpret)[:n]

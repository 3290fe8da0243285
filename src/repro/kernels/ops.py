"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` resolves by backend: compiled Mosaic kernels on a TPU,
interpret mode (the Pallas body executed as jnp) on the CPU, where the
tests run.  ``weighted_ce`` wires the forward/backward kernels into a
custom_vjp so the fused loss is a drop-in for training.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import ignorance as _ig
from repro.kernels import weighted_ce as _wce


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------ weighted CE
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def weighted_ce(logits, labels, weights, interpret: bool | None = None):
    """Per-token ignorance-weighted NLL [T] (fused Pallas kernel)."""
    interp = _default_interpret() if interpret is None else interpret
    loss, _ = _wce.weighted_ce_fwd(logits, labels, weights, interpret=interp)
    return loss


def _wce_fwd(logits, labels, weights, interpret):
    interp = _default_interpret() if interpret is None else interpret
    loss, lse = _wce.weighted_ce_fwd(logits, labels, weights, interpret=interp)
    return loss, (logits, labels, weights, lse)


def _wce_bwd(interpret, res, g):
    logits, labels, weights, lse = res
    interp = _default_interpret() if interpret is None else interpret
    dlogits = _wce.weighted_ce_bwd(logits, labels, weights, lse, g,
                                   interpret=interp)
    return dlogits, None, None


weighted_ce.defvjp(_wce_fwd, _wce_bwd)


# --------------------------------------------------------- flash attention
def flash_attention(q, k, v, *, causal=True, window=None,
                    interpret: bool | None = None):
    interp = _default_interpret() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               interpret=interp)


# --------------------------------------------------------- ignorance update
def ignorance_update(w, r, alpha, *, axis_name: str | None = None,
                     interpret: bool | None = None):
    """Fused eqs. (10)/(12) at any score length (the kernel pads a ragged
    tail itself).  Under shard_map pass axis_name to make the normalizer
    global across the data-sharded score vector."""
    interp = _default_interpret() if interpret is None else interpret
    w_new, psums = _ig.ignorance_update_unnormalized(w, r, alpha,
                                                     interpret=interp)
    total = jnp.sum(psums)
    if axis_name is not None:
        total = jax.lax.psum(total, axis_name)
    return w_new / jnp.maximum(total, 1e-12)


def quantize_dequant(x, u, qmax, *, bn: int = 1024,
                     interpret: bool | None = None):
    """Fused per-tile quantize-dequant for wire codecs (repro.comm.codecs):
    returns (dequantized [n], int8 wire values [n], per-tile scales)."""
    interp = _default_interpret() if interpret is None else interpret
    from repro.kernels import quantize as _q
    return _q.quantize_dequant_tiles(x, u, qmax, bn=bn, interpret=interp)


def quantize_dequant_block(x, u, qmax, *, bn: int = 1024,
                           interpret: bool | None = None):
    """Row-major tiled quantize-dequant for [n, k] score blocks (the
    prediction-time ScoreBlockMsg wire codec): returns (dequantized [n, k],
    int8 wire values [n, k], per-row-tile scales)."""
    interp = _default_interpret() if interpret is None else interpret
    from repro.kernels import quantize as _q
    return _q.quantize_dequant_block(x, u, qmax, bn=bn, interpret=interp)


def pack_int4(q, *, interpret: bool | None = None):
    """Pack int8-carried int4 values into real 4-bit wire bytes: two
    sign-extended nibbles per int8 byte (flat, ceil(numel/2) long) — the
    int4 codec's actual wire array (repro.comm.codecs)."""
    interp = _default_interpret() if interpret is None else interpret
    from repro.kernels import quantize as _q
    return _q.pack_int4(q, interpret=interp)


def unpack_int4(packed, n: int, *, interpret: bool | None = None):
    """Inverse of :func:`pack_int4`: n int8-carried int4 values (flat)."""
    interp = _default_interpret() if interpret is None else interpret
    from repro.kernels import quantize as _q
    return _q.unpack_int4(packed, n, interpret=interp)


def flash_decode(q, k, v, pos, *, k_scale=None, v_scale=None, window=None,
                 interpret: bool | None = None):
    """Single-token flash attention vs a long (optionally int8) KV cache."""
    interp = _default_interpret() if interpret is None else interpret
    return _fd.flash_decode(q, k, v, pos, k_scale=k_scale, v_scale=v_scale,
                            window=window, interpret=interp)

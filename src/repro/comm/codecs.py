"""Wire codecs: what an ignorance vector becomes on the way to another agent.

A :class:`Codec` is a pure ``encode``/``decode`` pair over length-n float
arrays.  ``encode`` produces the wire representation (what the byte ledger
prices — see :meth:`Codec.wire_bits`), ``decode`` reconstructs what the
receiving agent sees, and ``roundtrip`` fuses the two — that composition is
the *channel*: the protocol trajectory continues from the decoded array, so
a lossy codec genuinely degrades the interchange rather than merely
relabeling its byte count.

Codecs are hashable frozen dataclasses of pure fixed-shape functions, the
same discipline as :class:`~repro.learners.base.LearnerCore`: a codec is a
valid jit static argument, rides inside the compiled session scan
(`core/compiled.py`), and vmaps across session fleets.  Both engine
backends run the exact same traced channel (`jitted_channel` here), which
is what keeps eager and compiled trajectories bit-identical with a codec
active.

Implemented codecs:

  ===========  =======================  ============================
  name         wire format              bits for a length-n vector
  ===========  =======================  ============================
  ``fp32``     raw float32              32n
  ``fp16``     IEEE float16             16n
  ``int8``     int8 + fp32 tile scales  8n + 32·ceil(n/bn)
  ``int4``     packed int4 (two         8·ceil(n/2) + 32·ceil(n/bn)
               nibbles per wire byte)
               + fp32 tile scales
  ``topk``     top-k values + indices   k·(32 + ceil(log2 n))
  ===========  =======================  ============================

The int codecs run the fused quantize-dequant Pallas kernel
(`kernels/quantize.py`); ``topk`` keeps a per-link error-feedback residual
(carried in ``SessionState.codec_state``) so the mass it drops is re-offered
on the next hop instead of lost.
"""
from __future__ import annotations

import abc
import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

# fold_in tags deriving the per-hop channel keys from the per-fit key: the
# channel consumes no PRNG state of its own, so attaching (or removing) a
# codec never shifts the fit keys — fp32 sessions stay bit-identical to
# pre-comm trajectories.
COMM_FOLD = 0x434F4D        # "COM"
PRIVACY_FOLD = 0
CODEC_FOLD = 1
# serve-path fold tag: prediction-time ScoreBlockMsg channel keys derive as
# fold_in(fit_key, SERVE_FOLD) then fold_in(., agent_index) — again no PRNG
# state consumed, so serving never shifts the fit stream, and both engine
# backends derive identical serve draws.
SERVE_FOLD = 0x535256       # "SRV"

SCALE_BITS = 32             # one fp32 scale per quantization tile


def numel(shape) -> int:
    """Element count of a wire payload shape: codecs accept either an int n
    (the PR-3 length-n ignorance vector) or a shape tuple (the [n, K]
    prediction-time score block)."""
    if isinstance(shape, (tuple, list)):
        out = 1
        for s in shape:
            out *= int(s)
        return out
    return int(shape)


#: The scalar ModelWeightMsg that accompanies every shipped interchange hop.
MODEL_WEIGHT_BITS = 32


def rung_bits(ladder, shape, extra: int = 0) -> tuple[int, ...]:
    """What one payload of ``shape`` costs on the wire at each rung of
    ``ladder``: the codec's encoded ``wire_bits``, or 32 bits per element on
    a None (raw fp32) rung, plus ``extra`` (the 32-bit ModelWeightMsg that
    rides an interchange hop).  Equal to what ``Message.bits`` books for
    the payload — the one price table behind the budget walk, the compiled
    replays and the traced programs' spent-bit and live counters."""
    raw = 32 * numel(shape)
    return tuple((raw if c is None else int(c.wire_bits(shape))) + extra
                 for c in ladder)


@dataclass(frozen=True)
class Codec(abc.ABC):
    """A pure encode/decode pair over float arrays: length-n ignorance
    vectors (training interchange) and [n, K] score blocks (prediction
    serve traffic) alike — every method is shape-generic."""

    #: Codecs with per-link state (error-feedback residuals) return it from
    #: ``init_state``; stateless codecs leave this False and pass None.
    stateful = False

    @abc.abstractmethod
    def wire_bits(self, shape) -> int:
        """Encoded size in bits of a payload (static).  ``shape`` is an int
        n (length-n vector) or a shape tuple like (n, K)."""

    def init_state(self, shape):
        """Fresh per-link codec state (None for stateless codecs)."""
        return None

    @abc.abstractmethod
    def encode(self, x: jnp.ndarray, key=None, state=None):
        """x -> (wire pytree, new_state)."""

    @abc.abstractmethod
    def decode(self, wire) -> jnp.ndarray:
        """wire -> reconstructed x_hat (what the receiver sees)."""

    def roundtrip(self, x: jnp.ndarray, key=None, state=None):
        """decode(encode(x)) fused; subclasses may override with a fused
        kernel, but must stay bit-identical to the encode/decode pair."""
        wire, state = self.encode(x, key, state)
        return self.decode(wire), state


@dataclass(frozen=True)
class Fp32Codec(Codec):
    """Passthrough: the PR-1 wire format, 32 bits per element."""

    def wire_bits(self, shape) -> int:
        return 32 * numel(shape)

    def encode(self, x, key=None, state=None):
        return x.astype(jnp.float32), state

    def decode(self, wire):
        return wire


@dataclass(frozen=True)
class Fp16Codec(Codec):
    """IEEE half precision: 2x cheaper, ~3 decimal digits kept."""

    def wire_bits(self, shape) -> int:
        return 16 * numel(shape)

    def encode(self, x, key=None, state=None):
        return x.astype(jnp.float16), state

    def decode(self, wire):
        return wire.astype(jnp.float32)


@dataclass(frozen=True)
class QuantCodec(Codec):
    """Symmetric int quantization with per-tile fp32 scales.

    ``bits`` integer bits per element (8 or 4; int4 travels in an int8
    carrier but is priced at 4 bits).  ``stochastic`` selects unbiased
    stochastic rounding (needs the hop key) vs deterministic round-half-up.
    ``roundtrip`` runs the fused Pallas kernel (kernels/quantize.py);
    ``encode``/``decode`` expose the wire halves and are pinned bit-identical
    to the kernel by tests/test_comm.py.
    """
    bits: int = 8
    stochastic: bool = True
    bn: int = 1024

    @property
    def qmax(self) -> float:
        return float(2 ** (self.bits - 1) - 1)

    def _tiles(self, shape) -> int:
        from repro.kernels.quantize import rows_for, tile_for
        if isinstance(shape, (tuple, list)) and len(shape) == 2:
            n, k = int(shape[0]), int(shape[1])
            return n // rows_for(n, k, self.bn)
        n = numel(shape)
        return n // tile_for(n, self.bn)

    def wire_bits(self, shape) -> int:
        m = numel(shape)
        if self.bits == 4:
            # real 4-bit carriers: two nibbles per int8 wire byte (odd
            # element counts pad the trailing high nibble), so the priced
            # payload is whole bytes, not a fictional 4·m
            payload = 8 * ((m + 1) // 2)
        else:
            payload = self.bits * m
        return payload + SCALE_BITS * self._tiles(shape)

    def _u(self, x, key):
        if self.stochastic:
            if key is None:
                raise ValueError("stochastic QuantCodec needs a hop key")
            return jax.random.uniform(key, x.shape, jnp.float32)
        return jnp.full(x.shape, 0.5, jnp.float32)

    def roundtrip(self, x, key=None, state=None, qmax=None):
        from repro.kernels import ops
        qd = ops.quantize_dequant_block if x.ndim == 2 else ops.quantize_dequant
        xhat, _, _ = qd(x, self._u(x, key),
                        self.qmax if qmax is None else qmax, bn=self.bn)
        return xhat, state

    def encode(self, x, key=None, state=None):
        from repro.kernels import ops, ref
        qd = ref.quantize_dequant_block if x.ndim == 2 else ref.quantize_dequant
        _, q, scales = qd(x, self._u(x, key), self.qmax, bn=self.bn)
        if self.bits == 4:
            # the wire array is a real 4-bit carrier: two nibbles per int8
            # byte (the Pallas pack pass); shape rides the wire tuple so
            # decode can unpack odd element counts exactly
            return (ops.pack_int4(q), scales, tuple(q.shape)), state
        return (q, scales), state

    def decode(self, wire):
        if self.bits == 4:
            from repro.kernels import ops
            packed, scales, shape = wire
            q = ops.unpack_int4(packed, numel(shape)).reshape(shape)
        else:
            q, scales = wire
        if q.ndim == 2:
            n, k = q.shape
            br = n // scales.shape[0]
            return (q.astype(jnp.float32).reshape(-1, br, k)
                    * scales[:, None, None]).reshape(n, k)
        n = q.shape[0]
        bn = n // scales.shape[0]
        return (q.astype(jnp.float32).reshape(-1, bn)
                * scales[:, None]).reshape(n)


@dataclass(frozen=True)
class TopKCodec(Codec):
    """Top-k sparsification with per-link error feedback.

    Ships the k = ceil(fraction·n) largest-magnitude entries as
    (value, index) pairs.  The mass not shipped accumulates in a per-link
    residual (EF-SGD style): each encode sees x + residual, and the new
    residual is what decode failed to reconstruct — dropped ignorance is
    deferred to the next hop on that link, not lost.  The residual rides in
    ``SessionState.codec_state`` (eager) / the session scan carry (compiled)
    and is checkpointed with the session.
    """
    fraction: float = 0.25

    stateful = True

    def k_for(self, n: int) -> int:
        """Entries shipped for an n-element payload (n = numel of the
        shape: rows for a vector, rows x classes for a score block)."""
        return max(1, int(math.ceil(self.fraction * numel(n))))

    def wire_bits(self, shape) -> int:
        m = numel(shape)
        idx_bits = max(1, math.ceil(math.log2(max(m, 2))))
        return self.k_for(m) * (32 + idx_bits)

    def init_state(self, shape):
        if isinstance(shape, (tuple, list)):
            return jnp.zeros(tuple(int(s) for s in shape), jnp.float32)
        return jnp.zeros((int(shape),), jnp.float32)

    def encode(self, x, key=None, state=None):
        shape = tuple(x.shape)
        if state is None:
            state = self.init_state(shape)
        y = (x.astype(jnp.float32) + state).reshape(-1)
        m = y.shape[0]
        _, idx = jax.lax.top_k(jnp.abs(y), self.k_for(m))
        vals = y[idx]
        dense = jnp.zeros((m,), jnp.float32).at[idx].set(vals)
        return (vals, idx, shape), (y - dense).reshape(shape)

    def decode(self, wire):
        vals, idx, shape = wire
        m = numel(shape)
        dense = jnp.zeros((m,), jnp.float32).at[idx].set(vals)
        return dense.reshape(shape)


CODECS = {
    "fp32": Fp32Codec,
    "fp16": Fp16Codec,
    "int8": lambda **kw: QuantCodec(bits=8, **kw),
    "int4": lambda **kw: QuantCodec(bits=4, **kw),
    "topk": TopKCodec,
}


def make_codec(name: str, **kw) -> Codec:
    """Codec registry lookup for CLI / benchmark sweep names."""
    if name not in CODECS:
        raise ValueError(f"unknown codec {name!r}; expected {sorted(CODECS)}")
    return CODECS[name](**kw)


def serve_key(state_key, request=None):
    """The serve-channel key for one prediction call: the session's (evolved)
    PRNG key folded with the SERVE tag, then — for request-keyed serving
    (the serve engine's stream of independent queries against one resident
    session) — with the integer request tag.  Pure fold_ins: no PRNG state
    is consumed, so serving never shifts the fit stream, repeated serves of
    the same request are deterministic, and distinct requests draw
    independent channel noise.  Both engine backends and the batched serve
    engine derive their keys here, which is what makes a batched slot
    bit-identical to a standalone ``predict_distributed(request=...)``."""
    key = jax.random.fold_in(state_key, SERVE_FOLD)
    if request is not None:
        if not isinstance(request, jax.Array):
            request = int(request)      # trace-safe: tracers pass through
        key = jax.random.fold_in(key, request)
    return key


# ===================================================================== channel
def channel_apply(codec, privacy, w, hop_key, state, qmax=None):
    """One hop through the wire: DP noise on the outgoing vector, then the
    codec roundtrip.  ``hop_key`` is the per-fit subkey; the privacy and
    codec keys are folded from it with fixed tags, so the channel consumes
    no PRNG state and both engine backends derive identical draws.  Pure and
    fixed-shape: jits, scans, and vmaps.  ``qmax`` optionally overrides a
    QuantCodec's static clipping level with a traced scalar (codec sweeps;
    see ``core.compiled.quant_sweep_run``)."""
    if privacy is not None:
        w = privacy.apply(w, jax.random.fold_in(
            jax.random.fold_in(hop_key, COMM_FOLD), PRIVACY_FOLD))
    if codec is not None:
        ck = jax.random.fold_in(
            jax.random.fold_in(hop_key, COMM_FOLD), CODEC_FOLD)
        if qmax is not None:
            w, state = codec.roundtrip(w, ck, state, qmax=qmax)
        else:
            w, state = codec.roundtrip(w, ck, state)
    return w, state


def quant_bits_per_element(qmax) -> int:
    """Wire bits per element for a symmetric integer range [-qmax, qmax]
    (the inverse of QuantCodec.qmax): 127 -> 8, 7 -> 4."""
    return max(1, math.ceil(math.log2(2 * int(qmax) + 2)))


@functools.lru_cache(maxsize=64)
def jitted_channel(codec, privacy):
    """Cached jit of ``channel_apply`` for a (codec, privacy) pair — the
    eager transports route through this so the eager engine runs the exact
    XLA program the compiled session scan embeds (the same trick as
    ``learners.base.jitted_fresh_fit``, and for the same reason: op-by-op
    dispatch fuses differently at the last ulp)."""
    return jax.jit(functools.partial(channel_apply, codec, privacy))

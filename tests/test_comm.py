"""Wire-format subsystem units: codec encode/decode/roundtrip properties
(hypothesis: quantize kernel vs host reference across dtypes and tilings),
top-k error feedback, the Gaussian mechanism + accountant, budget specs,
and the hardened TransportLog."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (BudgetSpec, GaussianMechanism, PrivacyAccountant,
                        make_codec)
from repro.comm.budget import MODEL_WEIGHT_BITS
from repro.comm.codecs import (Fp16Codec, Fp32Codec, QuantCodec, TopKCodec,
                               quant_bits_per_element, rung_bits)
from repro.core.engine import GradientMsg, MeteredTransport
from repro.core.transport import TransportLog
from repro.kernels import ops, ref


# ===================================================== quantize kernel vs ref
def _x(n, dtype, seed):
    key = jax.random.key(seed)
    return (jax.random.dirichlet(key, jnp.ones(n)) * 0.5).astype(dtype)


def test_kernel_matches_reference_grid():
    """The fused Pallas quantize-dequant equals the host reference bit for
    bit at every tiling regime (sub-tile, exact tile, multi-tile), input
    dtype, and quantization width — no hypothesis dependency needed for the
    core pin."""
    for n in (4, 64, 257, 1024, 2048):
        for dtype in (jnp.float32, jnp.bfloat16):
            for qmax in (127.0, 7.0):
                x = _x(n, dtype, n)
                u = jax.random.uniform(jax.random.key(n + 1), (n,))
                out_k = ops.quantize_dequant(x, u, qmax)
                out_r = ref.quantize_dequant(x, u, qmax)
                for a, b in zip(out_k, out_r):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))


def _block(n, k, dtype, seed):
    key = jax.random.key(seed)
    return (jax.random.normal(key, (n, k)) * 0.5).astype(dtype)


def test_block_kernel_matches_reference_grid():
    """The row-major 2-D quantize-dequant (ScoreBlockMsg payloads) equals
    the host reference bit for bit at every tiling regime — sub-tile (one
    global scale), exact row tiles, odd row counts — input dtype, and
    quantization width."""
    for (n, k) in ((4, 3), (60, 8), (128, 8), (257, 5), (1024, 8)):
        for dtype in (jnp.float32, jnp.bfloat16):
            for qmax in (127.0, 7.0):
                x = _block(n, k, dtype, n + k)
                u = jax.random.uniform(jax.random.key(n + 1), (n, k))
                out_k = ops.quantize_dequant_block(x, u, qmax)
                out_r = ref.quantize_dequant_block(x, u, qmax)
                for a, b in zip(out_k, out_r):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))


try:
    import hypothesis  # noqa: F401
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    SHAPES = st.sampled_from([4, 64, 257, 1024, 2048])
    DTYPES = st.sampled_from([jnp.float32, jnp.bfloat16])
    QMAXES = st.sampled_from([127.0, 31.0, 7.0])

    @given(n=SHAPES, dtype=DTYPES, qmax=QMAXES, seed=st.integers(0, 99))
    @settings(max_examples=25, deadline=None)
    def test_kernel_matches_reference_prop(n, dtype, qmax, seed):
        """Property form of the kernel-vs-reference pin, plus the
        quantization-error bound: |xhat - x| <= one step (stochastic
        rounding moves at most one level past floor)."""
        x = _x(n, dtype, seed)
        u = jax.random.uniform(jax.random.key(seed + 1), (n,))
        xh_k, q_k, s_k = ops.quantize_dequant(x, u, qmax)
        xh_r, q_r, s_r = ref.quantize_dequant(x, u, qmax)
        np.testing.assert_array_equal(np.asarray(xh_k), np.asarray(xh_r))
        np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
        np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
        step = np.repeat(np.asarray(s_k), n // s_k.shape[0])
        err = np.abs(np.asarray(xh_k) - np.asarray(x, np.float32))
        assert (err <= step * (1 + 1e-5)).all()

    @given(n=SHAPES, dtype=DTYPES, bits=st.sampled_from([8, 4]),
           seed=st.integers(0, 99))
    @settings(max_examples=25, deadline=None)
    def test_quant_roundtrip_equals_encode_decode(n, dtype, bits, seed):
        """QuantCodec.roundtrip (fused kernel) == decode(encode(x)) (host
        wire halves) bit for bit — the codec contract."""
        codec = QuantCodec(bits=bits)
        x = _x(n, dtype, seed).astype(jnp.float32)
        key = jax.random.key(seed)
        fused, _ = codec.roundtrip(x, key)
        wire, _ = codec.encode(x, key)
        np.testing.assert_array_equal(np.asarray(fused),
                                      np.asarray(codec.decode(wire)))

    @given(n=SHAPES, seed=st.integers(0, 99),
           frac=st.sampled_from([0.1, 0.25, 0.5]))
    @settings(max_examples=25, deadline=None)
    def test_topk_error_feedback_invariant(n, seed, frac):
        """decode(wire) + new_residual == x + old_residual exactly: the
        channel defers mass, never loses it."""
        codec = TopKCodec(fraction=frac)
        x = _x(n, jnp.float32, seed)
        resid = jax.random.normal(jax.random.key(seed + 7), (n,)) * 0.01
        wire, new_resid = codec.encode(x, state=resid)
        np.testing.assert_allclose(
            np.asarray(codec.decode(wire) + new_resid),
            np.asarray(x + resid), rtol=1e-6, atol=1e-7)

    # -------------------------------------------- 2-D score-block properties
    BLOCK_NS = st.sampled_from([4, 60, 128, 257, 1024])
    BLOCK_KS = st.sampled_from([2, 3, 8])

    @given(n=BLOCK_NS, k=BLOCK_KS, dtype=DTYPES, qmax=QMAXES,
           seed=st.integers(0, 99))
    @settings(max_examples=25, deadline=None)
    def test_block_kernel_matches_reference_prop(n, k, dtype, qmax, seed):
        """Property form of the 2-D kernel-vs-reference pin, plus the
        quantization-error bound: |xhat - x| <= one step of the row-tile
        the element lives in."""
        x = _block(n, k, dtype, seed)
        u = jax.random.uniform(jax.random.key(seed + 1), (n, k))
        out_k = ops.quantize_dequant_block(x, u, qmax)
        out_r = ref.quantize_dequant_block(x, u, qmax)
        for a, b in zip(out_k, out_r):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        xh, _, scales = out_k
        step = np.repeat(np.asarray(scales), n // scales.shape[0])[:, None]
        err = np.abs(np.asarray(xh) - np.asarray(x, np.float32))
        assert (err <= step * (1 + 1e-5)).all()

    @given(n=BLOCK_NS, k=BLOCK_KS, seed=st.integers(0, 99),
           name=st.sampled_from(["fp32", "fp16", "int8", "int4", "topk"]))
    @settings(max_examples=25, deadline=None)
    def test_codec_block_shape_dtype_preserved(n, k, seed, name):
        """Every codec, fed an [n, K] block: encode/decode reconstructs to
        the original shape in float32, and roundtrip == decode(encode(x))
        (the serve-path codec contract)."""
        codec = make_codec(name)
        x = _block(n, k, jnp.float32, seed)
        key = jax.random.key(seed)
        wire, state = codec.encode(x, key)
        dec = codec.decode(wire)
        assert dec.shape == (n, k) and dec.dtype == jnp.float32
        fused, _ = codec.roundtrip(x, key)
        assert fused.shape == (n, k) and fused.dtype == jnp.float32
        if not codec.stateful:          # fresh top-k state differs per call
            np.testing.assert_array_equal(np.asarray(fused),
                                          np.asarray(dec))
        # int codecs: quantization error bounded by the tile step size; the
        # int4 wire is a packed 4-bit carrier (plus the original shape)
        if isinstance(codec, QuantCodec):
            if codec.bits == 4:
                packed, scales, shape = wire
                assert shape == (n, k)
                assert packed.shape[0] == (n * k + 1) // 2
                assert packed.dtype == jnp.int8
            else:
                q, scales = wire
            step = np.repeat(np.asarray(scales),
                             n // scales.shape[0])[:, None]
            err = np.abs(np.asarray(fused) - np.asarray(x, np.float32))
            assert (err <= step * (1 + 1e-5)).all()

    @given(n=st.sampled_from([16, 60, 257]), k=BLOCK_KS,
           seed=st.integers(0, 99), frac=st.sampled_from([0.1, 0.25]))
    @settings(max_examples=25, deadline=None)
    def test_topk_block_residual_carry_over_rounds(n, k, seed, frac):
        """Error feedback telescopes across serve rounds on [n, K] blocks:
        sum_t decode_t + final_residual == sum_t x_t + initial_residual —
        deferred mass is carried, round after round, never dropped."""
        codec = TopKCodec(fraction=frac)
        keys = jax.random.split(jax.random.key(seed), 3)
        xs = [_block(n, k, jnp.float32, seed + 11 * t) for t in range(3)]
        resid = codec.init_state((n, k))
        shipped = jnp.zeros((n, k), jnp.float32)
        for t, x in enumerate(xs):
            wire, resid = codec.encode(x, keys[t], state=resid)
            assert resid.shape == (n, k)
            shipped = shipped + codec.decode(wire)
        np.testing.assert_allclose(
            np.asarray(shipped + resid),
            np.asarray(sum(xs)), rtol=1e-5, atol=1e-6)


def test_pack_int4_kernel_matches_reference_grid():
    """The int4 pack/unpack Pallas pass equals the host reference bit for
    bit and round-trips exactly — at even sizes, odd sizes (padded high
    nibble), multi-tile sizes, and the full nibble range [-8, 7]."""
    rng = np.random.default_rng(0)
    for n in (2, 7, 64, 257, 1024, 2048, 4096):
        q = jnp.asarray(rng.integers(-8, 8, n), jnp.int8)
        p_k = ops.pack_int4(q)
        p_r = ref.pack_int4(q)
        np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
        assert p_k.shape == ((n + 1) // 2,) and p_k.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(ops.unpack_int4(p_k, n)),
                                      np.asarray(q))
        np.testing.assert_array_equal(np.asarray(ref.unpack_int4(p_r, n)),
                                      np.asarray(q))
    # every nibble value survives the trip
    q = jnp.asarray(np.arange(-8, 8), jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(ops.unpack_int4(ops.pack_int4(q), 16)), np.asarray(q))
    # 2-D payloads flatten row-major
    q2 = jnp.asarray(rng.integers(-8, 8, (60, 3)), jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(ops.unpack_int4(ops.pack_int4(q2), 180).reshape(60, 3)),
        np.asarray(q2))
    with pytest.raises(ValueError, match="cannot hold"):
        ops.unpack_int4(jnp.zeros((3,), jnp.int8), 100)


def test_int4_codec_wire_is_packed():
    """The int4 codec's wire array is a real 4-bit carrier: ceil(m/2) int8
    bytes, decode unpacks losslessly, and decode(encode(x)) still equals
    the fused kernel roundtrip."""
    codec = QuantCodec(bits=4)
    for n in (64, 257, 600):
        x = _x(n, jnp.float32, n)
        key = jax.random.key(n)
        (packed, scales, shape), _ = codec.encode(x, key)
        assert packed.shape == ((n + 1) // 2,) and packed.dtype == jnp.int8
        assert shape == (n,)
        fused, _ = codec.roundtrip(x, key)
        np.testing.assert_array_equal(
            np.asarray(fused),
            np.asarray(codec.decode((packed, scales, shape))))
    # int8 stays an unpacked (q, scales) wire
    wire8, _ = QuantCodec(bits=8).encode(_x(64, jnp.float32, 1),
                                         jax.random.key(0))
    q8, _ = wire8
    assert q8.shape == (64,)


def test_stochastic_rounding_unbiased():
    """E[dequant] over rounding draws approaches x (the reason int8 wires
    survive many hops where deterministic rounding collapses)."""
    n, reps = 256, 400
    x = _x(n, jnp.float32, 0)
    codec = QuantCodec(bits=8)
    keys = jax.random.split(jax.random.key(1), reps)
    outs = jax.vmap(lambda k: codec.roundtrip(x, k)[0])(keys)
    mean = np.asarray(jnp.mean(outs, axis=0))
    scale = float(jnp.max(jnp.abs(x))) / codec.qmax
    np.testing.assert_allclose(mean, np.asarray(x), atol=0.15 * scale)


def test_wire_bits_formulas():
    n = 600
    assert Fp32Codec().wire_bits(n) == 32 * n
    assert Fp16Codec().wire_bits(n) == 16 * n
    assert QuantCodec(bits=8).wire_bits(n) == 8 * n + 32      # one tile
    assert QuantCodec(bits=4).wire_bits(n) == 4 * n + 32
    assert QuantCodec(bits=8).wire_bits(2048) == 8 * 2048 + 2 * 32
    # int4 prices whole packed wire bytes: odd element counts round up to
    # the padded nibble, even counts reduce to the nominal 4 bits/element
    assert QuantCodec(bits=4).wire_bits(257) == 8 * 129 + 32
    assert QuantCodec(bits=8).wire_bits(257) == 8 * 257 + 32
    k = TopKCodec(fraction=0.25).k_for(n)
    assert TopKCodec(fraction=0.25).wire_bits(n) == k * (32 + 10)  # log2(600)
    assert quant_bits_per_element(127) == 8
    assert quant_bits_per_element(7) == 4


def test_wire_bits_formulas_2d():
    """Score-block wire sizes: elementwise codecs scale by n*K; the quant
    codecs add one fp32 scale per row tile (rows_for: ~1024 elements per
    tile when the row count divides evenly, else one global tile)."""
    from repro.kernels.quantize import rows_for
    shape = (600, 8)                     # 4800 elements
    assert Fp32Codec().wire_bits(shape) == 32 * 4800
    assert Fp16Codec().wire_bits(shape) == 16 * 4800
    # 600 rows of k=8: 1024 // 8 = 128-row tiles don't divide 600 -> one
    # global tile, a single fp32 scale
    assert rows_for(600, 8) == 600
    assert QuantCodec(bits=8).wire_bits(shape) == 8 * 4800 + 32
    assert QuantCodec(bits=4).wire_bits(shape) == 4 * 4800 + 32
    # 1024 rows of k=8 tile into 8 row groups of 128 -> 8 scales
    assert rows_for(1024, 8) == 128
    assert QuantCodec(bits=8).wire_bits((1024, 8)) == 8 * 8192 + 8 * 32
    # top-k flattens: k_for and index width follow the element count
    t = TopKCodec(fraction=0.25)
    assert t.k_for(4800) == 1200
    assert t.wire_bits(shape) == 1200 * (32 + 13)      # ceil(log2(4800))


def test_codec_registry():
    assert isinstance(make_codec("int8"), QuantCodec)
    assert make_codec("int4").bits == 4
    assert isinstance(make_codec("topk"), TopKCodec)
    with pytest.raises(ValueError, match="unknown codec"):
        make_codec("zstd")


def test_fp16_codec_roundtrip_is_half_precision():
    x = _x(257, jnp.float32, 3)
    out, _ = Fp16Codec().roundtrip(x)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(x, np.float16).astype(np.float32))


# ==================================================================== privacy
def test_gaussian_mechanism_calibration_and_clip():
    mech = GaussianMechanism(epsilon=2.0, delta=1e-5, clip=0.5)
    assert mech.sigma == pytest.approx(
        0.5 * np.sqrt(2 * np.log(1.25 / 1e-5)) / 2.0)
    x = jnp.full((64,), 10.0)          # norm 80 >> clip
    out = mech.apply(x, jax.random.key(0))
    assert float(jnp.min(out)) >= 0.0  # clamped (post-processing)
    # determinism per key, fresh noise per key
    out2 = mech.apply(x, jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    out3 = mech.apply(x, jax.random.key(1))
    assert np.abs(np.asarray(out) - np.asarray(out3)).max() > 0


def test_gaussian_mechanism_validation():
    for bad in (dict(epsilon=0.0), dict(delta=0.0), dict(delta=1.5),
                dict(clip=-1.0)):
        with pytest.raises(ValueError):
            GaussianMechanism(**bad)


def test_privacy_accountant_composition():
    mech = GaussianMechanism(epsilon=0.5, delta=1e-6)
    acct = PrivacyAccountant()
    for _ in range(3):
        acct.record("agent0")
    acct.record("agent1")
    assert acct.spent("agent0", mech) == pytest.approx((1.5, 3e-6))
    assert acct.spent("agent2", mech) == (0.0, 0.0)
    rep = acct.report(mech)
    assert list(rep) == ["agent0", "agent1"]          # deterministic order
    assert rep["agent0"]["releases"] == 3


# ===================================================================== budget
def test_budget_spec_validation():
    with pytest.raises(ValueError, match="at least one"):
        BudgetSpec(ladder=())
    with pytest.raises(ValueError, match="stateless"):
        BudgetSpec(ladder=(TopKCodec(),))
    with pytest.raises(ValueError, match="positive"):
        BudgetSpec(session_bits=0)


def test_budget_choose_rule():
    spec = BudgetSpec(session_bits=10 ** 9)
    n = 100
    costs = spec.hop_costs(n)
    assert costs[0] == 32 * n + MODEL_WEIGHT_BITS
    assert list(costs) == sorted(costs, reverse=True)   # ladder degrades
    assert spec.choose_costs(costs, float("inf"), float("inf")) == 0
    # only the cheapest rung affordable
    assert spec.choose_costs(costs, costs[-1], float("inf")) == \
        len(costs) - 1
    # nothing affordable -> skip
    assert spec.choose_costs(costs, costs[-1] - 1, float("inf")) is None
    # the link cap binds too
    assert spec.choose_costs(costs, float("inf"), costs[-1]) == \
        len(costs) - 1


# =================================================== one price for every ledger
PRICE_RUNGS = {"raw": None, "fp16": Fp16Codec(), "int8": QuantCodec(bits=8),
               "int4": QuantCodec(bits=4)}
N_PRICE, K_PRICE, D_PRICE = 70, 3, 33     # odd sizes: int4 pads a nibble
_A, _B = SimpleNamespace(name="a"), SimpleNamespace(name="b")
PRICE_HOPS = {
    # an [n] ignorance hop with its 32-bit alpha
    "ignorance": ((N_PRICE,), MODEL_WEIGHT_BITS,
                  lambda t, x, key: t.interchange(_A, _B, x, x, 0.5,
                                                  lambda w, r, a: w,
                                                  key=key)),
    # a bare [n] async-barrier release
    "barrier": ((N_PRICE,), 0,
                lambda t, x, key: t.barrier_release(_A, x, key=key)),
    # an [n, K] prediction-time score block
    "score_block": ((N_PRICE, K_PRICE), 0,
                    lambda t, x, key: t.serve_block(_B, _A, x, key=key)),
    # a [d] FedAvg gradient uplink
    "gradient": ((D_PRICE,), 0,
                 lambda t, x, key: t.ship(_B, _A, x, GradientMsg, key=key)),
}


@pytest.mark.parametrize("rung", sorted(PRICE_RUNGS))
@pytest.mark.parametrize("hop", sorted(PRICE_HOPS))
def test_rung_bits_is_what_book_hop_books(hop, rung):
    """``rung_bits`` — the one price table of budget walks, compiled
    replays and traced counters — equals the bits a metered transport books
    for the messages ``book_hop`` sends on every eager hop kind, at every
    rung, raw included."""
    codec = PRICE_RUNGS[rung]
    shape, extra, run = PRICE_HOPS[hop]
    t = MeteredTransport(codec=codec)
    x = jax.random.uniform(jax.random.key(3), shape)
    run(t, x, jax.random.key(4))
    assert [e["kind"] for e in t.log.entries][1:] == \
        (["model_weight"] if extra else [])
    assert t.log.total_bits == rung_bits((codec,), shape, extra)[0]
    assert t.log.entries[0]["bits"] == rung_bits((codec,), shape)[0]


# =============================================================== TransportLog
def test_transport_log_rejects_bad_counts():
    log = TransportLog()
    with pytest.raises(ValueError, match=">= 0"):
        log.send("a", "b", "ignorance", -1)
    with pytest.raises(TypeError, match="integer"):
        log.send("a", "b", "ignorance", 2.5)
    with pytest.raises(TypeError, match="integer"):
        log.send("a", "b", "ignorance", True)
    with pytest.raises(ValueError, match=">= 0"):
        log.send_bits("a", "b", "ignorance", -8)
    with pytest.raises(TypeError, match="integer"):
        log.send_bits("a", "b", "ignorance", 8.0)
    assert log.entries == []                  # nothing booked on rejection
    log.send("a", "b", "ignorance", np.int64(4), 32)   # np ints are fine
    assert log.total_bits == 128


def test_transport_log_bits_by_kind_deterministic_order():
    log = TransportLog()
    log.send("a", "b", "score_block", 2)
    log.send("a", "b", "ignorance", 4)
    log.send_bits("a", "b", "model_weight", 32)
    log.send("a", "b", "ignorance", 1)
    kinds = log.bits_by_kind()
    assert list(kinds) == sorted(kinds)       # name-ordered, JSON-diff-stable
    assert kinds["ignorance"] == 5 * 32
    assert sum(kinds.values()) == log.total_bits

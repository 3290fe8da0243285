"""Kernel benchmarks: Pallas (interpret on CPU) vs pure-jnp reference —
allclose + relative wall time.  On TPU the same harness times the compiled
kernels; on this box wall-times of interpret mode are NOT performance
numbers, only correctness gates (the roofline table carries the perf
story)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed
from repro.kernels import ops, ref
from repro.launch.compile_cache import enable_compile_cache


def run() -> list[dict]:
    key = jax.random.key(0)
    rows = []

    t, v = 512, 2048
    logits = jax.random.normal(key, (t, v))
    labels = jax.random.randint(key, (t,), 0, v)
    w = jax.random.uniform(key, (t,))
    out_k, us_k = timed(lambda: ops.weighted_ce(logits, labels, w))
    (out_r, _), us_r = timed(lambda: ref.weighted_ce(logits, labels, w))
    rows.append({"kernel": "weighted_ce", "shape": f"{t}x{v}",
                 "max_err": float(jnp.max(jnp.abs(out_k - out_r))),
                 "us_pallas_interp": us_k, "us_ref": us_r})

    b, h, kv, s, d = 1, 4, 2, 512, 64
    q = jax.random.normal(key, (b, h, s, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, kv, s, d))
    vv = jax.random.normal(jax.random.fold_in(key, 2), (b, kv, s, d))
    for window in (None, 128):
        o_k, us_k = timed(lambda: ops.flash_attention(q, k, vv, window=window))
        o_r, us_r = timed(lambda: ref.flash_attention(q, k, vv, window=window))
        rows.append({"kernel": f"flash_attention(w={window})",
                     "shape": f"{b}x{h}x{s}x{d}",
                     "max_err": float(jnp.max(jnp.abs(o_k - o_r))),
                     "us_pallas_interp": us_k, "us_ref": us_r})

    # flash-decode: one token vs a long (fp / int8) cache
    from repro.models.attention import quantize_kv
    b2, h2, kv2, s2, d2 = 1, 4, 2, 1024, 64
    qd = jax.random.normal(key, (b2, h2, d2))
    kd = jax.random.normal(jax.random.fold_in(key, 3), (b2, kv2, s2, d2))
    vd = jax.random.normal(jax.random.fold_in(key, 4), (b2, kv2, s2, d2))
    pos = jnp.asarray(900, jnp.int32)
    o_k, us_k = timed(lambda: ops.flash_decode(qd, kd, vd, pos))
    o_r, us_r = timed(lambda: ref.flash_decode(qd, kd, vd, pos))
    rows.append({"kernel": "flash_decode(fp)", "shape": f"{b2}x{h2}x{s2}x{d2}",
                 "max_err": float(jnp.max(jnp.abs(o_k - o_r))),
                 "us_pallas_interp": us_k, "us_ref": us_r})
    kq, ks = quantize_kv(kd); vq, vs = quantize_kv(vd)
    o_k, us_k = timed(lambda: ops.flash_decode(qd, kq, vq, pos,
                                               k_scale=ks, v_scale=vs))
    o_r, us_r = timed(lambda: ref.flash_decode(qd, kq, vq, pos,
                                               k_scale=ks, v_scale=vs))
    rows.append({"kernel": "flash_decode(int8)",
                 "shape": f"{b2}x{h2}x{s2}x{d2}",
                 "max_err": float(jnp.max(jnp.abs(o_k - o_r))),
                 "us_pallas_interp": us_k, "us_ref": us_r})

    n = 8192
    wv = jax.random.dirichlet(key, jnp.ones(n))
    r = (jax.random.uniform(key, (n,)) > 0.5).astype(jnp.float32)
    o_k, us_k = timed(lambda: ops.ignorance_update(wv, r, jnp.asarray(1.1)))
    o_r, us_r = timed(lambda: ref.ignorance_update(wv, r, jnp.asarray(1.1)))
    rows.append({"kernel": "ignorance_update", "shape": f"{n}",
                 "max_err": float(jnp.max(jnp.abs(o_k - o_r))),
                 "us_pallas_interp": us_k, "us_ref": us_r})
    return rows


def main():
    enable_compile_cache()
    for r in run():
        print(f"{r['kernel']},{r['shape']},err={r['max_err']:.2e},"
              f"us_interp={r['us_pallas_interp']:.0f},us_ref={r['us_ref']:.0f}")


if __name__ == "__main__":
    main()

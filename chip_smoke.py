"""Run the ASCII session and serve path once on a TPU and check the results.

The deployment is Fashion halves (paper Section VI-B), at full width:
70,000 surrogate images split 70/30 (49,000 training rows), 784 pixels
split 392/392 between two agents, K = 10, MLP(128, 64) learners with 200
steps per fit, 4 rounds.  Data and weights come from fixed seeds.  The
times it prints are set-up and first-run wall times, not device metrics.

    python chip_smoke.py               one chip: (a) session, (b) serve,
                                       (c) kernels
    python chip_smoke.py --four-chips  only the fleet sharded over four
                                       chips, against the same fleet on one

Every check prints PASS or FAIL.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every check passed;
any failed check exits nonzero.  Without a TPU it exits nonzero at once:
there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

N = 70_000                  # Fashion-MNIST's 70,000 images
STEPS = 200                 # MLP steps per fit (examples/fashion_halves_nn.py)
ROUNDS = 4
REQUESTS = 32               # serve requests, alternating between two tenants
BLOCK = 256                 # test rows per request
FLEET = 8                   # sessions in the four-chip fleet

# Eager and compiled runs are separately compiled XLA programs, and so are
# the sharded and the one-device fleet.  On the TPU an f32 matmul at default
# precision rounds its inputs to bf16 (8 mantissa bits, 4e-3 relative), so a
# different fusion of the same fit can round different intermediates; 200
# dependent gradient steps carry that into params and alphas.  An alpha may
# therefore differ by 2% of its size (at least 0.02), and test accuracy by
# 1 point (210 of 21,000 rows).  A protocol error moves alphas by tens of
# percent and accuracy by many points.
ALPHA_RTOL = 0.02
ACC_TOL = 0.01
# Kernel against its jnp reference on the same chip: Mosaic's exp and divide
# may differ from XLA's in the last ulps.  A quantized value may then sit
# one step away where x / scale + u falls within an ulp of an integer,
# which is rare: at most 1 in 10^3 values may differ, each by one step.
KERNEL_RTOL = 1e-5
Q_MISMATCH_FRAC = 1e-3

_failures: list[str] = []
# JAX's own reports since the last compile_report(): seconds spent in the
# backend compiler (a persistent-cache load counts here too) and the
# persistent cache's hits and misses
_compiles = {"seconds": 0.0, "hits": 0, "misses": 0}


def watch_compiles() -> None:
    import jax

    def duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _compiles["seconds"] += seconds

    def count(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _compiles["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _compiles["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(duration)
    jax.monitoring.register_event_listener(count)


def compile_report(phase: str) -> None:
    print(f"  {phase} compile: {_compiles['seconds']:.1f} s in the backend "
          f"compiler, persistent cache {_compiles['hits']} hits, "
          f"{_compiles['misses']} misses (set-up)", flush=True)
    _compiles.update(seconds=0.0, hits=0, misses=0)


def check(ok: bool, what: str, detail: str = "") -> None:
    print(f"{'PASS' if ok else 'FAIL'} {what}" + (f": {detail}" if detail
                                                  else ""), flush=True)
    if not ok:
        _failures.append(what)


def require_tpu(count: int):
    """The device description of the first TPU; exits when there is none
    or when fewer than ``count`` chips are visible."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's default backend is "
                 f"{devices[0].platform!r}); nothing runs on the CPU instead")
    if len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def fashion_halves():
    import jax
    from repro.data.partition import train_test_split, vertical_split
    from repro.data.synthetic import fashion_surrogate
    ds = fashion_surrogate(jax.random.key(0), n=N)
    tr, te = train_test_split(0, N)
    Xs = vertical_split(ds.X, ds.splits)
    return ([x[tr] for x in Xs], ds.classes[tr], [x[te] for x in Xs],
            ds.classes[te])


def mosaic_in(lowered) -> bool:
    """Whether a lowered program calls a compiled Pallas (Mosaic) kernel;
    interpret mode and a jnp hand-off leave no such call."""
    return "tpu_custom_call" in lowered.as_text()


def close_alphas(a, b) -> tuple[bool, float]:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    gap = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))
    return a.shape == b.shape and gap <= ALPHA_RTOL, gap


# ===================================================================== phases
def phase_session(data):
    """(a) The int8-wire session, compiled and eager, and the mesh-ring
    session; returns the compiled int8 protocol and its inputs."""
    import jax
    import jax.numpy as jnp
    from repro.comm import make_codec
    from repro.core.engine import (MeshRingTransport, MeteredTransport,
                                   Protocol, SessionConfig, endpoints_for)
    from repro.core.protocol import ASCIIConfig, fit_single_agent_adaboost
    from repro.learners.mlp import MLP

    Xtr, ctr, Xte, cte = data
    learners = [MLP(hidden=(128, 64), steps=STEPS) for _ in Xtr]
    cfg = SessionConfig(num_classes=10, max_rounds=ROUNDS)

    def run(name, transport, backend):
        proto = Protocol(cfg, transport=transport, backend=backend)
        t0 = time.perf_counter()
        fitted = proto.fit(jax.random.key(1), endpoints_for(learners, Xtr),
                           ctr)
        acc = float(jnp.mean(fitted.predict(Xte) == cte))
        print(f"  {name}: {fitted.num_rounds} rounds, "
              f"{len(fitted.components)} components, test acc {acc:.4f}, "
              f"first run incl. compile {time.perf_counter() - t0:.1f} s "
              f"wall (set-up)", flush=True)
        return proto, fitted, acc

    def int8_wire():
        return MeteredTransport(codec=make_codec("int8"),
                                serve_codec=make_codec("int8"))

    comp, fit_c, acc_c = run("compiled, int8 wire", int8_wire(), "compiled")
    ring, _, acc_r = run("compiled, mesh ring", MeshRingTransport(),
                         "compiled")
    eager, fit_e, acc_e = run("eager, int8 wire", int8_wire(), "eager")
    single = fit_single_agent_adaboost(
        jax.random.key(2), Xtr[0], ctr, learners[0],
        ASCIIConfig(num_classes=10, max_rounds=ROUNDS))
    acc_s = float(jnp.mean(single.predict([Xte[0]]) == cte))
    print(f"  single agent (left half only): test acc {acc_s:.4f}",
          flush=True)

    check([(c.agent, c.round) for c in fit_c.components]
          == [(c.agent, c.round) for c in fit_e.components],
          "(a) compiled and eager keep the same components")
    ok, gap = close_alphas([c.alpha for c in fit_c.components],
                           [c.alpha for c in fit_e.components])
    check(ok, "(a) compiled vs eager alphas",
          f"largest gap {gap:.3g} of max(1, |alpha|), tolerance {ALPHA_RTOL}")
    check(abs(acc_c - acc_e) <= ACC_TOL, "(a) compiled vs eager test accuracy",
          f"{acc_c:.4f} vs {acc_e:.4f}, tolerance {ACC_TOL}")
    check(comp.transport.log.entries == eager.transport.log.entries,
          "(a) compiled and eager book the same wire ledger",
          f"{comp.transport.total_bits} bits")
    check(acc_c > acc_s, "(a) ASCII beats the single agent (int8 wire)",
          f"{acc_c:.4f} > {acc_s:.4f}")
    check(acc_r > acc_s, "(a) ASCII beats the single agent (mesh ring)",
          f"{acc_r:.4f} > {acc_s:.4f}")
    return comp, ring


def phase_serve(proto, data):
    """(b) Requests from two tenants through the serve engine, each answer
    checked against the same request served alone."""
    import numpy as np
    from repro.serve import ServeEngine

    _, _, Xte, _ = data
    blocks = {rid: [x[rid * BLOCK:(rid + 1) * BLOCK] for x in Xte]
              for rid in range(REQUESTS)}
    engine = ServeEngine(max_batch=8)
    engine.add_session("fashion", proto)
    t0 = time.perf_counter()
    for rid, Xblk in blocks.items():
        engine.submit(("tenant-a", "tenant-b")[rid % 2], "fashion", Xblk,
                      request=rid)
    done = engine.flush()
    print(f"  first flush incl. compile {time.perf_counter() - t0:.1f} s "
          f"wall (set-up)", flush=True)
    stats = engine.batcher.stats()
    print(f"  requests served {len(done)}, batches {stats['batches_run']}, "
          f"padded slots {stats['padded_slots']}", flush=True)
    check(len(done) == REQUESTS, "(b) every request served",
          f"{len(done)} of {REQUESTS}")
    equal = sum(
        np.array_equal(done[rid].preds,
                       np.asarray(proto.predict_distributed(Xblk,
                                                            request=rid)))
        for rid, Xblk in blocks.items() if rid in done)
    check(equal == REQUESTS,
          "(b) batched answers equal predict_distributed(request=rid)",
          f"{equal} of {REQUESTS}")


def phase_kernels(comp, ring, data):
    """(c) Each Pallas kernel once at the phases' shapes against
    kernels/ref.py, and a Mosaic call in every program that uses one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import compiled
    from repro.kernels import ops, ref

    Xtr, ctr, Xte, _ = data
    shapes = tuple(x.shape[1:] for x in Xtr)
    key = jax.random.key(1)
    for name, proto in (("int8 wire", comp), ("mesh ring", ring)):
        plan = proto._compiled_ctx[1]
        lowered = compiled._session_program(plan, shapes).lower(
            key, tuple(Xtr), ctr)
        check(mosaic_in(lowered), f"(c) session program ({name}) calls a "
              f"Mosaic kernel (tpu_custom_call)")
    _, plan, result = comp._compiled_ctx
    serve = jax.jit(lambda res, k, Xs: compiled.serve_session(plan, res, k,
                                                              Xs))
    lowered = serve.lower(result, key, tuple(x[:BLOCK] for x in Xte))
    check(mosaic_in(lowered), "(c) serve program (int8 wire) calls a Mosaic "
          "kernel (tpu_custom_call)")

    n = Xtr[0].shape[0]
    k1, k2, k3 = jax.random.split(jax.random.key(3), 3)
    w = jax.random.uniform(k1, (n,)) + 0.1
    w = w / jnp.sum(w)
    r = (jax.random.uniform(k2, (n,)) > 0.3).astype(jnp.float32)
    alpha = jnp.asarray(1.7, jnp.float32)

    def kernel(name, fn, args):
        check(mosaic_in(jax.jit(fn).lower(*args)),
              f"(c) {name} runs as a Mosaic kernel")
        return fn(*args)

    got = kernel(f"ignorance update n={n}",
                 lambda w, r, a: ops.ignorance_update(w, r, a), (w, r, alpha))
    want = ref.ignorance_update(w, r, alpha)
    err = float(jnp.max(jnp.abs(got - want) / jnp.abs(want)))
    check(err <= KERNEL_RTOL, f"(c) ignorance update n={n} vs ref",
          f"largest relative error {err:.3g}, tolerance {KERNEL_RTOL}")

    def quantized(name, fn, ref_fn, x):
        u = jax.random.uniform(k3, x.shape)
        xhat, q, scales = kernel(name, lambda x, u: fn(x, u, 127.0), (x, u))
        xhat_r, q_r, scales_r = ref_fn(x, u, 127.0)
        s_err = float(jnp.max(jnp.abs(scales - scales_r) / scales_r))
        dq = np.abs(np.asarray(q, np.int32) - np.asarray(q_r, np.int32))
        frac = float(np.mean(dq > 0))
        step = np.repeat(np.asarray(scales_r), q.size // scales.size)
        xerr = np.abs(np.asarray(xhat - xhat_r)).reshape(-1)
        check(s_err <= KERNEL_RTOL and dq.max() <= 1
              and frac <= Q_MISMATCH_FRAC
              and bool(np.all(xerr <= step * (1 + KERNEL_RTOL))),
              f"(c) {name} vs ref",
              f"scale error {s_err:.3g}, {int(np.sum(dq > 0))} of {q.size} "
              f"int8 values one step off, tolerance {Q_MISMATCH_FRAC}")

    quantized(f"int8 quantize-dequant n={n}", ops.quantize_dequant,
              ref.quantize_dequant, w)
    scores = jax.random.normal(k1, (Xte[0].shape[0], 10))
    for rows in (scores.shape[0], BLOCK):
        quantized(f"int8 score-block quantize-dequant {rows}x10",
                  ops.quantize_dequant_block, ref.quantize_dequant_block,
                  scores[:rows])
    q4 = jax.random.randint(k2, (n,), -7, 8).astype(jnp.int8)
    packed = kernel(f"int4 pack m={n}", ops.pack_int4, (q4,))
    check(np.array_equal(packed, ref.pack_int4(q4)),
          f"(c) int4 pack m={n} equals ref")
    unpacked = kernel(f"int4 unpack m={n}",
                      lambda p: ops.unpack_int4(p, n), (packed,))
    check(np.array_equal(unpacked, q4), f"(c) int4 unpack m={n} round-trips")


def phase_four_chips(data):
    """The fleet sharded over all chips against the same fleet on one."""
    import jax
    from repro.core import compiled
    from repro.learners.logistic import LogisticRegression

    Xtr, ctr, _, _ = data
    plan = compiled.plan_for([LogisticRegression() for _ in Xtr], 10,
                             max_rounds=ROUNDS)
    keys = jax.random.split(jax.random.key(7), FLEET)
    t0 = time.perf_counter()
    sharded = compiled.fleet_run(plan, keys, Xtr, ctr, shard_axis="data")
    jax.block_until_ready(sharded)
    print(f"  sharded fleet of {FLEET} over {len(jax.devices())} chips: "
          f"first run incl. compile {time.perf_counter() - t0:.1f} s wall "
          f"(set-up)", flush=True)
    check(len(sharded.alphas.sharding.device_set) == len(jax.devices()),
          "(4) the sharded fleet spans every chip")
    one = jax.devices()[0]
    t0 = time.perf_counter()
    with jax.default_device(one):
        local = compiled.fleet_run(
            plan, jax.device_put(keys, one),
            [jax.device_put(x, one) for x in Xtr], jax.device_put(ctr, one))
        jax.block_until_ready(local)
    print(f"  same fleet on one chip: first run incl. compile "
          f"{time.perf_counter() - t0:.1f} s wall (set-up)", flush=True)
    check(local.alphas.sharding.device_set == {one},
          "(4) the reference fleet ran on one chip")
    sharded, local = jax.device_get((sharded, local))
    for s in range(FLEET):
        ok, gap = close_alphas(sharded.alphas[s], local.alphas[s])
        same = bool((sharded.valid[s] == local.valid[s]).all())
        check(ok and same, f"(4) session {s}: sharded vs one chip",
              f"same components {same}, largest alpha gap {gap:.3g} of "
              f"max(1, |alpha|), tolerance {ALPHA_RTOL}")


# ======================================================================= main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet sharded over four chips, "
                         "against the same fleet on one chip")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: {SRC / 'repro'} not found; run this script "
                 f"from a checkout of the repository")
    sys.path.insert(0, str(SRC))
    device = require_tpu(4 if args.four_chips else 1)
    from repro.launch.compile_cache import enable_compile_cache
    cache = Path(enable_compile_cache())
    cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(f"device: {device['kind']} x{device['count']}; compile cache "
          f"{cache} holds {cached} entries", flush=True)
    watch_compiles()

    t0 = time.perf_counter()
    data = fashion_halves()
    print(f"data: {N} images, train {data[1].shape[0]} rows, test "
          f"{data[3].shape[0]} rows, {data[0][0].shape[1]}+"
          f"{data[0][1].shape[1]} px, made in "
          f"{time.perf_counter() - t0:.1f} s (set-up)", flush=True)
    compile_report("data")
    if args.four_chips:
        print("phase (4): sharded fleet", flush=True)
        phase_four_chips(data)
        compile_report("phase (4)")
    else:
        print("phase (a): session", flush=True)
        comp, ring = phase_session(data)
        compile_report("phase (a)")
        print("phase (b): serve", flush=True)
        phase_serve(comp, data)
        compile_report("phase (b)")
        print("phase (c): kernels", flush=True)
        phase_kernels(comp, ring, data)
        compile_report("phase (c)")
    now = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(f"compile cache: {cached} entries before, {now} after", flush=True)
    if _failures:
        print(f"chip_smoke: {len(_failures)} checks failed: "
              + "; ".join(_failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

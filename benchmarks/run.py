"""Benchmark orchestrator — one section per paper table/figure plus the
kernel and roofline reports.  Prints ``name,us_per_call,derived`` CSV lines
per section.  Use --full for paper-scale replication counts."""
from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import enable_compile_cache


def _section(name):
    print(f"\n# === {name} ===", flush=True)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes/replications (slow)")
    args, _ = ap.parse_known_args()
    quick = not args.full

    t0 = time.time()
    _section("fig3_accuracy (ASCII vs Single vs Oracle)")
    from benchmarks import fig3_accuracy
    for r in fig3_accuracy.run(reps=5 if args.full else 2,
                               rounds=10 if args.full else 6, quick=quick):
        print(f"fig3_{r['dataset']}_{r['method']},"
              f"{0:.0f},final_acc={r['final_acc']:.4f}")

    _section("fig4_transmission (bits at 90%-oracle)")
    from benchmarks import fig4_transmission
    for r in fig4_transmission.run(quick=quick):
        print(f"fig4_{r['dataset']},{0:.0f},cost_ratio={r['cost_ratio']:.1f}x"
              f";ascii_bits={r['ascii_bits']};oracle_bits={r['oracle_bits']}")

    _section("comm frontier (accuracy vs encoded bits across wire codecs)")
    cf = fig4_transmission.frontier(quick=quick, out="BENCH_comm.json")
    for r in cf["rows"]:
        print(f"comm_{r['point']},{0:.0f},acc={r['acc']:.4f};"
              f"interchange_bits={r['interchange_bits']};"
              f"ratio_vs_fp32={r['bits_ratio_vs_fp32']:.2f}x")
    print("comm_frontier,0,written=BENCH_comm.json")

    _section("fig6_variants (ASCII vs Simple/Random/Ensemble/Async)")
    from benchmarks import fig6_variants
    for r in fig6_variants.run(reps=3 if args.full else 1,
                               rounds=8 if args.full else 5, quick=quick):
        print(f"fig6_{r['dataset']}_{r['method']},"
              f"{0:.0f},final_acc={r['final_acc']:.4f}")

    _section("fleet (eager loop vs compiled session vs vmapped fleet)")
    from benchmarks import fleet_bench
    fr = fleet_bench.run(sessions=16 if args.full else 8,
                         rounds=6 if args.full else 4,
                         steps=150 if args.full else 80,
                         out="BENCH_fleet.json")
    for mode in ("eager", "compiled", "fleet"):
        print(f"fleet_{mode},{fr[mode]['seconds'] * 1e6:.0f},"
              f"sessions_per_sec={fr[mode]['sessions_per_sec']:.2f}")
    print(f"fleet_speedup,0,fleet_vs_eager="
          f"{fr['speedup_fleet_vs_eager']:.1f}x (BENCH_fleet.json)")

    _section("serve (continuous batching vs per-request dispatch)")
    from benchmarks import serve_bench
    sr = serve_bench.run(sessions=8, requests=128 if args.full else 48,
                         steps=80 if args.full else 40,
                         verify=True, out="BENCH_serve.json")
    for mode in ("sequential", "batched"):
        print(f"serve_{mode},{sr[mode]['seconds'] * 1e6:.0f},"
              f"qps={sr[mode]['qps']:.1f};p50_ms={sr[mode]['p50_ms']:.2f};"
              f"p99_ms={sr[mode]['p99_ms']:.2f}")
    print(f"serve_speedup,0,batched_vs_sequential="
          f"{sr['speedup_batched_vs_sequential']:.2f}x;"
          f"verified={sr['verified_bit_identical']} (BENCH_serve.json)")

    _section("telemetry (instrumented vs dark, bit-identity + overhead)")
    from benchmarks import telemetry_bench
    tb = telemetry_bench.run(repeats=5 if args.full else 3,
                             out="BENCH_telemetry.json")
    print(f"telemetry_instrumented,"
          f"{tb['instrumented']['seconds'] * 1e6:.0f},"
          f"overhead={tb['overhead_ratio']:.3f}x;"
          f"bit_identical={tb['bit_identical']};"
          f"spans={tb['spans']} (BENCH_telemetry.json)")

    _section("kernels (Pallas interpret vs jnp oracle)")
    from benchmarks import kernels_bench
    for r in kernels_bench.run():
        print(f"kernel_{r['kernel']},{r['us_pallas_interp']:.0f},"
              f"max_err={r['max_err']:.2e}")

    _section("roofline (from dry-run artifacts)")
    from benchmarks import roofline
    rows = roofline.load()
    if not rows:
        print("roofline,0,no artifacts (run repro.launch.dryrun first)")
    else:
        for line in roofline.table(rows):
            print(line)

    print(f"\n# total bench wall time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()

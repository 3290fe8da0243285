"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``, whose ``generator`` names the module in
``bench/traffic/`` that drives it).  One run:

1. requires as many TPU chips as the cell asks for, and exits nonzero
   without them (there is no CPU fallback);
2. keeps JAX's persistent compilation cache in the checkout (``.jax_cache``)
   or where ``JAX_COMPILATION_CACHE_DIR`` says;
3. makes the cell's data on the device from ``--seed``, fits what the
   traffic needs and warms up the cell's own shapes: all of that, from
   process start, is ``setup_s``;
4. measures for ``--seconds``.  With ``--trace 0`` it reports the cell's
   end-to-end metrics; with ``--trace 1`` it records a profiler trace of the
   window and reports the per-layer metrics, each read by
   ``bench/metrics/<metric>.py`` from the run's record;
5. after the window, with the program's state freed, compares what the
   timed path produced with the plain reference (``bench/ref.py``) and
   prints each number beside its limit, on standard error and under
   ``checks`` in the result line, which is the last line of standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# run as a script, Python puts bench/ first on the path, where trace.py and
# data.py would shadow modules of the same name; the checkout root goes
# there instead, so the harness imports as the ``bench`` package
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TRACE_DIR = ROOT / ".bench_trace"
# seconds of the window a traced run records: a blob20 session's trace holds
# about 250,000 device events a second, so the trace stops after the unit
# of work that crosses this mark
TRACE_S = 4.0


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    key: object = None        # jax.random.key(seed), set when JAX is up


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(bench: dict, workload: str, seed: int, seconds: float,
              trace: bool, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r}; known: "
                         f"{sorted(cells)}")
    entry = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{entry['traffic']}.json").read_text())
    return Cell(workload, config, traffic, int(entry["chips"]), seed,
                seconds, trace)


def reported(bench: dict, workload: str, kind: str) -> list:
    """The metric entries this cell reports: end-to-end ones that list it
    (or list no cells); per-layer ones that list it, or list no cells and
    move an end-to-end metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in names)]


def read_metric(name: str, record: dict):
    """Run ``bench/metrics/<name>.py``'s ``read(record)``: a number, or
    None where the run holds nothing for it to read."""
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(record)


def require_chips(count: int) -> dict:
    """The platform, kind and count of the devices; exits nonzero when
    they are not TPUs or fewer than ``count``."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX's backend is "
                         f"{devices[0].platform!r}); nothing runs on the CPU "
                         f"instead")
    if len(devices) < count:
        raise SystemExit(f"bench: the cell needs {count} TPU chips, found "
                         f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


class Compiles:
    """JAX's own reports: backend-compile seconds and count, persistent
    cache hits and misses."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = self.hits = self.misses = 0

        def duration(event, seconds, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += seconds
                self.count += 1

        def event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(duration)
        jax.monitoring.register_event_listener(event)

    def snapshot(self) -> tuple:
        return self.seconds, self.count, self.hits, self.misses


def memory_peak_bytes() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Profile:
    """A profiler trace of the first ``TRACE_S`` seconds of the window (to
    the end of the unit of work that crosses it), annotated
    ``bench.traced``; the traffic calls ``tick`` after each unit of work."""

    def __init__(self, directory: Path, on: bool):
        self.directory, self.on = directory, on
        self.annotation = None

    def start(self) -> None:
        if not self.on:
            return
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(str(self.directory))
        self.annotation = jax.profiler.TraceAnnotation("bench.traced")
        self.annotation.__enter__()
        self.t0 = time.perf_counter()

    def tick(self) -> None:
        if self.annotation is not None \
                and time.perf_counter() - self.t0 >= TRACE_S:
            self.stop()

    def stop(self) -> None:
        if self.annotation is not None:
            import jax
            self.annotation.__exit__(None, None, None)
            self.annotation = None
            jax.profiler.stop_trace()


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def run_cell(cell: Cell, bench: dict, device: dict) -> dict:
    """Set up, measure and check one run of ``cell``; the result line."""
    import jax

    from bench import flops, program
    program.ensure_importable()
    compiles = Compiles()
    cell.key = jax.random.key(cell.seed)
    generator = importlib.import_module(
        f"bench.traffic.{cell.traffic['generator']}")
    traffic = generator.Traffic(cell)
    traffic.setup()
    setup_s = time.perf_counter() - T_START
    c0 = compiles.snapshot()
    log(f"set-up {setup_s:.3f} s: backend compile {c0[0]:.3f} s in {c0[1]} "
        f"programs, persistent cache {c0[2]} hits {c0[3]} misses")

    trace_dir = TRACE_DIR / cell.name
    profile = Profile(trace_dir, cell.trace)
    profile.start()
    try:
        e2e = traffic.window(cell.seconds, profile.tick)
    finally:
        profile.stop()
    c1 = compiles.snapshot()
    log(f"window: {c1[1] - c0[1]} compiles ({c1[0] - c0[0]:.3f} s), "
        f"persistent cache {c1[2] - c0[2]} hits {c1[3] - c0[3]} misses")
    device = dict(device, memory_peak_bytes=memory_peak_bytes())
    attempted, failed = traffic.attempted()

    out_metrics, breakdown = {}, None
    if cell.trace:
        from bench import trace as tr
        reduced = tr.reduce(tr.load(tr.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        record = dict(traffic.record(), trace=reduced, config=cell.config,
                      traffic=cell.traffic, peak=flops.peaks(device["kind"]))
        for m in reported(bench, cell.name, "per_layer"):
            value = read_metric(m["name"], record)
            if value is not None:
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured = dict(e2e, setup_s=setup_s)
        for m in reported(bench, cell.name, "end_to_end"):
            out_metrics[m["name"]] = {"value": measured[m["name"]],
                                      "unit": m["unit"]}

    traffic.release()
    gc.collect()
    checks = traffic.check()
    correct = all(value <= limit for _, value, limit in checks)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": out_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    cell = load_cell(bench, args.workload, args.seed, args.seconds,
                     bool(args.trace))
    from bench import program
    program.ensure_importable()
    device = require_chips(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # every program goes in the cache, however fast it compiled, so that a
    # second run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"{device['kind']} x{device['count']}; compile cache {cache}")
    result = run_cell(cell, bench, device)
    for name, check in result["checks"].items():
        print(f"check {name} {check['value']!r} limit {check['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

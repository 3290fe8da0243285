"""The readers of the program's ``fit``/``plan``/``extract``/``replay``
spans and ``ascii_update_<j>``/``ascii_channel_<j>`` name scopes: their
numbers on hand-built records, None on a record of a program that opens
none of them, and the host shares of a CPU run of each session cell."""
import gzip
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_small  # noqa: E402
from bench import run  # noqa: E402
from bench import trace as tr  # noqa: E402

HOST = ("protocol.plan_share", "protocol.extract_share",
        "protocol.replay_share")
DEVICE = ("update.device_share", "channel.device_share")
CELLS = ("fashion.session.int8", "blob20.session.adaptive")


def test_bench_span_metrics_are_reported_in_both_cells():
    bench = run.load_benchmark()
    for cell in CELLS:
        names = {m["name"] for m in run.reported(bench, cell, "per_layer")}
        assert set(HOST + DEVICE) <= names


def test_bench_span_metrics_host_shares_by_hand():
    # two sessions: fit 10 s = plan 1 + session 6 + extract 2 + replay 0.5
    # + 0.5 of fit's own
    one = [("plan", 1.0), ("session", 6.0), ("extract", 2.0),
           ("replay", 0.5), ("fit", 10.0)]
    rec = {"spans": one + one}
    got = {m: run.read_metric(m, rec) for m in HOST}
    assert got == pytest.approx({"protocol.plan_share": 10.0,
                                 "protocol.extract_share": 20.0,
                                 "protocol.replay_share": 5.0})


def test_bench_span_metrics_device_shares_by_hand():
    body = "jit(f)/while/body/"
    ops = [(0, 2000, "fusion.1", body + "ascii_hop_0/dot", 0),
           (2000, 2500, "fusion.2", body + "ascii_update_0/mul", 0),
           (2500, 3500, "ignorance_update.3",
            body + "ascii_update_0/pallas_call", 0),
           (3500, 4500, "fusion.4", body + "ascii_channel_0/select", 0),
           (4500, 5000, "copy.5", "", 0),
           # half of it past the window's end
           (9000, 11000, "fusion.6", body + "ascii_channel_1/round", 0)]
    red = tr.reduce(tr.Trace(ops, [(0, 10000, "bench.traced")]))
    # the new scopes are no hop's: the fit share reads what it read before
    assert red["scope_s"] == pytest.approx({"ascii_hop_0": 2e-6,
                                            "other": 4e-6})
    rec = {"trace": red}
    assert run.read_metric("update.device_share", rec) == pytest.approx(25.0)
    assert run.read_metric("channel.device_share", rec) == pytest.approx(
        100.0 / 3)
    assert run.read_metric("learner.fit_share", rec) == pytest.approx(
        100.0 / 3)


def test_bench_span_metrics_none_without_the_new_spans_and_scopes():
    """A record of a program that opens only ``session`` and ``replay``
    spans and only ``ascii_hop_<j>`` scopes: a traced window recorded on a
    TPU v5e before the new spans and scopes existed."""
    path = Path(__file__).parent / "data" / "trace_cut_fashion.json.gz"
    with gzip.open(path, "rt") as f:
        cut = json.load(f)
    t = tr.Trace([tuple(o) for o in cut["ops"]],
                 [tuple(h) for h in cut["host"]])
    red = tr.reduce(t, *cut["window"])
    rec = {"trace": red, "spans": [("session", 0.2), ("replay", 0.01)]}
    assert sum(red["scope_s"].values()) > 0
    for m in HOST + DEVICE:
        assert run.read_metric(m, rec) is None, m


@pytest.mark.parametrize("workload", CELLS)
def test_bench_span_metrics_host_shares_on_a_cpu_run(workload):
    """The session queue at test size with the window's telemetry on (as
    a traced run has it, the profiler off): the three host shares and the
    ``session`` span's lie inside the ``fit`` spans."""
    import jax
    from bench.shares import span_share
    from bench.traffic import session_queue
    _, cell = bench_small.small_cell(workload, 2**31 + 21)
    cell.trace = True
    cell.key = jax.random.key(cell.seed)
    traffic = session_queue.Traffic(cell)
    traffic.setup()
    traffic.window(cell.seconds)
    rec = traffic.record()
    shares = [run.read_metric(m, rec) for m in HOST]
    for name, share in zip(HOST, shares):
        assert share is not None and 0 < share < 100, (name, share)
    assert sum(shares) + span_share(rec, "session") <= 100

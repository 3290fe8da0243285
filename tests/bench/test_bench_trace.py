"""The trace reduction of the chip benchmark (bench/trace.py): the
profiler's XSpace read into device ops and host annotations, busy time as
the union of op intervals, op and name-scope sums over leaf ops, and idle
gaps named by the innermost host annotation."""
import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace as tr  # noqa: E402

# one device plane (a while loop holding two ops, then a kernel) and one
# host thread (the traced window, a session annotation, a replay span)
XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 2500000 duration_ps: 1500000 }
    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.3 = (f32[]) while()"
    display_name: "while.3" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = f32[] fusion()"
    stats { metadata_id: 7 str_value: "jit(f)/while/body/ascii_hop_0/dot" }
    stats { metadata_id: 8 str_value: "convolution fusion" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.2 = f32[] fusion()"
    stats { metadata_id: 7 str_value: "jit(f)/while/body/ascii_hop_1/add" } } }
  event_metadata { key: 4 value { id: 4 name: "%quantize_dequant.20 = ()"
    stats { metadata_id: 7 str_value: "jit(f)/quantize_dequant/pallas_call" } } }
  event_metadata { key: 5 value { id: 5 name: "jit_f(123)" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "hlo_category" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 6500000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "bench.session" } }
  event_metadata { key: 3 value { id: 3 name: "replay" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(f)" } }
}
"""


@pytest.fixture(scope="module")
def hand_built():
    from google.protobuf import text_format
    space = text_format.Parse(XSPACE, tr._xspace_class()())
    # round-trip through the wire format, as a trace file is read
    again = tr._xspace_class()()
    again.ParseFromString(space.SerializeToString())
    return tr.from_xspace(again)


def test_bench_trace_reads_device_ops_and_kept_host_annotations(hand_built):
    t = hand_built
    assert [(o[2], o[3]) for o in t.ops] == [
        ("while.3", ""), ("fusion.1", "jit(f)/while/body/ascii_hop_0/dot"),
        ("fusion.2", "jit(f)/while/body/ascii_hop_1/add"),
        ("quantize_dequant.20", "jit(f)/quantize_dequant/pallas_call")]
    assert t.ops[0][:2] == (1000.0, 6000.0)
    assert {o[4] for o in t.ops} == {0}
    # the JAX dispatch event is not one of the kept annotations
    assert [h[2] for h in t.host] == ["bench.traced", "bench.session",
                                      "replay"]


def test_bench_trace_reduction_on_hand_built_events(hand_built):
    red = tr.reduce(hand_built)
    assert (red["lo"], red["hi"]) == (0.0, 12000.0)
    assert red["window_s"] == pytest.approx(12e-6)
    # busy: [1000, 6000] (the while loop covers its body) and [9000, 10000]
    assert red["busy_s"] == pytest.approx(6e-6)
    # the while loop's own event is not a leaf; its body ops are
    assert [o[2] for o in red["ops"]] == ["fusion.1", "fusion.2",
                                          "quantize_dequant.20"]
    assert red["scope_s"] == pytest.approx(
        {"ascii_hop_0": 1e-6, "ascii_hop_1": 1.5e-6, "other": 1e-6})
    assert dict(red["device_ops"]) == pytest.approx(
        {"ascii_hop_1/fusion": 1.5e-6, "ascii_hop_0/fusion": 1e-6,
         "other/quantize_dequant": 1e-6})
    # idle gaps and the innermost annotation open at their midpoints:
    # [0, 1000] at 500 in bench.session (it opens at 500), [6000, 9000] at
    # 7500 in replay, [10000, 12000] at 11000 in the traced window alone
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"bench.session": 1e-6, "replay": 3e-6, "bench.traced": 2e-6})
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"])


@pytest.mark.parametrize("intervals,busy", [
    ([(0, 10), (5, 15), (20, 30)], 25),       # overlap
    ([(0, 100), (10, 20), (30, 40)], 100),    # nested
    ([(5, 6), (0, 1)], 2),                    # unsorted, disjoint
    ([(0, 10), (10, 20)], 20),                # touching
])
def test_bench_trace_busy_is_the_union_of_intervals(intervals, busy):
    ops = [(s, e, "op", "", 0) for s, e in intervals]
    assert tr.busy_ns(ops, -1e9, 1e9) == busy
    assert tr.busy_ns(ops, 2, 8) == pytest.approx(
        sum(e - s for s, e in tr.merge(tr.clip(intervals, 2, 8))))


def test_bench_trace_short_names():
    assert tr.short_name("%fusion.12 = f32[8]{0} fusion(%p)") == "fusion.12"
    assert tr.short_name("quantize_dequant") == "quantize_dequant"
    assert tr.base_name("convolution_add_fusion.11") == "convolution_add_fusion"


@pytest.fixture(scope="module")
def chip_cut():
    path = Path(__file__).parent / "data" / "trace_cut_fashion.json.gz"
    with gzip.open(path, "rt") as f:
        cut = json.load(f)
    return cut, tr.Trace([tuple(o) for o in cut["ops"]],
                         [tuple(h) for h in cut["host"]])


def test_bench_trace_reduction_on_a_chip_trace(chip_cut):
    """A 5 ms cut of a fashion.session.int8 trace recorded on a TPU v5e:
    the reduction agrees with a direct sweep over the event boundaries."""
    cut, t = chip_cut
    lo, hi = cut["window"]
    red = tr.reduce(t, lo, hi)
    # busy by brute force: sweep every boundary, count covered stretches
    points = sorted({lo, hi} | {max(lo, min(hi, x)) for o in t.ops
                                for x in o[:2]})
    covered = sum(b - a for a, b in zip(points, points[1:])
                  if any(o[0] <= a and b <= o[1] for o in t.ops))
    assert red["busy_s"] == pytest.approx(covered * 1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])
    # leaf ops never overlap on one TPU core, so their sum is the busy time
    leaf = sum(min(o[1], hi) - max(o[0], lo) for o in red["ops"]
               if o[1] > lo and o[0] < hi) * 1e-9
    assert sum(red["scope_s"].values()) == pytest.approx(leaf)
    assert leaf == pytest.approx(red["busy_s"], rel=1e-6)
    assert any(k.startswith("ascii_hop_") for k in red["scope_s"])
    sums = [v for _, v in red["device_ops"]]
    assert sums == sorted(sums, reverse=True) and len(sums) <= 10

"""What decides ``correct`` in the dsv2lite.session.notes cell of the chip
benchmark, at a size the CPU test run holds: a sound run passes; the
control (the plain reference in bfloat16 put in the program's place) and
every fault planted in the timed path fail.  The cell's per-layer metric
readers read a hand-made record, and read nothing (None) from a record of
a program that does not count its backbone's work."""
import importlib.util
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_small_backbone as small  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(autouse=True)
def fresh_programs():
    small.clear_programs()
    yield
    small.clear_programs()


def test_bench_check_dsv2lite_sound_run_is_correct():
    result = small.sound_result(2**31 + 7)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert list(result)[-1] == "checks"


def test_bench_check_dsv2lite_control_fails():
    checks = small.control_checks(2**31 + 8)
    assert any(value > limit for _, value, limit in checks), checks


@pytest.mark.parametrize("fault", small.FAULTS)
def test_bench_check_dsv2lite_fault_fails(fault, monkeypatch):
    result = small.faulty_result(2**31 + 9, fault, monkeypatch)
    assert not result["correct"], result["checks"]


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _record(counted: bool) -> dict:
    from bench import run
    bench = run.load_benchmark()
    cell = run.load_cell(bench, small.CELL, 1, 30.0, True)
    session = {"wall_s": 20.0, "hops": [2, 2]}
    if counted:
        session.update(tokens_fit=2 * 32 * 16 * 512,
                       tokens_predict=2 * 1024 * 512,
                       expert_tokens_fit=2 * 196_608,
                       expert_tokens_predict=2 * 3_145_728)
    ns = 1e9
    ops = [(0, ns, "fusion.1", "jit(f)/ascii_hop_0/backbone_experts/x", 0),
           (ns, 2 * ns, "ragged-dot-none", "", 0),
           (2 * ns, 3 * ns, "fusion.2", "jit(f)/ascii_hop_0/backbone_attn/y", 0),
           (3 * ns, 4 * ns, "fusion.3",
            "jit(f)/ascii_hop_0/backbone_predict/backbone_attn/z", 0),
           (4 * ns, 5 * ns, "fusion.4", "jit(f)/ascii_update_0/w", 0)]
    trace = {"scope_s": {"ascii_hop_0": 4.0, "other": 1.0}, "ops": ops,
             "lo": 0, "hi": 5 * ns, "window_s": 5.0, "busy_s": 5.0}
    return {"n_train": 1024, "widths": [512, 16], "sessions": [session],
            "spans": [], "trace": trace, "config": cell.config,
            "traffic": cell.traffic, "peak": {"bf16_flops_per_s": 197e12}}


def test_bench_check_dsv2lite_metric_readers_read_a_record():
    from bench import flops_backbone
    rec = _record(counted=True)
    assert _reader("backbone.expert_share")(rec) == pytest.approx(40.0)
    assert _reader("backbone.attention_share")(rec) == pytest.approx(40.0)
    assert _reader("backbone.predict_share")(rec) == pytest.approx(20.0)
    cfg = rec["config"]
    want = flops_backbone.backbone_flops(cfg, rec["sessions"][0]) + 2 * \
        flops_backbone.mlp_hop_flops(cfg, cfg["agents"][1], 16)
    got = _reader("backbone.mfu")(rec)
    assert got == pytest.approx(100.0 * want / (20.0 * 197e12))
    assert 0.0 < got < 100.0


def test_bench_check_dsv2lite_metric_readers_read_nothing_from_the_parent():
    rec = _record(counted=False)
    rec["trace"]["ops"] = [o[:2] + (f"fusion.{i}", "jit(f)/ascii_hop_0", 0)
                           for i, o in enumerate(rec["trace"]["ops"])]
    for name in ("backbone.mfu", "backbone.expert_share",
                 "backbone.attention_share", "backbone.predict_share"):
        assert _reader(name)(rec) is None, name


def test_bench_check_dsv2lite_host_shares_on_a_cpu_run():
    """The cell at test size with the window's telemetry on (as a traced
    run has it, the profiler off): the readers of the protocol's spans,
    which the cell reports beside the backbone's, read its record."""
    import jax
    from bench import run
    from bench.traffic import backbone_session_queue as bq
    _, cell = small.small_cell(2**31 + 21)
    cell.trace = True
    cell.key = jax.random.key(cell.seed)
    traffic = bq.Traffic(cell)
    traffic.setup()
    traffic.window(cell.seconds)
    rec = traffic.record()
    bench = run.load_benchmark()
    names = {m["name"] for m in run.reported(bench, small.CELL,
                                             "per_layer")}
    host = ("protocol.host_share", "protocol.plan_share",
            "protocol.extract_share", "protocol.replay_share")
    assert set(host) <= names
    for name in host:
        share = run.read_metric(name, rec)
        assert share is not None and 0 < share < 100, (name, share)

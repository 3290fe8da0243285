"""Launch-CLI pins for the session driver's argparse surface (PR 9).

Clock-skew must be rejected *at argparse time* on every path — the
explicit ``--clock-skew`` flag with the default scheduler used to fall
through to ``Scenario.validate`` with a message that never named the
flags — and the combinations the compiled backend newly accepts
(async variant, budget-aware scheduler) must actually run end to end.
"""
import sys

import pytest

from repro.launch import session as cli
from repro.scenarios import Scenario


def run_cli(monkeypatch, argv):
    monkeypatch.setattr(sys, "argv", ["session.py"] + argv)
    cli.main()


# ----------------------------------------------------------- clock-skew pins
def test_clock_skew_explicit_flag_errors_at_argparse(monkeypatch, capsys):
    """The hoisted check: explicit --clock-skew with the default variant
    dies in argparse with a message naming both flags, not deep in the
    session."""
    with pytest.raises(SystemExit) as exc:
        run_cli(monkeypatch, ["--clock-skew", "0,0,1,2", "--rounds", "2"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "--clock-skew" in err and "--variant async" in err


def test_clock_skew_preset_conflict_errors_at_argparse(monkeypatch, capsys):
    """The preset path keeps its own argparse-time rejection."""
    with pytest.raises(SystemExit) as exc:
        run_cli(monkeypatch, ["--scenario", "clean",
                              "--clock-skew", "0,0,1,2"])
    assert exc.value.code == 2
    assert "presets fix the scenario knobs" in capsys.readouterr().err


def test_clock_skew_malformed_value_errors(monkeypatch, capsys):
    with pytest.raises(SystemExit) as exc:
        run_cli(monkeypatch, ["--variant", "async",
                              "--clock-skew", "a,b"])
    assert exc.value.code == 2
    assert "comma-separated" in capsys.readouterr().err


def test_clock_skew_scenario_validate_still_rejects_nonasync():
    """The underlying Scenario.validate guard the CLI check hoists — kept
    as the backstop for non-CLI constructions."""
    from repro.core.engine import SequentialScheduler
    from repro.scenarios import make_variant
    scenario = Scenario("unit", clock_skew=(0, 0, 1, 2))
    with pytest.raises(ValueError, match="async"):
        scenario.validate(4, SequentialScheduler(), make_variant("ascii"))


def test_clock_skew_async_runs(monkeypatch, capsys):
    run_cli(monkeypatch, ["--variant", "async", "--clock-skew", "0,0,1,2",
                          "--rounds", "1", "--n", "120"])
    assert "async,metered" in capsys.readouterr().out


# ------------------------------------------- newly-legal compiled CLI combos
def test_compiled_async_accepted(monkeypatch, capsys):
    """PR 9: --backend compiled --variant async (with a wire codec) runs —
    both rejections this combination used to hit are gone."""
    run_cli(monkeypatch, ["--variant", "async", "--backend", "compiled",
                          "--learner", "logistic", "--steps", "10",
                          "--rounds", "1", "--n", "120",
                          "--codec", "int8"])
    out = capsys.readouterr().out
    assert "async,metered,compiled" in out


def test_compiled_budget_aware_accepted(monkeypatch, capsys):
    run_cli(monkeypatch, ["--scheduler", "budget-aware", "--backend",
                          "compiled", "--learner", "logistic", "--steps",
                          "10", "--rounds", "1", "--n", "120",
                          "--byte-budget", "6000"])
    out = capsys.readouterr().out
    assert "compiled" in out and "budget: spent=" in out


def test_compiled_async_still_rejects_controller(monkeypatch, capsys):
    with pytest.raises(SystemExit) as exc:
        run_cli(monkeypatch, ["--variant", "async",
                              "--controller", "resid"])
    assert exc.value.code == 2
    assert "per barrier" in capsys.readouterr().err


# ------------------------------------------------------- backbone learner
@pytest.mark.parametrize("argv", [["--learner", "backbone"],
                                  ["--dataset", "notes"],
                                  ["--dataset", "notes", "--learner",
                                   "backbone"]],
                         ids=["no-notes", "no-backbone", "no-arch"])
def test_backbone_learner_needs_notes_and_arch(monkeypatch, capsys, argv):
    with pytest.raises(SystemExit) as exc:
        run_cli(monkeypatch, argv)
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "--learner backbone" in err


def test_backbone_learner_runs_compiled(monkeypatch, capsys):
    """A text agent (deepseek-v2-lite at its smoke-test size) beside a
    chart MLP, through the compiled backend."""
    run_cli(monkeypatch, ["--dataset", "notes", "--learner", "backbone",
                          "--arch", "deepseek-v2-lite", "--reduced",
                          "--steps", "2", "--rounds", "1", "--n", "64",
                          "--backend", "compiled"])
    line = capsys.readouterr().out.splitlines()[0]
    assert line.startswith("notes,ascii,metered,compiled,rounds=")

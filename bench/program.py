"""The system under test, built from a configuration file.

The only module of the benchmark that imports the program (``repro``):
learners, the wire and the session engine, configured as the configuration
states.
"""
from __future__ import annotations

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"


def ensure_importable() -> None:
    """Put the checkout's ``src`` on the path; fail where it is missing."""
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"bench: the program is not in this checkout "
                         f"({SRC / 'repro'} is missing)")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def learners(config: dict) -> list:
    spec = config["learner"]
    if spec["kind"] == "mlp":
        from repro.learners.mlp import MLP
        make = lambda: MLP(hidden=tuple(spec["hidden"]),  # noqa: E731
                           steps=int(spec["steps"]), lr=float(spec["lr"]))
    elif spec["kind"] == "logistic":
        from repro.learners.logistic import LogisticRegression
        make = lambda: LogisticRegression(  # noqa: E731
            steps=int(spec["steps"]), lr=float(spec["lr"]),
            l2=float(spec.get("l2", 1e-4)))
    else:
        raise ValueError(f"unknown learner kind {spec['kind']!r}")
    return [make() for _ in config["splits"]]


def transport(config: dict):
    """A metered transport with the configuration's wire: one codec, or an
    adaptive controller over a ladder."""
    from repro.comm import make_codec
    from repro.core.engine import MeteredTransport
    wire = config["wire"]
    ctrl = wire.get("controller")
    if ctrl is None:
        return MeteredTransport(codec=make_codec(wire["codec"]))
    from repro.control.adaptive import AdaptiveController
    controller = AdaptiveController(
        ladder=tuple(make_codec(c) for c in wire["ladder"]),
        thresholds=tuple(float(c) for c in ctrl["cuts"]),
        beta=float(ctrl["beta"]), stat=ctrl["stat"])
    return MeteredTransport(controller=controller)


def protocol(config: dict, telemetry=None):
    """A compiled-backend ``Protocol`` with a fresh metered transport."""
    from repro.core.engine import Protocol, SessionConfig
    cfg = SessionConfig(num_classes=int(config["num_classes"]),
                        max_rounds=int(config["rounds"]),
                        alpha_cap=float(config.get("alpha_cap", 20.0)))
    return Protocol(cfg, transport=transport(config), backend="compiled",
                    telemetry=telemetry)


def endpoints(config: dict, Xs):
    from repro.core.engine import endpoints_for
    return endpoints_for(learners(config), list(Xs))


def telemetry():
    """Registry and spans on the profiler's clock (traced runs only)."""
    from repro.telemetry import Telemetry
    return Telemetry(profile=True)

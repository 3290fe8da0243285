"""The spans, name scopes and trace counters that say where a session's time
goes.

A ``Protocol.fit`` is one ``fit`` span.  On the compiled backend its
children are ``plan`` (transport attach, scheduler bind, ``plan_for``),
``session`` (the fenced compiled call, with ``traced``: the programs traced
during it), ``extract`` (the fitted ensemble and the agent-major view, with
``dispatches``: the device programs it launched) and
``replay`` (the ledger, with ``dispatches``: the one row-split program
that cuts every booked payload); on the eager backend ``session`` ->
``round`` -> ``hop``.  In the compiled round body the model weight and
reweight carry an ``ascii_update_<j>`` scope and the wire channel an
``ascii_channel_<j>`` scope, siblings of the hop's ``ascii_hop_<j>``.
``TRACE_COUNTS`` counts the traces of every program family.
"""
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import make_codec
from repro.comm.codecs import Fp16Codec, QuantCodec
from repro.control import AdaptiveController
from repro.core import compiled
from repro.core.engine import (MeteredTransport, Protocol, SessionConfig,
                               endpoints_for)
from repro.data.partition import train_test_split, vertical_split
from repro.data.synthetic import blob_fig3
from repro.learners.logistic import LogisticRegression
from repro.telemetry import Telemetry

ROOT = Path(__file__).resolve().parents[1]
COMPILED_CHILDREN = ("plan", "session", "extract", "replay")


@pytest.fixture(scope="module")
def blob():
    ds = blob_fig3(jax.random.key(0), n=240)
    tr, _ = train_test_split(0, 240)
    Xs = vertical_split(ds.X, ds.splits)
    return [x[tr] for x in Xs], ds.classes[tr], ds.num_classes


def _controller():
    return AdaptiveController(ladder=(Fp16Codec(), QuantCodec(bits=4)),
                              thresholds=(0.5,), beta=0.0)


def _fit(blob, backend, telemetry, key=7):
    Xtr, ctr, k = blob
    transport = MeteredTransport(controller=_controller())
    proto = Protocol(SessionConfig(num_classes=k, max_rounds=3),
                     transport=transport, backend=backend,
                     telemetry=telemetry)
    eps = endpoints_for([LogisticRegression(steps=20) for _ in Xtr], Xtr)
    fitted = proto.fit(jax.random.key(key), eps, ctr)
    return fitted, transport


def _children(spans, parent):
    return [s for s in spans if s.parent_id == parent.span_id]


def _inside(child, parent):
    return parent.start_s <= child.start_s <= child.end_s <= parent.end_s


def test_compiled_fit_span_tree(blob):
    tele = Telemetry()
    fitted, transport = _fit(blob, "compiled", tele)
    spans = tele.tracer.spans
    assert tele.tracer.well_formed()
    roots = [s for s in spans if s.parent_id is None]
    assert [s.name for s in roots] == ["fit"]
    fit = roots[0]
    kids = _children(spans, fit)
    assert tuple(s.name for s in kids) == COMPILED_CHILDREN
    for s in kids:
        assert _inside(s, fit), s.name
    for a, b in zip(kids, kids[1:]):
        assert a.end_s <= b.start_s
    by = {s.name: s for s in kids}
    assert by["session"].attrs["backend"] == "compiled"
    assert by["session"].attrs["agents"] == len(blob[0])
    assert by["extract"].attrs["components"] == len(fitted.components)
    # a logistic component's parameters are two leaves (w, b)
    assert by["extract"].attrs["leaves"] == 2 * len(fitted.components)
    assert by["extract"].attrs["dispatches"] == 1
    assert by["replay"].attrs["messages"] == len(transport.log.entries)
    assert by["replay"].attrs["dispatches"] == 1


def test_eager_fit_span_tree(blob):
    tele = Telemetry()
    _fit(blob, "eager", tele)
    spans = tele.tracer.spans
    by_id = {s.span_id: s for s in spans}
    roots = [s for s in spans if s.parent_id is None]
    assert [s.name for s in roots] == ["fit"]
    sessions = _children(spans, roots[0])
    assert [s.name for s in sessions] == ["session"]
    rounds = _children(spans, sessions[0])
    assert rounds and {s.name for s in rounds} == {"round"}
    hops = [s for s in spans if s.name == "hop"]
    assert hops
    for s in rounds + hops:
        assert _inside(s, by_id[s.parent_id])
    for s in hops:
        assert by_id[s.parent_id].name == "round"


def test_session_span_counts_traces(blob):
    compiled._session_program.cache_clear()
    tele = Telemetry()
    _fit(blob, "compiled", tele, key=1)
    _fit(blob, "compiled", tele, key=2)
    traced = [s.attrs["traced"] for s in tele.tracer.spans
              if s.name == "session"]
    assert traced[0] >= 1 and traced[1] == 0


def test_extract_is_one_program_traced_once(blob):
    """Every fit of one configuration builds its ensemble with one launch
    of one extraction program, traced by the first fit only."""
    compiled._extract_program.cache_clear()
    compiled.TRACE_COUNTS.clear()
    tele = Telemetry()
    fits = [_fit(blob, "compiled", tele, key=k)[0] for k in (1, 2)]
    assert compiled.TRACE_COUNTS["extract"] == 1
    spans = [s for s in tele.tracer.spans if s.name == "extract"]
    assert [s.attrs["dispatches"] for s in spans] == [1, 1]
    assert [s.attrs["leaves"] for s in spans] == \
           [2 * len(f.components) for f in fits]


def test_replay_is_one_program_traced_once(blob):
    """Every fit of one configuration cuts the payloads its replay books
    with one launch of one row-split program, traced by the first fit
    only."""
    compiled._split_program.cache_clear()
    compiled.TRACE_COUNTS.clear()
    tele = Telemetry()
    for k in (1, 2):
        _fit(blob, "compiled", tele, key=k)
    assert compiled.TRACE_COUNTS["replay"] == 1
    spans = [s for s in tele.tracer.spans if s.name == "replay"]
    assert [s.attrs["dispatches"] for s in spans] == [1, 1]


def test_session_program_scopes_update_and_channel(blob):
    Xtr, ctr, k = blob
    plan = compiled.plan_for([LogisticRegression(steps=5) for _ in Xtr], k,
                             max_rounds=2, controller=_controller())
    Xs = tuple(Xtr)
    shapes = tuple(x.shape[1:] for x in Xs)
    hlo = jax.jit(compiled.make_session_fn(plan, shapes)).lower(
        jax.random.key(0), Xs, ctr).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    sys.path.insert(0, str(ROOT))
    from bench.trace import SCOPE
    for scope in ("ascii_update_0", "ascii_channel_0"):
        under = [n for n in names if f"/{scope}/" in n]
        assert under, scope
        assert not SCOPE.fullmatch(scope)
        # siblings of the hop's scope, never inside it
        assert not any(re.search(r"ascii_hop_\d+/", n) for n in under)
        assert not any(SCOPE.search(n) for n in under)
    assert any("/ascii_hop_0/" in n for n in names)


def test_scopes_keep_values(blob, monkeypatch):
    """Name scopes are metadata: the session program traced without any
    of them gives bit for bit what the scoped program gives."""
    from contextlib import nullcontext
    Xtr, ctr, k = blob
    plan = compiled.plan_for([LogisticRegression(steps=5) for _ in Xtr], k,
                             max_rounds=2, controller=_controller())
    shapes = tuple(x.shape[1:] for x in Xtr)
    args = (jax.random.key(3), tuple(Xtr), ctr)
    scoped = jax.jit(compiled.make_session_fn(plan, shapes))(*args)
    monkeypatch.setattr(jax, "named_scope", lambda name: nullcontext())
    bare = jax.jit(compiled.make_session_fn(plan, shapes))(*args)
    for a, b in zip(jax.tree.leaves(scoped), jax.tree.leaves(bare)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _family_runs(blob):
    """Each program family with the cache its factory keeps and a call that
    runs it once."""
    Xtr, ctr, k = blob
    lrs = [LogisticRegression(steps=5) for _ in Xtr]
    plain = compiled.plan_for(lrs, k, max_rounds=2)
    quant = compiled.plan_for(lrs, k, max_rounds=2, codec=make_codec("int8"))
    ctrl = compiled.plan_for(lrs, k, max_rounds=2, controller=_controller())
    stale = compiled.plan_for(lrs, k, max_rounds=2,
                              scheduler=compiled.AsyncStalePlan())
    key = jax.random.key(0)
    keys = jnp.stack([key, key])
    num = plain.num_agents
    big = np.iinfo(np.int32).max

    def result():
        return compiled.compiled_session(plain, key, Xtr, ctr)

    def slots(res):
        return [{"key": key, "Xs": tuple(Xtr), "params": res.params,
                 "alphas": res.alphas, "valid": res.valid,
                 "rem_session": jnp.asarray(big, jnp.int32),
                 "rem_link": jnp.asarray([big] * num, jnp.int32),
                 "deliver": np.ones(num, bool)}]

    return {
        "session": (compiled._session_program, None,
                    lambda _: compiled.compiled_session(plain, key, Xtr,
                                                        ctr)),
        "async_session": (compiled._async_session_program, None,
                          lambda _: compiled.async_session(stale, key, Xtr,
                                                           ctr)),
        "serve": (compiled._serve_program, result,
                  lambda res: compiled.serve_session(plain, res, None, Xtr)),
        "serve_batch": (compiled._serve_batch_program, result,
                        lambda res: compiled.serve_batch(plain, slots(res))),
        "fleet": (compiled._fleet_program, None,
                  lambda _: compiled.fleet_run(plain, keys, Xtr, ctr)),
        "sweep": (compiled._sweep_program, None,
                  lambda _: compiled.quant_sweep_run(
                      quant, keys, Xtr, ctr, jnp.asarray([127.0, 7.0]))),
        "sweep_serve": (compiled._sweep_serve_program, None,
                        lambda _: compiled.quant_sweep_run(
                            quant, keys, Xtr, ctr, jnp.asarray([127.0, 7.0]),
                            serve_Xs=Xtr)),
        "control_sweep": (compiled._control_sweep_program, None,
                          lambda _: compiled.control_sweep_run(
                              ctrl, keys, Xtr, ctr,
                              betas=[0.0, 0.5])),
        "extract": (compiled._extract_program, result,
                    lambda res: compiled.extract_params(res.params)),
        "replay": (compiled._split_program, result,
                   lambda res: compiled.split_rows(res.w_trace)),
    }


FAMILIES = ("session", "async_session", "serve", "serve_batch", "fleet",
            "sweep", "sweep_serve", "control_sweep", "extract", "replay")


@pytest.mark.parametrize("family", FAMILIES)
def test_trace_counts_once_per_trace(blob, family):
    cache, prepare, call = _family_runs(blob)[family]
    arg = prepare() if prepare is not None else None
    cache.cache_clear()
    compiled.TRACE_COUNTS.clear()
    jax.block_until_ready(call(arg))
    jax.block_until_ready(call(arg))
    assert compiled.TRACE_COUNTS == {family: 1}


def test_trace_counts_export_as_gauges(blob):
    tele = Telemetry()
    _, transport = _fit(blob, "compiled", tele)
    tele.sync_gauges(transport)
    assert compiled.TRACE_COUNTS
    for family, count in compiled.TRACE_COUNTS.items():
        assert tele.registry.gauge("program_traces",
                                   program=family) == count
    assert "program_traces" not in tele.registry.counter_names()


@pytest.mark.parametrize("backend", ["eager", "compiled"])
def test_profiled_spans_open_trace_annotations(blob, backend, monkeypatch):
    opened = []

    class Recorder:
        def __init__(self, name, **_):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Recorder)
    tele = Telemetry(profile=True)
    _fit(blob, backend, tele)
    assert opened == [s.name for s in tele.tracer.spans]
    if backend == "compiled":
        assert opened == ["fit", *COMPILED_CHILDREN]
    else:
        assert opened[:2] == ["fit", "session"]

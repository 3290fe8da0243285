"""Learner interface: the model class F_0^(m) an agent brings to ASCII.

Every learner implements weighted supervised training (Algorithm 2 / WST):
``fit(key, X, classes, w) -> params`` minimizing the w-weighted training
loss, plus ``predict(params, X) -> class indices``.  Learners are stateless
objects; fitted parameters are plain pytrees so they jit/vmap/shard cleanly.

Per Prop. 1, minimizing the weighted exponential loss over F_0 is equivalent
to minimizing the w-weighted 0/1 classification error; trees do this
directly, while differentiable learners (logistic / MLP / neural backbones)
use the w-weighted cross-entropy as the standard smooth surrogate — the same
choice as the paper's own neural-network experiments (Section VI-B).
"""
from __future__ import annotations

import abc
import functools
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any


@functools.lru_cache(maxsize=256)
def jitted_fresh_fit(core: "LearnerCore", shapes: tuple):
    """Cached jit of the fresh-fit composition ``fit(init(key), key, ...)``
    (cores are hashable frozen dataclasses, so they key the cache).

    Eager ``Learner.fit`` wrappers route through this so the eager engine
    runs the exact XLA program the compiled session scan embeds — init and
    fit traced together — which, not luck, is what keeps the two backends
    bit-identical (op-by-op dispatch fuses differently at the last ulp)."""

    def fresh(key, X, onehot, w):
        return core.fit(core.init(key, shapes), key, X, onehot, w)

    return jax.jit(fresh)


class LearnerCore(abc.ABC):
    """Pure functional learner contract — the compilable half of a Learner.

    A core is a *static* (hashable, frozen-dataclass) bundle of pure
    functions over fixed-shape pytree params, so a whole ASCII session can
    be lowered into one ``lax.scan`` program (``core/compiled.py``) and
    vmapped across session fleets:

      * ``init(key, shapes) -> params``    — fresh params for feature shape
        ``shapes`` (e.g. ``(p,)``), fixed pytree structure.
      * ``fit(params, key, X, onehot, w) -> params`` — Algorithm 2 / WST:
        minimize the w-weighted loss starting from ``params``.
      * ``logits(params, X) -> [n, K]``    — class scores.
      * ``predict(params, X) -> [n]``      — argmax of ``logits``.

    Key discipline: ``init`` and ``fit`` both receive the *same* per-fit
    key and derive any sub-keys internally, such that

        core.fit(core.init(key, X.shape[1:]), key, X, onehot, w)

    reproduces the matching eager ``Learner.fit(key, X, classes, w, K)``
    bit for bit — that identity is what makes the compiled engine backend
    a drop-in for the eager one (tests/test_compiled.py).
    """

    #: Where set, a compiled session counts the traces of this core's hops
    #: in ``repro.core.compiled.TRACE_COUNTS`` as ``<trace_family>_fit``
    #: and ``<trace_family>_predict``.
    trace_family: str | None = None

    @abc.abstractmethod
    def init(self, key, shapes: tuple[int, ...]) -> PyTree:
        """Fresh fixed-shape params for feature shape ``shapes``."""

    @abc.abstractmethod
    def fit(self, params: PyTree, key, X: jnp.ndarray, onehot: jnp.ndarray,
            w: jnp.ndarray) -> PyTree:
        """Weighted supervised training from ``params`` (Algorithm 2)."""

    @abc.abstractmethod
    def logits(self, params: PyTree, X: jnp.ndarray) -> jnp.ndarray:
        """Class scores, shape [n, K]."""

    def predict(self, params: PyTree, X: jnp.ndarray) -> jnp.ndarray:
        return jnp.argmax(self.logits(params, X), axis=-1)

    def fit_counted(self, params: PyTree, key, X: jnp.ndarray,
                    onehot: jnp.ndarray, w: jnp.ndarray):
        """``fit`` and the work it did, a dict of int32 scalars (tokens
        processed, tokens routed to held experts; ``{}`` where the core
        counts nothing).  The compiled session sums them per hop."""
        return self.fit(params, key, X, onehot, w), {}

    def predict_counted(self, params: PyTree, X: jnp.ndarray):
        """``predict`` and the work it did, as in :meth:`fit_counted`."""
        return self.predict(params, X), {}


class Learner(abc.ABC):
    """A private model class F_0 held by a single agent."""

    #: Adapter flag: True when :meth:`core` returns a functional
    #: LearnerCore, i.e. the learner can ride the compiled engine backend.
    #: Eager-only learners (decision tree / random forest, whose fits are
    #: argmin/argmax programs rather than fixed-shape differentiable
    #: updates) keep the default False and stay on the eager path.
    functional = False

    @abc.abstractmethod
    def fit(self, key, X: jnp.ndarray, classes: jnp.ndarray,
            w: jnp.ndarray, num_classes: int) -> PyTree:
        """Weighted supervised training (Algorithm 2, line 1)."""

    @abc.abstractmethod
    def predict(self, params: PyTree, X: jnp.ndarray) -> jnp.ndarray:
        """Hard class predictions, shape [n]."""

    def core(self, num_classes: int) -> LearnerCore | None:
        """The pure functional core of this learner, or None when the
        learner is eager-only (``functional = False``)."""
        return None

    def reward(self, params: PyTree, X: jnp.ndarray,
               classes: jnp.ndarray) -> jnp.ndarray:
        """Prop. 1 reward r_i = I{g(x_i) = y_i} (Algorithm 2, line 2)."""
        return (self.predict(params, X) == classes).astype(jnp.float32)

    def endpoint(self, agent_id: int, X: jnp.ndarray, name: str = ""):
        """Wrap this learner + its private feature block as a protocol
        AgentEndpoint (see repro.core.engine)."""
        from repro.core.engine import AgentEndpoint
        return AgentEndpoint(agent_id, self, X, name=name)

"""Neural-backbone ASCII agent: any assigned architecture, read through the
sequence classifier head (``models/classifier.py``), as a Learner over
token sequences.  The agent's private block ``X`` is int32 token ids
``[n, L]``: a text modality over the same subjects as the other agents'
feature blocks.

The fit (Algorithm 2) is a minibatched weighted fit: each of ``steps``
AdamW steps draws ``batch_size`` rows i.i.d. from ``categorical(log w)``,
so the rows the ignorance vector has gathered on are the rows fitted, and
minimizes the mean cross-entropy over the draw, an unbiased estimate of
the w-weighted loss.  ``logits`` runs the backbone over the rows in blocks
of ``predict_block``, so that scoring all n rows fits in memory.

Key discipline (the :class:`~repro.learners.base.LearnerCore` contract):
``init`` takes ``split(key)[1]`` and ``fit`` draws step ``i``'s rows from
``fold_in(split(key)[0], i)``, so the eager and compiled backends see the
same weights and the same rows.

Under the ``ascii_hop_<j>`` scope the draw and gather run under
``backbone_batch`` and the blocked forward under ``backbone_predict``; the
backbone's own scopes (``backbone_attn``, ``backbone_router``, ...) nest
inside.  ``fit_counted``/``predict_counted`` also return the tokens
processed (``tokens_fit``, ``tokens_predict``) and the (token, choice)
pairs routed to held experts (``expert_tokens_fit``,
``expert_tokens_predict``); a compiled session counts their traces as
``backbone_fit`` and ``backbone_predict`` (``trace_family``).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.learners.base import Learner, LearnerCore, jitted_fresh_fit
from repro.models import classifier
from repro.optim.optimizers import adamw


@dataclass(frozen=True)
class NeuralCore(LearnerCore):
    num_classes: int
    cfg: ArchConfig = None
    steps: int = 32
    lr: float = 1e-3
    batch_size: int = 16
    predict_block: int = 16

    trace_family = "backbone"

    def init(self, key, shapes):
        _, init_key = jax.random.split(key)
        return classifier.init_params(init_key, self.cfg, self.num_classes)

    def fit_counted(self, params, key, X, onehot, w):
        batch_key, _ = jax.random.split(key)
        cfg, bs = self.cfg, self.batch_size
        opt = adamw(self.lr)
        log_w = jnp.log(w.astype(jnp.float32))

        def loss_fn(p, xb, ob):
            logits, count = classifier.apply_counted(p, {"tokens": xb}, cfg)
            ll = jnp.sum(ob * logits, -1) - jax.nn.logsumexp(logits, -1)
            return -jnp.mean(ll), count

        def body(i, carry):
            p, state, count = carry
            with jax.named_scope("backbone_batch"):
                rows = jax.random.categorical(
                    jax.random.fold_in(batch_key, i), log_w, shape=(bs,))
                xb, ob = X[rows], onehot[rows]
            grads, c = jax.grad(loss_fn, has_aux=True)(p, xb, ob)
            p, state = opt.update(grads, state, p, i)
            return p, state, count + c

        params, _, count = jax.lax.fori_loop(
            0, self.steps, body,
            (params, opt.init(params), jnp.zeros((), jnp.int32)))
        tokens = jnp.asarray(self.steps * bs * X.shape[1], jnp.int32)
        return params, {"tokens_fit": tokens, "expert_tokens_fit": count}

    def fit(self, params, key, X, onehot, w):
        return self.fit_counted(params, key, X, onehot, w)[0]

    def logits_counted(self, params, X):
        n, length = X.shape
        bs = math.gcd(n, self.predict_block)

        def block(xb):
            return classifier.apply_counted(params, {"tokens": xb}, self.cfg)

        with jax.named_scope("backbone_predict"):
            logits, counts = jax.lax.map(block, X.reshape(n // bs, bs, length))
        return logits.reshape(n, -1), jnp.sum(counts)

    def logits(self, params, X):
        return self.logits_counted(params, X)[0]

    def predict_counted(self, params, X):
        logits, count = self.logits_counted(params, X)
        tokens = jnp.asarray(X.shape[0] * X.shape[1], jnp.int32)
        return (jnp.argmax(logits, axis=-1),
                {"tokens_predict": tokens, "expert_tokens_predict": count})


@dataclass(frozen=True)
class NeuralBackbone(Learner):
    cfg: ArchConfig = None
    steps: int = 32
    lr: float = 1e-3
    batch_size: int = 16
    predict_block: int = 16

    functional = True

    def core(self, num_classes: int) -> NeuralCore:
        return NeuralCore(num_classes, self.cfg, self.steps, self.lr,
                          self.batch_size, self.predict_block)

    def fit(self, key, X, classes, w, num_classes):
        core = self.core(num_classes)
        onehot = jax.nn.one_hot(classes, num_classes)
        return jitted_fresh_fit(core, X.shape[1:])(key, X, onehot, w)

    def predict(self, params, X):
        core = self.core(params["cls_head"]["w"].shape[-1])
        return _predict(core, params, X)


@functools.partial(jax.jit, static_argnums=0)
def _predict(core: NeuralCore, params, X):
    return core.predict(params, X)

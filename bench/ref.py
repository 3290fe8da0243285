"""Plain reference of the ASCII protocol, for deciding ``correct``.

Written from the paper's equations and the wire format, in straightforward
``jax.numpy``: one jitted weighted fit per hop, the model weight (eq. 13),
the ignorance update (eqs. 10/12), the adaptive rung rule and the codec
round trip.  It imports nothing of the program and takes nothing it made:
data and keys are its only inputs, so its weights are its own.

``dtype`` is the precision of the whole computation (data, parameters,
optimizer state, the ignorance vector and the model weights): float32 with
every matrix product at ``HIGHEST`` precision for the reference, bfloat16
for the control.  The codecs encode a float32 vector in both, as the wire
format defines.

PRNG use follows the system's documented key discipline, so the same key
gives the same initial weights and rounding draws:

- per hop ``key, sub = split(key)``; the MLP's initial weights come from
  ``split(sub)[1]``;
- the wire's draws from ``fold_in(fold_in(sub, COMM_FOLD), CODEC_FOLD)``.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

COMM_FOLD = 0x434F4D
CODEC_FOLD = 1
EPS = 1e-12
BN = 1024


# ===================================================================== wire
def tile_len(n: int, bn: int = BN) -> int:
    """Elements per scale tile: ``bn`` when it divides n, else all of n."""
    return bn if (n >= bn and n % bn == 0) else n


def codec_bits(codec: str, n: int) -> int:
    """Encoded size of a length-n vector: the values, plus one f32 scale
    per tile for the integer codecs (int4 packs two values a byte)."""
    if codec == "fp32":
        return 32 * n
    if codec == "fp16":
        return 16 * n
    payload = 8 * ((n + 1) // 2) if codec == "int4" else 8 * n
    return payload + 32 * (n // tile_len(n))


def roundtrip(codec: str, x, key):
    """What the receiver decodes from the vector ``x`` sent through
    ``codec``: stochastic rounding to a symmetric per-tile integer grid
    for the integer codecs."""
    if codec == "fp32":
        return x
    if codec == "fp16":
        return x.astype(jnp.float16).astype(jnp.float32)
    qmax = {"int8": 127.0, "int4": 7.0}[codec]
    u = jax.random.uniform(key, x.shape, jnp.float32)
    bn = tile_len(x.shape[0])
    xt, ut = x.reshape(-1, bn), u.reshape(-1, bn)
    scale = jnp.maximum(jnp.max(jnp.abs(xt), axis=1), EPS) / qmax
    q = jnp.clip(jnp.floor(xt / scale[:, None] + ut), -qmax, qmax)
    return (q * scale[:, None]).reshape(x.shape)


def wire_key(sub):
    return jax.random.fold_in(jax.random.fold_in(sub, COMM_FOLD), CODEC_FOLD)


# ================================================================= learners
def _adam_fit(loss, params, steps: int, lr: float):
    """``steps`` full-batch AdamW steps (b1 0.9, b2 0.999, eps 1e-8, no
    weight decay), in the parameters' own dtype."""
    grad = jax.grad(loss)
    zeros = jax.tree.map(jnp.zeros_like, params)

    def body(i, carry):
        p, m, v = carry
        g = grad(p)
        t = i.astype(jnp.float32) + 1.0
        m = jax.tree.map(lambda m_, g_: 0.9 * m_ + 0.1 * g_, m, g)
        v = jax.tree.map(lambda v_, g_: 0.999 * v_ + 0.001 * g_ * g_, v, g)
        bc1, bc2 = 1.0 - 0.9 ** t, 1.0 - 0.999 ** t

        def leaf(p_, m_, v_):
            upd = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + 1e-8)
            return (p_ - lr * upd).astype(p_.dtype)

        return jax.tree.map(leaf, p, m, v), m, v

    p, _, _ = jax.lax.fori_loop(0, steps, body, (params, zeros, zeros))
    return p


@dataclass(frozen=True)
class Learner:
    """One agent's model class: ``kind`` ``mlp`` (ReLU hidden layers,
    He-normal init) or ``logistic`` (zero init, L2 penalty)."""
    kind: str
    num_classes: int
    steps: int
    lr: float
    hidden: tuple = ()
    l2: float = 1e-4
    dtype: str = "float32"

    @property
    def precision(self):
        return (jax.lax.Precision.HIGHEST if self.dtype == "float32"
                else jax.lax.Precision.DEFAULT)

    def init(self, key, p: int):
        dt = jnp.dtype(self.dtype)
        if self.kind == "logistic":
            return {"w": jnp.zeros((p, self.num_classes), dt),
                    "b": jnp.zeros((self.num_classes,), dt)}
        dims = (p,) + tuple(self.hidden) + (self.num_classes,)
        _, k = jax.random.split(key)
        params = []
        for d_in, d_out in zip(dims[:-1], dims[1:]):
            k, sub = jax.random.split(k)
            w = jax.random.normal(sub, (d_in, d_out)) * jnp.sqrt(2.0 / d_in)
            params.append({"w": w.astype(dt), "b": jnp.zeros((d_out,), dt)})
        return params

    def logits(self, params, X):
        mm = functools.partial(jnp.matmul, precision=self.precision)
        if self.kind == "logistic":
            return mm(X, params["w"]) + params["b"]
        h = X
        for layer in params[:-1]:
            h = jax.nn.relu(mm(h, layer["w"]) + layer["b"])
        return mm(h, params[-1]["w"]) + params[-1]["b"]

    def loss(self, params, X, onehot, w):
        """The w-weighted cross-entropy (plus the logistic L2 penalty)."""
        z = self.logits(params, X).astype(jnp.float32)
        ll = jnp.sum(onehot * z, axis=-1) - jax.nn.logsumexp(z, axis=-1)
        out = -jnp.sum(w * ll) / jnp.maximum(jnp.sum(w), EPS)
        if self.kind == "logistic":
            out = out + self.l2 * jnp.sum(
                jnp.square(params["w"].astype(jnp.float32)))
        return out

    @functools.partial(jax.jit, static_argnums=0)
    def fit(self, key, X, onehot, w):
        X = X.astype(self.dtype)
        params = self.init(key, X.shape[1])
        return _adam_fit(lambda p: self.loss(p, X, onehot, w), params,
                         self.steps, self.lr)

    @functools.partial(jax.jit, static_argnums=0)
    def eval_loss(self, params, X, onehot, w):
        """The loss of ``params`` (any dtype) in this learner's precision."""
        params = jax.tree.map(lambda p: p.astype(self.dtype), params)
        return self.loss(params, X.astype(self.dtype), onehot, w)

    @functools.partial(jax.jit, static_argnums=0)
    def predict(self, params, X):
        return jnp.argmax(self.logits(params, X.astype(self.dtype)), axis=-1)


def learner_from(config: dict, dtype: str = "float32") -> Learner:
    spec = config["learner"]
    return Learner(kind=spec["kind"], num_classes=int(config["num_classes"]),
                   steps=int(spec["steps"]), lr=float(spec["lr"]),
                   hidden=tuple(spec.get("hidden", ())),
                   l2=float(spec.get("l2", 1e-4)), dtype=dtype)


# ================================================================== session
@dataclass
class Session:
    """What one reference session produced."""
    components: list = field(default_factory=list)  # (agent, round, a, params)
    ledger: list = field(default_factory=list)      # (kind, bits)


def _tv(a, b):
    a = a / jnp.maximum(jnp.sum(a), EPS)
    b = b / jnp.maximum(jnp.sum(b), EPS)
    return 0.5 * jnp.sum(jnp.abs(a - b))


def session(key, Xs, classes, config: dict, dtype: str = "float32"
            ) -> Session:
    """Algorithm 1 over the agents' feature blocks ``Xs`` in chain order,
    for ``config['rounds']`` rounds, with upstream side information, the
    alpha <= 0 stop, and the configuration's wire."""
    lr = learner_from(config, dtype)
    dt = jnp.dtype(dtype)
    k = int(config["num_classes"])
    cap = float(config.get("alpha_cap", 20.0))
    wire = config["wire"]
    ladder = wire.get("ladder") or [wire["codec"]]
    ctrl = wire.get("controller")
    n = int(classes.shape[0])
    onehot = jax.nn.one_hot(classes, k, dtype=jnp.float32)
    w = jnp.full((n,), 1.0 / n, dt)
    ema = jnp.asarray(1.0, dt)
    out = Session()
    for _ in Xs[1:]:
        out.ledger += [("labels", 32 * n), ("sample_ids", 32 * n)]
    for t in range(int(config["rounds"])):
        u = jnp.ones((n,), dt)
        for j, X in enumerate(Xs):
            key, sub = jax.random.split(key)
            params = lr.fit(sub, X, onehot, w.astype(jnp.float32))
            r = (lr.predict(params, X) == classes).astype(dt)
            s_c, s_w = jnp.sum(w * u * r), jnp.sum(w * u * (1 - r))
            alpha = (jnp.log(jnp.maximum(s_c, EPS))
                     - jnp.log(jnp.maximum(s_w, EPS)) + math.log(k - 1))
            alpha = float(jnp.clip(alpha, -cap, cap))
            if alpha <= 0:
                return out
            out.components.append((j, t, alpha, params))
            a = jnp.asarray(alpha, dt)
            u = u * jnp.where(r > 0, jnp.exp(-a / (k - 1)),
                              jnp.exp(a / (k - 1) ** 2))
            w_upd = w * jnp.exp(a * (1 - r))
            w_upd = w_upd / jnp.maximum(jnp.sum(w_upd), EPS)
            rung = 0
            if ctrl is not None:
                beta = float(ctrl["beta"])
                ema = beta * ema + (1.0 - beta) * _tv(w_upd, w)
                rung = int(jnp.sum(ema < jnp.asarray(ctrl["cuts"], dt)))
            codec = ladder[rung]
            w = roundtrip(codec, w_upd.astype(jnp.float32),
                          wire_key(sub)).astype(dt)
            out.ledger += [("ignorance", codec_bits(codec, n)),
                           ("model_weight", 32)]
    return out


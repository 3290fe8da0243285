"""Plain reference of a backbone configuration's session, for deciding
``correct``: Algorithm 1's round 0 with a DeepSeek-V2 text agent.

Written from the published equations in straightforward ``jax.numpy``; it
imports nothing of the program.  Layer l on x in R^{L x d}:

    h   = x + MLA(rms(x));   out = h + FFN_l(rms(h))
    MLA: q = u W_q -> [H, nope | rope];  [c | k_pe] = u W_kva;  c = rms(c)
         k_nope = c W_kb,  v = c W_vb;  rope (YaRN frequencies) on q_pe and
         the shared k_pe;  causal softmax at (nope + rope)^-1/2 *
         mscale(f, mscale_all_dim)^2;  out = softmax . v W_o
    FFN_0 = SwiGLU of width d_ff;  for l >= 1
    FFN_l(u) = sum over e in top-k(s) held here of s_e E_e(u)  +  S(u),
         s = softmax(u W_g) over every routed expert in float32, not
         renormalized; E_e and S SwiGLUs (S of width shared * moe width)
    classifier: final rms, the mean over positions, [d, K]

The experts held here are computed densely for every token and masked by
the routing (no sort, no grouped matmul); attention is the expanded form.
Departures from the published model, as in the configuration's file: rope
rotates the halves of the rope slice (a fixed permutation of interleaved
pairs), and the router's balance term is not in the loss.

The fit is Algorithm 2 as a minibatched weighted fit: step i draws B rows
from ``categorical(fold_in(split(sub)[0], i), log w)`` and takes an AdamW
step (b1 0.9, b2 0.999, eps 1e-8, no decay) on the mean cross-entropy of
the draw, its gradient summed over blocks of rows so that it fits.

Parameters follow the system's documented key discipline, so the same key
gives the same initial weights: ``init`` takes ``split(sub)[1] -> (k1, k2)``
with the head from ``k2`` and from ``k1``: ``embed, layers, _ =
split(k1, 3)``; MoE layer i from ``split(layers, units)[i]``, the dense
layers from ``split(fold_in(layers, 1), k_dense)``; in a layer, ``ks =
split(split(key, 1)[0], 4)``, attention from ``ks[0]`` (``split(., 6)``:
W_q 0, W_kva 2, W_kb 3, W_vb 4, W_o 5), the FFN from ``ks[1]`` (dense:
``split(., 3)``; MoE: ``split(., 4)`` router, gate, up, down, and the
shared SwiGLU from ``split(fold_in(., 1), 3)``).  He-normal weights, the
embedding at 0.02, norms at 1.

``dtype`` is the precision of everything: float32 with every product at
``HIGHEST`` for the reference, bfloat16 at default precision for the
control.  The round's other agents and the wire come from ``bench/ref.py``.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from bench import ref

FIT_BLOCK = 4          # rows a gradient block of the reference fit holds
FORWARD_BLOCK = 8      # rows a forward block holds


# ==================================================================== shape
@dataclass(frozen=True)
class Shape:
    """The widths and counts of one backbone, from a configuration file."""
    d: int
    layers: int
    dense_layers: int
    heads: int
    nope: int
    rope: int
    dv: int
    rank: int
    d_ff: int
    moe_ff: int
    experts: int           # router outputs
    held: int              # experts held here, 0 .. held - 1
    top_k: int
    shared: int
    vocab: int
    eps: float
    theta: float
    factor: float
    mscale: float
    mscale_all: float
    original: int
    beta_fast: float
    beta_slow: float
    classes: int


def shape_from(config: dict) -> Shape:
    rs = config["rope_scaling"]
    return Shape(
        d=int(config["hidden_size"]), layers=int(config["num_hidden_layers"]),
        dense_layers=int(config["first_k_dense_replace"]),
        heads=int(config["num_attention_heads"]),
        nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]), dv=int(config["v_head_dim"]),
        rank=int(config["kv_lora_rank"]), d_ff=int(config["intermediate_size"]),
        moe_ff=int(config["moe_intermediate_size"]),
        experts=int(config["router_experts"]),
        held=int(config["n_routed_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        shared=int(config["n_shared_experts"]),
        vocab=int(config["vocab_size"]), eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]), factor=float(rs["factor"]),
        mscale=float(rs["mscale"]), mscale_all=float(rs["mscale_all_dim"]),
        original=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        classes=int(config["num_classes"]))


def yarn_scale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(s: Shape):
    """theta^(-2i/D) blended with the same over ``factor`` by a linear ramp
    between the correction dimensions of beta_fast and beta_slow."""
    dim = s.rope

    def correction(rotations):
        return (dim * math.log(s.original / (rotations * 2 * math.pi))
                / (2 * math.log(s.theta)))

    low = max(math.floor(correction(s.beta_fast)), 0)
    high = min(math.ceil(correction(s.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (s.theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                               / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (extra / s.factor) * ramp + extra * (1.0 - ramp)


# ===================================================================== init
def _he(key, shape, fan_in, dt):
    return (jax.random.normal(key, shape) * (2.0 / fan_in) ** 0.5).astype(dt)


def _swiglu_init(key, d, f, dt):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"wi_gate": _he(k1, (d, f), d, dt), "wi_up": _he(k2, (d, f), d, dt),
            "wo": _he(k3, (f, d), f, dt)}


def _layer_init(key, s: Shape, dense: bool, dt):
    ks = jax.random.split(jax.random.split(key, 1)[0], 4)
    ka = jax.random.split(ks[0], 6)
    h, qd = s.heads, s.nope + s.rope
    attn = {"wq": _he(ka[0], (s.d, h * qd), s.d, dt),
            "wkv_a": _he(ka[2], (s.d, s.rank + s.rope), s.d, dt),
            "kv_a_norm": {"scale": jnp.ones((s.rank,), dt)},
            "wk_b": _he(ka[3], (s.rank, h * s.nope), s.rank, dt),
            "wv_b": _he(ka[4], (s.rank, h * s.dv), s.rank, dt),
            "wo": _he(ka[5], (h * s.dv, s.d), h * s.dv, dt)}
    p = {"ln1": {"scale": jnp.ones((s.d,), dt)}, "attn": attn,
         "ln2": {"scale": jnp.ones((s.d,), dt)}}
    if dense:
        p["mlp"] = _swiglu_init(ks[1], s.d, s.d_ff, dt)
        return {"sub0": p}
    km = jax.random.split(ks[1], 4)
    e, f = s.held, s.moe_ff
    p["moe"] = {"router": _he(km[0], (s.d, s.experts), s.d, dt),
                "wi_gate": _he(km[1], (e, s.d, f), s.d, dt),
                "wi_up": _he(km[2], (e, s.d, f), s.d, dt),
                "wo": _he(km[3], (e, f, s.d), f, dt),
                "shared_mlp": _swiglu_init(jax.random.fold_in(ks[1], 1), s.d,
                                           s.shared * f, dt)}
    return {"sub0": p}


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init(key, s: Shape, dt):
    """Fresh parameters for a hop's key ``sub`` (the program's layout)."""
    k1, k2 = jax.random.split(jax.random.split(key)[1])
    k_embed, k_layers, _ = jax.random.split(k1, 3)
    units = s.layers - s.dense_layers
    params = {
        "embed": {"embedding": (jax.random.normal(k_embed, (s.vocab, s.d))
                                * 0.02).astype(dt)},
        "layers": _stack([_layer_init(k, s, False, dt) for k in
                          jax.random.split(k_layers, units)]),
        "final_norm": {"scale": jnp.ones((s.d,), dt)},
        "cls_head": {"w": _he(k2, (s.d, s.classes), s.d, dt)},
    }
    if s.dense_layers:
        params["lead"] = _stack([
            _layer_init(k, s, True, dt) for k in jax.random.split(
                jax.random.fold_in(k_layers, 1), s.dense_layers)])
    return params


# ================================================================== forward
def _rms(p, x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def _swiglu(p, u):
    return (jax.nn.silu(u @ p["wi_gate"]) * (u @ p["wi_up"])) @ p["wo"]


def _rotate(x, positions, inv_freq):
    """Rope on the last axis's halves: x [b, t, ..., r]."""
    ang = positions[:, :, None].astype(jnp.float32) * inv_freq  # [b, t, r/2]
    while ang.ndim < x.ndim:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _mla(p, u, s: Shape):
    b, t, _ = u.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    inv_freq = yarn_inv_freq(s)
    q = (u @ p["wq"]).reshape(b, t, s.heads, s.nope + s.rope)
    q_nope, q_pe = q[..., :s.nope], _rotate(q[..., s.nope:], positions,
                                            inv_freq)
    kv = u @ p["wkv_a"]
    c = _rms(p["kv_a_norm"], kv[..., :s.rank], s.eps)
    k_pe = _rotate(kv[..., s.rank:], positions, inv_freq)          # [b,t,r]
    k_nope = (c @ p["wk_b"]).reshape(b, t, s.heads, s.nope)
    v = (c @ p["wv_b"]).reshape(b, t, s.heads, s.dv)
    scale = ((s.nope + s.rope) ** -0.5
             * yarn_scale(s.factor, s.mscale_all) ** 2)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe)
              ).astype(jnp.float32) * scale
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.reshape(b, t, s.heads * s.dv) @ p["wo"]


def _moe(p, u, s: Shape):
    """The held experts' part for the tokens routed to them, plus the
    shared experts."""
    gate = jax.nn.softmax((u.astype(jnp.float32)
                           @ p["router"].astype(jnp.float32)), -1)
    top, idx = jax.lax.top_k(gate, s.top_k)                    # [b, t, k]
    held = jnp.arange(s.held)
    weight = jnp.sum(jnp.where(idx[..., None] == held, top[..., None], 0.0),
                     axis=-2).astype(u.dtype)                  # [b, t, held]
    act = jax.nn.silu(jnp.einsum("btd,edf->btef", u, p["wi_gate"]))
    hidden = act * jnp.einsum("btd,edf->btef", u, p["wi_up"])
    routed = jnp.einsum("bte,btef,efd->btd", weight, hidden, p["wo"])
    return routed + _swiglu(p["shared_mlp"], u)


def _layer(p, x, s: Shape):
    p = p["sub0"]
    h = x + _mla(p["attn"], _rms(p["ln1"], x, s.eps), s)
    u = _rms(p["ln2"], h, s.eps)
    return h + (_swiglu(p["mlp"], u) if "mlp" in p else _moe(p["moe"], u, s))


def logits(params, tokens, s: Shape):
    """Class scores [b, K] of a block of token rows."""
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    layer = jax.checkpoint(functools.partial(_layer, s=s))
    for group in ("lead", "layers"):
        if group in params:
            for i in range(jax.tree.leaves(params[group])[0].shape[0]):
                x = layer(jax.tree.map(lambda a, _i=i: a[_i], params[group]),
                          x)
    pooled = jnp.mean(_rms(params["final_norm"], x, s.eps), axis=1)
    return (pooled.astype(jnp.float32)
            @ params["cls_head"]["w"].astype(jnp.float32))


def _ce(z, onehot):
    return jnp.sum(onehot * z, -1) - jax.nn.logsumexp(z, -1)


# ================================================================= learner
@dataclass(frozen=True)
class Backbone:
    shape: Shape
    steps: int
    batch: int
    lr: float
    dtype: str = "float32"

    @property
    def precision(self):
        return "highest" if self.dtype == "float32" else "default"

    @functools.partial(jax.jit, static_argnums=0)
    def init(self, sub):
        return init(sub, self.shape, jnp.dtype(self.dtype))

    def fit(self, sub, tokens, onehot, w, steps=None):
        """The hop's fit from its key ``sub``: ``steps`` AdamW steps (the
        configured number where None; one program for every count)."""
        steps = self.steps if steps is None else steps
        return self._fit(sub, tokens, onehot, w, jnp.asarray(steps, jnp.int32))

    @functools.partial(jax.jit, static_argnums=0)
    def _fit(self, sub, tokens, onehot, w, steps):
        with jax.default_matmul_precision(self.precision):
            s, dt = self.shape, jnp.dtype(self.dtype)
            params = init(sub, s, dt)
            batch_key = jax.random.split(sub)[0]
            log_w = jnp.log(w.astype(jnp.float32))
            blocks = self.batch // FIT_BLOCK

            def block_loss(p, xb, ob):
                return -jnp.sum(_ce(logits(p, xb, s), ob)) / self.batch

            grad = jax.grad(block_loss)

            def step(i, carry):
                p, m, v = carry
                rows = jax.random.categorical(jax.random.fold_in(batch_key, i),
                                              log_w, shape=(self.batch,))
                xs = tokens[rows].reshape(blocks, FIT_BLOCK, -1)
                os_ = onehot[rows].reshape(blocks, FIT_BLOCK, -1)

                def acc(g, blk):
                    return jax.tree.map(jnp.add, g, grad(p, *blk)), None

                g, _ = jax.lax.scan(acc, jax.tree.map(jnp.zeros_like, p),
                                    (xs, os_))
                t = i.astype(jnp.float32) + 1.0
                m = jax.tree.map(lambda m_, g_: 0.9 * m_ + 0.1 * g_, m, g)
                v = jax.tree.map(lambda v_, g_: 0.999 * v_ + 0.001 * g_ * g_,
                                 v, g)
                bc1, bc2 = 1.0 - 0.9 ** t, 1.0 - 0.999 ** t

                def leaf(p_, m_, v_):
                    upd = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + 1e-8)
                    return (p_ - self.lr * upd).astype(p_.dtype)

                return jax.tree.map(leaf, p, m, v), m, v

            zeros = jax.tree.map(jnp.zeros_like, params)
            params, _, _ = jax.lax.fori_loop(0, steps, step,
                                             (params, zeros, zeros))
            return params

    @functools.partial(jax.jit, static_argnums=0)
    def scores(self, params, tokens):
        """Class scores of every row, in blocks of ``FORWARD_BLOCK``."""
        with jax.default_matmul_precision(self.precision):
            params = jax.tree.map(lambda a: a.astype(self.dtype), params)
            n = tokens.shape[0]
            blocks = tokens.reshape(n // FORWARD_BLOCK, FORWARD_BLOCK, -1)
            out = jax.lax.map(lambda xb: logits(params, xb, self.shape),
                              blocks)
            return out.reshape(n, -1)


def backbone_from(config: dict, agent: dict, dtype: str = "float32"):
    return Backbone(shape_from(config), int(agent["steps"]),
                    int(agent["batch"]), float(agent["lr"]), dtype)


def mlp_from(config: dict, agent: dict, dtype: str = "float32"):
    return ref.Learner(kind="mlp", num_classes=int(config["num_classes"]),
                       steps=int(agent["steps"]), lr=float(agent["lr"]),
                       hidden=tuple(agent["hidden"]), dtype=dtype)


def _scales(tree):
    """The RMSNorm gains of a parameter tree (every leaf named ``scale``)."""
    return [x for path, x in jax.tree_util.tree_leaves_with_path(tree)
            if getattr(path[-1], "key", None) == "scale"]


@jax.jit
def scale_update_gap(got, want, start):
    """``| |got - start| / |want - start| - 1 |`` over the RMSNorm gains
    (Euclidean norms, in float32): 0 for a fit that moved them as far as
    the reference's fit from the same start, 1 for one that left them
    where they started.  The gains start at 1 and move by about the
    learning rate a step, below bfloat16's resolution there (half a step
    of 2^-7), so a fit that stores them in bfloat16 never moves them.  The
    other parameters' moves are not compared here: below float32
    ``HIGHEST`` products AdamW's normalized step turns the rounding of a
    near-zero gradient into a full step, and two sound fits part
    (:func:`update_gap` compares the moves of fits at ``HIGHEST``)."""
    def norm(a, b):
        return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)
                                               - y.astype(jnp.float32)))
                            for x, y in zip(_scales(a), _scales(b))))

    return jnp.abs(norm(got, start) / norm(want, start) - 1.0)


@jax.jit
def update_gap(got, want, start):
    """The worst leaf's ``|(got - start) - (want - start)| / |want -
    start|`` (Euclidean norms, in float32): how far a fit's move from
    ``start`` lies from the reference's, relative to the reference's move.
    0 for the same move, 1 for a leaf left where it started, 2 for a move
    of the other sign."""
    def gap(g, w, s):
        g, w, s = (x.astype(jnp.float32) for x in (g, w, s))
        return (jnp.sqrt(jnp.sum(jnp.square(g - w)))
                / jnp.maximum(jnp.sqrt(jnp.sum(jnp.square(w - s))), 1e-30))

    return jnp.max(jnp.stack(jax.tree.leaves(
        jax.tree.map(gap, got, want, start))))


# ================================================================== session
@dataclass
class Round:
    """What the reference's round 0 produced."""
    components: list        # (agent, round, alpha, params)
    ledger: list            # (kind, bits)
    alphas: list            # every executed hop's, the stopping one too
    first: object = None    # the first hop's parameters, whatever its alpha
    w: object = None        # the weights the next hop is sent
    key: object = None      # the session key the next hop splits


def first_sub(key):
    """The first hop's key ``sub`` of a session key."""
    return jax.random.split(key)[1]


def round_zero(key, blocks, classes, config: dict, dtype: str = "float32",
               first=None) -> Round:
    """Algorithm 1's first round over the agents in chain order, with
    upstream side information, the alpha <= 0 stop, and the configuration's
    single-codec wire.  ``first``, where given, is taken as the first hop's
    fitted parameters in place of the reference's own fit (the rest of the
    round then follows from it)."""
    dt = jnp.dtype(dtype)
    k = int(config["num_classes"])
    cap = float(config.get("alpha_cap", 20.0))
    codec = config["wire"]["codec"]
    n = int(classes.shape[0])
    onehot = jax.nn.one_hot(classes, k, dtype=jnp.float32)
    w = jnp.full((n,), 1.0 / n, dt)
    u = jnp.ones((n,), dt)
    out = Round([], [], [])
    for _ in blocks[1:]:
        out.ledger += [("labels", 32 * n), ("sample_ids", 32 * n)]
    for j, (agent, X) in enumerate(zip(config["agents"], blocks)):
        key, sub = jax.random.split(key)
        if agent["kind"] == "backbone":
            lr = backbone_from(config, agent, dtype)
            if j == 0 and first is not None:
                params = first
            else:
                params = lr.fit(sub, X, onehot, w.astype(jnp.float32))
            pred = jnp.argmax(lr.scores(params, X), -1)
            if j == 0:
                out.first = params
        else:
            lr = mlp_from(config, agent, dtype)
            params = lr.fit(sub, X, onehot, w.astype(jnp.float32))
            pred = lr.predict(params, X)
        r = (pred == classes).astype(dt)
        s_c, s_w = jnp.sum(w * u * r), jnp.sum(w * u * (1 - r))
        alpha = (jnp.log(jnp.maximum(s_c, ref.EPS))
                 - jnp.log(jnp.maximum(s_w, ref.EPS)) + math.log(k - 1))
        alpha = float(jnp.clip(alpha, -cap, cap))
        out.alphas.append(alpha)
        out.w, out.key = w, key
        if alpha <= 0:
            return out
        out.components.append((j, 0, alpha, params))
        a = jnp.asarray(alpha, dt)
        u = u * jnp.where(r > 0, jnp.exp(-a / (k - 1)),
                          jnp.exp(a / (k - 1) ** 2))
        w_upd = w * jnp.exp(a * (1 - r))
        w_upd = w_upd / jnp.maximum(jnp.sum(w_upd), ref.EPS)
        w = ref.roundtrip(codec, w_upd.astype(jnp.float32),
                          ref.wire_key(sub)).astype(dt)
        out.ledger += [("ignorance", ref.codec_bits(codec, n)),
                       ("model_weight", 32)]
        out.w = w
    return out

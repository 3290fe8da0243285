"""A configuration's data, made on the device from the seed in one jitted
call.

The generators are copies of the system's own synthetic datasets (paper
Section VI): the Fashion-MNIST surrogate (10 classes of 28x28 smooth
templates plus pixel noise of the configured ``std``, agents holding the
left and right halves) and
the isotropic Gaussian blobs of Fig. 6a.  They live here so that the data a
cell runs on cannot change with the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def fashion_images(key, n: int, noise: float, side: int = 28):
    """[n, side*side] images with the left-half pixels first, and classes;
    ``noise`` is the standard deviation of the pixel noise."""
    kt, kx, kc = jax.random.split(key, 3)
    freq = jnp.linspace(0.3, 1.2, 4)
    coords = jnp.linspace(-1, 1, side)
    xx, yy = jnp.meshgrid(coords, coords)
    phases = jax.random.uniform(kt, (10, 4, 2), maxval=2 * jnp.pi)
    amps = jax.random.normal(jax.random.fold_in(kt, 1), (10, 4))

    def template(c):
        return sum(amps[c, i] * jnp.sin(freq[i] * 3 * xx + phases[c, i, 0])
                   * jnp.cos(freq[i] * 3 * yy + phases[c, i, 1])
                   for i in range(4))

    templates = jnp.stack([template(c) for c in range(10)])
    # the class signal ramps left to right: the left half alone is weak
    templates = templates * jnp.linspace(0.25, 1.3, side)[None, None, :]
    classes = jax.random.randint(kc, (n,), 0, 10).astype(jnp.int32)
    imgs = (templates[classes]
            + noise * jax.random.normal(kx, (n, side, side)))
    col = jnp.arange(side * side).reshape(side, side)
    order = jnp.concatenate([col[:, :side // 2].reshape(-1),
                             col[:, side // 2:].reshape(-1)])
    return imgs.reshape(n, side * side)[:, order], classes


def blobs(key, n: int, features: int, classes: int, std: float = 1.0,
          box: float = 10.0):
    """Isotropic Gaussian blobs (``make_blobs`` semantics)."""
    ck, xk, lk, _ = jax.random.split(key, 4)
    centers = jax.random.uniform(ck, (classes, features), minval=-box,
                                 maxval=box)
    c = jax.random.randint(lk, (n,), 0, classes)
    X = centers[c] + std * jax.random.normal(xk, (n, features))
    return X, c.astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(0,))
def _make(spec: tuple, key, train_idx, test_idx):
    kind, n, splits = spec[0], spec[1], spec[2]
    if kind == "fashion_surrogate":
        X, c = fashion_images(key, n, spec[4])
    elif kind == "blobs":
        X, c = blobs(key, n, sum(splits), spec[3], spec[4])
    else:
        raise ValueError(f"unknown dataset generator {kind!r}")
    cuts = np.cumsum((0,) + splits)
    Xtr, Xte = X[train_idx], X[test_idx]
    return (tuple(Xtr[:, a:b] for a, b in zip(cuts[:-1], cuts[1:])),
            c[train_idx],
            tuple(Xte[:, a:b] for a, b in zip(cuts[:-1], cuts[1:])),
            c[test_idx])


def split_rows(config: dict, seed: int):
    """Training and held-out row indices: a seeded 70/30 permutation
    (paper Section VI), or the generator's first ``n_train`` rows and the
    rest when the configuration fixes the sizes."""
    ds = config["dataset"]
    n = int(ds["n"])
    if "n_train" in ds:
        rows = np.arange(n)
        return rows[:int(ds["n_train"])], rows[int(ds["n_train"]):]
    perm = np.random.default_rng(seed).permutation(n)
    cut = int(round(float(ds["train_frac"]) * n))
    return perm[:cut], perm[cut:]


def make(config: dict, key, seed: int):
    """(train blocks per agent, train classes, held-out blocks, held-out
    classes), all on the default device."""
    ds = config["dataset"]
    spec = (ds["generator"], int(ds["n"]), tuple(config["splits"]),
            int(config["num_classes"]), float(ds.get("std", 1.0)))
    tr, te = split_rows(config, seed)
    return _make(spec, key, jnp.asarray(tr, jnp.int32),
                 jnp.asarray(te, jnp.int32))

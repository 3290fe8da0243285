"""Synthetic dataset generators for the paper's experiments.

MIMIC-III is credential-gated (PhysioNet DUA) and UCI/Fashion-MNIST are not
reachable offline, so per DESIGN.md §6 we generate surrogates with the
paper's exact dimensionalities, class counts, and per-agent feature splits.
Blob data is generated exactly as described (isotropic Gaussian blobs).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Dataset:
    name: str
    X: jnp.ndarray          # [n, p]
    classes: jnp.ndarray    # [n] int32
    num_classes: int
    splits: tuple[int, ...]  # per-agent feature counts (sum == p)


def gaussian_blobs(key, *, n: int, num_features: int, num_classes: int,
                   cluster_std: float = 1.0, center_box: float = 10.0,
                   num_redundant: int = 0) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Isotropic Gaussian blobs (sklearn.datasets.make_blobs semantics)."""
    ck, xk, lk, rk = jax.random.split(key, 4)
    centers = jax.random.uniform(ck, (num_classes, num_features),
                                 minval=-center_box, maxval=center_box)
    classes = jax.random.randint(lk, (n,), 0, num_classes)
    X = centers[classes] + cluster_std * jax.random.normal(xk, (n, num_features))
    if num_redundant:
        noise = jax.random.normal(rk, (n, num_redundant)) * center_box / 2
        X = jnp.concatenate([X, noise], axis=-1)
    return X, classes.astype(jnp.int32)


def blob_fig3(key, n: int = 1000) -> Dataset:
    """Fig. 3a: 10-class blobs, 8 features, 4 agents x 2 features."""
    X, c = gaussian_blobs(key, n=n, num_features=8, num_classes=10,
                          cluster_std=1.5)
    return Dataset("blob", X, c, 10, (2, 2, 2, 2))


def blob_fig4(key, n: int = 1000) -> Dataset:
    """Fig. 4a: 10-class blobs, 5 informative + 195 redundant features,
    randomly divided into 2 agents x 100 features."""
    X, c = gaussian_blobs(key, n=n, num_features=5, num_classes=10,
                          cluster_std=1.0, num_redundant=195)
    perm = jax.random.permutation(jax.random.fold_in(key, 7), 200)
    return Dataset("blob200", X[:, perm], c, 10, (100, 100))


def blob_fig6(key, n: int = 1000) -> Dataset:
    """Fig. 6a: 20-class blobs, 20 features, 20 agents x 1 feature."""
    X, c = gaussian_blobs(key, n=n, num_features=20, num_classes=20,
                          cluster_std=1.0)
    return Dataset("blob20", X, c, 20, tuple([1] * 20))


def _tabular_surrogate(key, *, name, n, p, num_classes, splits,
                       informative_frac=0.7, noise=1.0, nonlinear=True):
    """Generic tabular surrogate: low-rank class-dependent means + optional
    sign interactions, standardized like a real tabular pull."""
    km, kx, kc, ki = jax.random.split(key, 4)
    num_inf = max(2, int(p * informative_frac))
    means = jax.random.normal(km, (num_classes, num_inf)) * 2.0
    classes = jax.random.randint(kc, (n,), 0, num_classes).astype(jnp.int32)
    X_inf = means[classes] + noise * jax.random.normal(kx, (n, num_inf))
    if nonlinear:
        # make a few informative columns only pairwise-informative
        X_inf = X_inf.at[:, :2].set(
            X_inf[:, :2] * jnp.sign(X_inf[:, 2:4] + 1e-3))
    X_noise = jax.random.normal(ki, (n, p - num_inf))
    X = jnp.concatenate([X_inf, X_noise], axis=-1)
    perm = jax.random.permutation(jax.random.fold_in(key, 11), p)
    X = X[:, perm]
    X = (X - X.mean(0)) / (X.std(0) + 1e-6)
    return Dataset(name, X, classes, num_classes, splits)


def mimic_surrogate(key, n: int = 15000) -> Dataset:
    """MIMIC-III extended-LoS surrogate: n=15000, p=16, K=2, split 3/12+1.

    The paper partitions 'according to the original data sources, one
    holding three features and the other holding 12' (16 total; the
    remaining feature rides with the larger source)."""
    return _tabular_surrogate(key, name="mimic", n=n, p=16, num_classes=2,
                              splits=(3, 13), informative_frac=0.6)


def mimic_notes(key, n: int = 15000, *, length: int = 512,
                vocab: int = 12800, noise: float = 1.0, cue_rate: float = 0.05,
                blank: float = 0.25, cue_words: int = 64):
    """MIMIC-III with clinical notes beside the chart (paper Sec. VI: one
    agent per data source over the same subjects): the 16 chart features
    of :func:`mimic_surrogate` (pixel-free tabular, ``noise`` the class
    spread's noise) and a note of ``length`` token ids in ``[0, vocab)``
    per subject.

    A note is background ids drawn uniformly, where each position with
    probability ``cue_rate`` is instead one of its class's ``cue_words``
    cue ids (drawn once per class); a ``blank`` share of notes never
    mention the finding and carry no cue, so the notes alone cannot fit
    every subject.  Returns (notes [n, length] int32, chart [n, 16] f32,
    classes [n] int32)."""
    k_chart, k_cue, k_bg, k_which, k_on, k_said = jax.random.split(key, 6)
    chart = _tabular_surrogate(k_chart, name="mimic", n=n, p=16,
                               num_classes=2, splits=(3, 13),
                               informative_frac=0.6, noise=noise)
    cues = jax.random.randint(k_cue, (chart.num_classes, cue_words), 0, vocab)
    background = jax.random.randint(k_bg, (n, length), 0, vocab)
    which = jax.random.randint(k_which, (n, length), 0, cue_words)
    said = jax.random.bernoulli(k_said, 1.0 - blank, (n,))
    on = jax.random.bernoulli(k_on, cue_rate, (n, length)) & said[:, None]
    notes = jnp.where(on, cues[chart.classes[:, None], which], background)
    return notes.astype(jnp.int32), chart.X, chart.classes


def qsar_surrogate(key, n: int = 1055) -> Dataset:
    """QSAR biodegradation surrogate: p=41, K=2, split 20/21."""
    return _tabular_surrogate(key, name="qsar", n=n, p=41, num_classes=2,
                              splits=(20, 21), informative_frac=0.5)


def wine_surrogate(key, n: int = 1599) -> Dataset:
    """Red-wine quality surrogate: p=11, K=6, split 6/5 (Fig. 3d) or
    11 x 1-feature agents (Fig. 6b)."""
    return _tabular_surrogate(key, name="wine", n=n, p=11, num_classes=6,
                              splits=(6, 5), informative_frac=0.9,
                              noise=1.6, nonlinear=False)


def fashion_surrogate(key, n: int = 4000, side: int = 28) -> Dataset:
    """Fashion-MNIST surrogate: 10 classes of 28x28 'garment' templates
    (class-dependent smooth random fields) + pixel noise; agents hold the
    left/right image halves (Fig. 5)."""
    kt, kx, kc = jax.random.split(key, 3)
    freq = jnp.linspace(0.3, 1.2, 4)
    coords = jnp.linspace(-1, 1, side)
    xx, yy = jnp.meshgrid(coords, coords)
    phases = jax.random.uniform(kt, (10, 4, 2), maxval=2 * jnp.pi)
    amps = jax.random.normal(jax.random.fold_in(kt, 1), (10, 4))

    def template(c):
        img = sum(amps[c, i] * jnp.sin(freq[i] * 3 * xx + phases[c, i, 0])
                  * jnp.cos(freq[i] * 3 * yy + phases[c, i, 1])
                  for i in range(4))
        return img

    templates = jnp.stack([template(c) for c in range(10)])   # [10, s, s]
    # class signal ramps left->right: the left-half agent alone is weak and
    # genuinely needs assistance (paper Fig. 5: B holds the other half)
    ramp = jnp.linspace(0.25, 1.3, side)[None, None, :]
    templates = templates * ramp
    classes = jax.random.randint(kc, (n,), 0, 10).astype(jnp.int32)
    imgs = templates[classes] + 1.1 * jax.random.normal(kx, (n, side, side))
    # left half -> agent A (columns 0..13), right half -> agent B
    X = imgs.reshape(n, side * side)
    # reorder pixels so the first side*side//2 belong to the left half
    col_idx = jnp.arange(side * side).reshape(side, side)
    left = col_idx[:, :side // 2].reshape(-1)
    right = col_idx[:, side // 2:].reshape(-1)
    X = X[:, jnp.concatenate([left, right])]
    half = side * (side // 2)
    return Dataset("fashion", X, classes, 10, (half, side * side - half))


def token_stream(key, *, vocab_size: int, batch: int, seq_len: int,
                 num_classes: int | None = None, copy_prob: float = 0.35):
    """Synthetic LM token batches for the end-to-end training driver and
    smoke tests: a genuine first-order Markov chain — with probability
    ``copy_prob`` token t is the affine map ``31 * t_{prev} + 7 (mod V)``
    of the *emitted* predecessor, else uniform noise.

    (The seed version applied the map to a pre-noise base sequence, which
    makes consecutive *output* tokens independent — ~zero learnable signal
    at any ``copy_prob``; that is why the tier-1 loss-decrease check could
    never pass.)  ``copy_prob`` scales the signal: at 1.0 the chain is
    deterministic and the next-token loss can approach 0."""
    kt, kl = jax.random.split(key)
    noise = jax.random.randint(kt, (batch, seq_len), 0, vocab_size)
    use_map = jax.random.bernoulli(kl, copy_prob, (batch, seq_len))

    def step(prev, xs):
        nz, um = xs
        nxt = jnp.where(um, (prev * 31 + 7) % vocab_size, nz)
        return nxt, nxt

    first = noise[:, 0]
    _, rest = jax.lax.scan(step, first,
                           (noise[:, 1:].T, use_map[:, 1:].T))
    tokens = jnp.concatenate([first[:, None], rest.T], axis=1)
    return tokens.astype(jnp.int32)

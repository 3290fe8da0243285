"""The notes-and-chart cohort of a backbone configuration, made on the
device from the seed in one jitted call.

A copy of the system's ``mimic_notes`` generator (``repro.data.synthetic``)
with the tabular surrogate it builds on, kept here so that the data a cell
runs on cannot change with the program: the 16 chart features of the
MIMIC-III extended-stay surrogate (paper Section VI) and, per subject, a
note of token ids drawn from the configuration's vocabulary slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def chart_surrogate(key, n: int, noise: float, p: int = 16,
                    num_classes: int = 2, informative_frac: float = 0.6):
    """[n, p] standardized features and classes: low-rank class means plus
    ``noise``, two columns only pairwise informative, then noise columns,
    in a seeded column order."""
    km, kx, kc, ki = jax.random.split(key, 4)
    num_inf = max(2, int(p * informative_frac))
    means = jax.random.normal(km, (num_classes, num_inf)) * 2.0
    classes = jax.random.randint(kc, (n,), 0, num_classes).astype(jnp.int32)
    X_inf = means[classes] + noise * jax.random.normal(kx, (n, num_inf))
    X_inf = X_inf.at[:, :2].set(X_inf[:, :2] * jnp.sign(X_inf[:, 2:4] + 1e-3))
    X = jnp.concatenate([X_inf, jax.random.normal(ki, (n, p - num_inf))],
                        axis=-1)
    X = X[:, jax.random.permutation(jax.random.fold_in(key, 11), p)]
    return (X - X.mean(0)) / (X.std(0) + 1e-6), classes


def mimic_notes(key, n: int, length: int, vocab: int, noise: float,
                cue_rate: float, blank: float, cue_words: int,
                num_classes: int = 2):
    """(notes [n, length] int32, chart [n, 16], classes [n]): background
    ids uniform over the vocabulary slice; each position with probability
    ``cue_rate`` one of the subject's class's ``cue_words`` cue ids, except
    in the ``blank`` share of notes that never mention the finding."""
    k_chart, k_cue, k_bg, k_which, k_on, k_said = jax.random.split(key, 6)
    chart, classes = chart_surrogate(k_chart, n, noise,
                                     num_classes=num_classes)
    cues = jax.random.randint(k_cue, (num_classes, cue_words), 0, vocab)
    background = jax.random.randint(k_bg, (n, length), 0, vocab)
    which = jax.random.randint(k_which, (n, length), 0, cue_words)
    said = jax.random.bernoulli(k_said, 1.0 - blank, (n,))
    on = jax.random.bernoulli(k_on, cue_rate, (n, length)) & said[:, None]
    notes = jnp.where(on, cues[classes[:, None], which], background)
    return notes.astype(jnp.int32), chart, classes


@functools.partial(jax.jit, static_argnums=(0,))
def _make(spec: tuple, key):
    return mimic_notes(key, *spec)


def make(config: dict, key):
    """(blocks in agent order, classes) on the default device: each agent's
    block is the notes or the chart, as its ``holds`` says."""
    ds = config["dataset"]
    spec = (int(ds["n"]), int(ds["length"]), int(config["vocab_size"]),
            float(ds["noise"]), float(ds["cue_rate"]), float(ds["blank"]),
            int(ds["cue_words"]), int(config["num_classes"]))
    notes, chart, classes = _make(spec, key)
    blocks = {"notes": notes, "chart": chart}
    return tuple(blocks[a["holds"]] for a in config["agents"]), classes

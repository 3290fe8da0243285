"""Device time under the ``backbone_attn`` name scope (multi-head latent
attention of every layer: projections, rope, scores, the causal softmax and
the values, in the fits and the predicts) as a share of all device
operation time in the traced window; None where no op ran under it."""
from bench.shares import scope_share


def read(rec):
    return scope_share(rec, r"backbone_attn")

"""Device time under the ``backbone_predict`` name scope (the blocked
forward pass over every subject's note that scores a backbone hop's reward)
as a share of all device operation time in the traced window; None where no
op ran under it."""
from bench.shares import scope_share


def read(rec):
    return scope_share(rec, r"backbone_predict")

"""Device time of the held routed experts of every MoE layer, in the fits
and the predicts, as a share of all device operation time in the traced
window: the ops under the ``backbone_experts`` name scope (the sort by
expert, the gather, the weighted combine) and the grouped matmuls, whose
TPU custom calls (``ragged-dot``) carry no name scope.  None where no op
ran under either."""
from bench.shares import scope_share


def read(rec):
    return scope_share(rec, r"backbone_experts|ragged-dot")

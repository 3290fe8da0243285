"""Device time under the ``ascii_channel_<j>`` name scopes (the wire:
controller step, budget walk, DP noise, one codec round trip per ladder
rung, the rung select, residual and cost updates) as a share of all device
operation time in the traced window."""
from bench.shares import scope_share


def read(rec):
    return scope_share(rec, r"ascii_channel_\d+")

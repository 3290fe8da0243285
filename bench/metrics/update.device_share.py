"""Device time under the ``ascii_update_<j>`` name scopes (eq. 13's model
weight, the stop rule, the upstream factor and the eqs. 10/12 reweight) as
a share of all device operation time in the traced window."""
from bench.shares import scope_share


def read(rec):
    return scope_share(rec, r"ascii_update_\d+")

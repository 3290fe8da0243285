"""The ``replay`` spans (``Protocol._replay_traffic``: the ledger booked
again on the host) as a share of the ``fit`` spans."""
from bench.shares import span_share


def read(rec):
    return span_share(rec, "replay")

"""Device time in operations under the ``ascii_hop_<j>`` name scopes (each
hop's weighted fit and the predict that scores it), as a share of all
device operation time in the traced window."""


def read(rec):
    scopes = rec["trace"]["scope_s"]
    total = sum(scopes.values())
    fit = sum(v for k, v in scopes.items() if k.startswith("ascii_hop_"))
    if total <= 0 or fit <= 0:
        return None
    return 100.0 * fit / total

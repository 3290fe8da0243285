"""1 minus the union of device operation intervals over the traced window
of a session cell."""


def read(rec):
    tr = rec["trace"]
    if tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

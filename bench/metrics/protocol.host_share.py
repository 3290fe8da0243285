"""The share of each ``Protocol.fit`` call (timed by the benchmark) spent
outside the protocol's fenced ``session`` span: the ledger replay, building
the fitted ensemble, key handling and dispatch."""


def read(rec):
    wall = sum(s["wall_s"] for s in rec.get("sessions", ()))
    inside = sum(d for name, d in rec.get("spans", ()) if name == "session")
    if wall <= 0 or inside <= 0:
        return None
    return 100.0 * (1.0 - inside / wall)

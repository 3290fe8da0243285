"""Model FLOPs of every hop the window's sessions ran (each fit's
full-batch steps and the predict that scores its reward, from
``bench/flops.py``) over the time those sessions took and the chip's bf16
peak.  Default-precision float32 products run on the MXU as single bf16
passes, so the bf16 peak is the one that bounds them."""
from bench import flops


def read(rec):
    sessions = rec.get("sessions")
    if not sessions:
        return None
    cfg = rec["config"]
    k, n = int(cfg["num_classes"]), int(rec["n_train"])
    total = sum(flops.hop_flops(cfg["learner"], n, int(cfg["splits"][j]), k)
                for s in sessions for executed in s["hops"]
                for j in range(executed))
    seconds = sum(s["wall_s"] for s in sessions)
    return 100.0 * total / (seconds * rec["peak"]["bf16_flops_per_s"])

"""Model FLOPs of every hop the window's sessions executed (the backbone's
fits and predicts from the ``session`` span's token and routed-pair counts,
``bench/flops_backbone.py``; the tabular agents' hops from
``bench/flops.py``) over the time those sessions took and the chip's bf16
peak.  None where the sessions carry no counts (a program that does not
count its backbone's work)."""
from bench import flops_backbone


def read(rec):
    sessions = rec.get("sessions")
    cfg = rec["config"]
    if not sessions or any("tokens_fit" not in s for s in sessions):
        return None
    total = 0
    for s in sessions:
        total += flops_backbone.backbone_flops(cfg, s)
        for j, agent in enumerate(cfg["agents"]):
            if agent["kind"] != "backbone":
                total += sum(flops_backbone.mlp_hop_flops(
                    cfg, agent, rec["widths"][j])
                    for executed in s["hops"] if executed > j)
    seconds = sum(s["wall_s"] for s in sessions)
    return 100.0 * total / (seconds * rec["peak"]["bf16_flops_per_s"])

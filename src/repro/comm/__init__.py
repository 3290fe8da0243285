"""Wire-format subsystem: what crosses an agent boundary, in how many bits,
at what precision, and with what privacy noise.

Sits between agents and transports (`repro.core.engine`):

  * :mod:`repro.comm.codecs`  — pure encode/decode pairs (fp32/fp16,
    int8/int4 stochastic quantization via the fused Pallas kernel, top-k
    sparsification with per-link error feedback).
  * :mod:`repro.comm.budget`  — per-link / per-session bit budgets and the
    degrade-then-skip :class:`~repro.comm.budget.BudgetedTransport`.
  * :mod:`repro.comm.privacy` — the Gaussian mechanism on outgoing
    ignorance vectors with per-agent epsilon accounting.

All three ride both engine backends: eager transports and the compiled
session scan run the same traced channel, so trajectories and byte ledgers
stay bit-identical across backends for every codec.

The policy layer above this subsystem — adaptive per-hop codec selection,
budget-aware round scheduling, RDP privacy accounting — lives in
:mod:`repro.control`.
"""
from repro.comm.codecs import (CODECS, Codec, Fp16Codec, Fp32Codec,
                               QuantCodec, TopKCodec, channel_apply,
                               jitted_channel, make_codec, rung_bits,
                               serve_key)
from repro.comm.privacy import GaussianMechanism, PrivacyAccountant

__all__ = [
    "CODECS", "Codec", "Fp16Codec", "Fp32Codec", "QuantCodec", "TopKCodec",
    "channel_apply", "jitted_channel", "make_codec", "rung_bits",
    "serve_key",
    "GaussianMechanism", "PrivacyAccountant",
    # lazy (avoids importing the engine on package import):
    "BudgetSpec", "BudgetedTransport", "DEFAULT_LADDER", "MODEL_WEIGHT_BITS",
    "TenantBudget",
]


def __getattr__(name):      # PEP 562: budget pulls in the engine; keep lazy
    if name in ("BudgetSpec", "BudgetedTransport", "DEFAULT_LADDER",
                "MODEL_WEIGHT_BITS", "TenantBudget"):
        from repro.comm import budget
        return getattr(budget, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

"""Mesh-native ignorance interchange (DESIGN.md §2).

The paper's chain 1→2→…→M→1 is a ring: on a TPU mesh with an ``agent`` axis
(device groups per agent) and a ``data`` axis (the length-n score sharded
like the batch), one interchange hop is

  * the fused local update  w ← w·exp(α(1−r)) / Z   (Pallas kernel, with
    the normalizer Z made global by a psum over the data axis), then
  * a pure neighbor ``ppermute`` along the agent ring — zero resharding,
    exactly one ICI hop of n/|data| floats per device.

`interchange_step` is the shard_map-ready building block;
`make_ring_interchange` wires it for a mesh.  The byte-metered
`core/transport.py` is the faithful single-host counterpart used by the
paper-figure benchmarks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops


def interchange_step(w_shard: jnp.ndarray, r_shard: jnp.ndarray,
                     alpha: jnp.ndarray, *, agent_axis: str,
                     data_axis: str | None) -> jnp.ndarray:
    """One hop of Algorithm 1 (eqs. 10/12) on a sharded score vector.

    w_shard/r_shard: this device's slice of the length-n score/reward.
    Returns the slice this device holds *for the next agent* (ring permute).
    """
    w_new = ops.ignorance_update(w_shard, r_shard, alpha,
                                 axis_name=data_axis)
    agent_size = jax.lax.axis_size(agent_axis)
    perm = [(i, (i + 1) % agent_size) for i in range(agent_size)]
    return jax.lax.ppermute(w_new, agent_axis, perm)


def make_ring_interchange(mesh, *, agent_axis: str = "agent",
                          data_axis: str = "data"):
    """shard_map-wrapped ring interchange over `mesh`.

    Inputs: w [M, n] (per-agent score replicas, agent-axis sharded, n
    data-sharded), r [M, n] (per-agent rewards), alpha [M].
    Output: w' [M, n] where agent (m+1) now holds agent m's updated score.
    """

    def step(w, r, alpha):
        out = interchange_step(w[0], r[0], alpha[0], agent_axis=agent_axis,
                               data_axis=data_axis)
        return out[None]

    return jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(agent_axis, data_axis), P(agent_axis, data_axis),
                  P(agent_axis)),
        out_specs=P(agent_axis, data_axis), check_vma=False)

"""Trace-time mesh context: lets model-internal shard_map blocks (the
ep_a2a MoE) see the mesh the launcher is lowering under, without threading
a Mesh handle through every model signature."""
from __future__ import annotations

import contextlib

_CURRENT_MESH = None


@contextlib.contextmanager
def mesh_context(mesh):
    global _CURRENT_MESH
    prev = _CURRENT_MESH
    _CURRENT_MESH = mesh
    try:
        yield mesh
    finally:
        _CURRENT_MESH = prev


def current_mesh():
    return _CURRENT_MESH

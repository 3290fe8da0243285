"""Sharding rules: divisibility-validity for every (arch, mesh), plus a
real lower+compile on a small host-device mesh via subprocess (the 512-way
production dry-run runs separately; see launch/dryrun.py)."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import ARCHS
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.sharding import rules

# An abstract 16x16 mesh for spec validation only (no devices needed).
from jax.sharding import AbstractMesh

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("mesh", [MESH, MESH3], ids=["1pod", "2pod"])
def test_param_specs_divisible(arch, mesh):
    cfg = ARCHS[arch]
    params_shape = jax.eval_shape(
        lambda: api.init_params(jax.random.key(0), cfg))
    specs = rules.param_specs(params_shape, cfg, mesh)
    flat_p = jax.tree.leaves(params_shape)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        assert len(spec) <= len(leaf.shape)
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 10):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % size == 0, (arch, leaf.shape, tuple(spec))


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "qwen3-moe-235b-a22b",
                                  "mamba2-130m", "jamba-v0.1-52b",
                                  "minicpm3-4b"])
def test_cache_specs_divisible(arch):
    cfg = ARCHS[arch]
    shape = INPUT_SHAPES["decode_32k"]
    caches = jax.eval_shape(
        lambda: api.init_cache(cfg, shape.global_batch, shape.seq_len))
    specs = rules.cache_spec_tree(caches, cfg, MESH, shape.global_batch,
                                  shape.seq_len)
    flat_c = jax.tree.leaves(caches)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for leaf, spec in zip(flat_c, flat_s):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 10):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            size = int(np.prod([MESH.shape[a] for a in axes]))
            assert dim % size == 0, (arch, leaf.shape, tuple(spec))


def test_tiny_models_skip_tp():
    assert not rules.use_tp(ARCHS["whisper-tiny"])
    assert not rules.use_tp(ARCHS["mamba2-130m"])
    assert rules.use_tp(ARCHS["gemma-7b"])


def test_production_mesh_shapes():
    # needs >= 512 devices only when actually building; validate shape logic
    # through the abstract path instead
    assert MESH.shape == {"data": 16, "model": 16}
    assert MESH3.shape == {"pod": 2, "data": 16, "model": 16}


@pytest.mark.slow
def test_dryrun_subprocess_smoke():
    """Real lower+compile of one pair through the actual dryrun entrypoint
    (spawns its own process so the 512-device XLA flag stays contained)."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-tiny", "--shape", "decode_32k", "--tag", "_test"],
        capture_output=True, text=True, env=env, timeout=560)
    assert "OK" in out.stdout, out.stdout + out.stderr

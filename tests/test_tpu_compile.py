"""The ASCII path's Pallas kernels compile for a TPU v5e.

Each kernel is compiled with ``interpret=False`` for one chip of a
described (not attached) v5e at the shapes of the Fashion-halves smoke
(`chip_smoke.py`): 49,000 training rows, 21,000 test rows, K = 10.  The
compiler refuses here what the chip would refuse (block shapes, scalar
stores, VMEM), at no chip time.  Nothing runs, so no result is checked.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ignorance, quantize

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # the TPU compiler otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _case(name, sharding):
    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    def i8(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int8, sharding=sharding)

    kind, size = name.split("@")
    if kind == "ignorance":
        n = int(size)
        return (lambda w, r, a: ignorance.ignorance_update_unnormalized(
            w, r, a, interpret=False), (f32(n), f32(n), f32()))
    if kind == "int8_tiles":
        n = int(size)
        return (lambda x, u, q: quantize.quantize_dequant_tiles(
            x, u, q, interpret=False), (f32(n), f32(n), f32()))
    if kind == "score_block":
        rows = int(size)
        return (lambda x, u, q: quantize.quantize_dequant_block(
            x, u, q, interpret=False), (f32(rows, 10), f32(rows, 10), f32()))
    m = int(size)
    if kind == "int4_pack":
        return (lambda q: quantize.pack_int4(q, interpret=False), (i8(m),))
    return (lambda p: quantize.unpack_int4(p, m, interpret=False),
            (i8((m + 1) // 2),))


@pytest.mark.parametrize("name", [
    "ignorance@49000", "ignorance@49152", "int8_tiles@49000",
    "score_block@21000", "score_block@256", "int4_pack@49000",
    "int4_unpack@49000"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES

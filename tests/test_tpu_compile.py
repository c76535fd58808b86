"""The SSSP Pallas kernels compile for a TPU v5e at the chip smoke's widths.

Nothing runs: each kernel is lowered and compiled for one chip of a
described (not attached) ``v5e:2x2`` topology with ``interpret=False``,
and the compiled text must hold a Mosaic kernel (``tpu_custom_call``).
This catches what interpret mode cannot — block shapes off the (8, 128)
tiling, dynamic lane offsets, scalar stores to VMEM, VMEM overflow — at
no chip time.  The topology is described inside a fixture only, so
importing this file never loads the TPU library; where it cannot be
described, every test here skips.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.frontier_relax import (frontier_scatter_min,
                                          frontier_scatter_min_batch)
from repro.kernels.relax import relax_ell
from repro.kernels.segment_min import masked_min

N = 1 << 20          # the smoke's grid: side 1024
CAP, DEG, B = 4096, 4, 8   # its frontier buffer, grid out-degree, batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip cannot read back what it writes to the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_relax_ell_compiles(spec):
    x = spec((N, 128), jnp.float32)
    text = _compiled_text(lambda d, w, m: relax_ell(d, w, m), x, x,
                          spec((N, 128), jnp.bool_))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batched", [False, True])
def test_masked_min_compiles(spec, batched):
    fn = lambda x, m: masked_min(x, m)  # noqa: E731
    shape = (N,)
    if batched:   # the pallas backend vmaps it over the batch lanes
        fn, shape = jax.vmap(fn), (B, N)
    text = _compiled_text(fn, spec(shape, jnp.float32),
                          spec(shape, jnp.bool_))
    assert "tpu_custom_call" in text


def test_frontier_scatter_min_compiles(spec):
    text = _compiled_text(lambda t, c: frontier_scatter_min(t, c, N),
                          spec((CAP, DEG), jnp.int32),
                          spec((CAP, DEG), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [N, 4 * N])
def test_frontier_scatter_min_batch_compiles(spec, n):
    # 4N: a resident output past the default scoped-VMEM limit
    text = _compiled_text(
        lambda t, c: frontier_scatter_min_batch(t, c, n),
        spec((CAP, DEG), jnp.int32), spec((B, CAP, DEG), jnp.float32))
    assert "tpu_custom_call" in text

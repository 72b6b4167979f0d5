"""Compile every Pallas kernel, and the flat sort graph, for a described
TPU v5e — the checks interpret mode cannot make (Mosaic's layout, cast
and primitive rules).  Nothing runs: the TPU compiler only has to accept
each program at the block sizes the system uses.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports this file.  The persistent compile cache is off
around these compiles, because an entry written for a described chip
cannot be read back without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitonic, encode, fused, histogram, rmi

N = 1 << 20  # rows per compiled program: a full super-batch
BLOCK_ROWS = 1024  # ops.py's block for encode / rmi / histogram


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_encode_kernel_compiles(one_chip):
    hlo = _compile(
        lambda k: encode.encode_pallas(k, block_rows=BLOCK_ROWS),
        _spec(one_chip, (N, 8), jnp.uint8),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n_leaf", [1024, 65536])
def test_rmi_kernel_compiles(one_chip, n_leaf):
    def fn(hi, lo, ints, consts, ftable, utable):
        return rmi.rmi_bucket_pallas(
            hi, lo, ints, consts, ftable, utable, block_rows=BLOCK_ROWS
        )

    hlo = _compile(
        fn,
        _spec(one_chip, (N,), jnp.uint32),
        _spec(one_chip, (N,), jnp.uint32),
        _spec(one_chip, (2,), jnp.uint32),
        _spec(one_chip, (4,), jnp.float32),
        _spec(one_chip, (n_leaf, 5), jnp.float32),
        _spec(one_chip, (n_leaf, 2), jnp.uint32),
    )
    assert hlo.count("tpu_custom_call") >= 2  # route + leaf passes


@pytest.mark.parametrize("n_buckets", [1000, 4096])
def test_histogram_kernel_compiles(one_chip, n_buckets):
    hlo = _compile(
        lambda ids: histogram.histogram_pallas(
            ids, n_buckets, block_rows=BLOCK_ROWS
        ),
        _spec(one_chip, (N,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("width", [1024, 2048])
def test_bitonic_kernel_compiles(one_chip, width):
    # fused.plan_batch's grid at 2**20 rows: 4096 rows of width 1024
    rows = N // 256
    hlo = _compile(
        lambda h, l, v: bitonic.sort_rows_pallas(h, l, v, block_rows=8),
        _spec(one_chip, (rows, width), jnp.uint32),
        _spec(one_chip, (rows, width), jnp.uint32),
        _spec(one_chip, (rows, width), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


def test_flat_sort_graph_compiles(one_chip):
    """The graph the batched executor dispatches on TPU."""
    hlo = _compile(
        fused._flat_impl,
        _spec(one_chip, (N, 8), jnp.uint8),
        _spec(one_chip, (N,), jnp.int32),
    )
    assert "sort" in hlo

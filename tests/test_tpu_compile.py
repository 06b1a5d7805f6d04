"""Mosaic compiles the main path's kernels for a TPU v5e — no chip needed.

The TPU compiler ships with JAX and compiles for a chip that is only
described (``v5e:2x2``), so these tests catch what interpret mode cannot:
shape casts Mosaic refuses, and tiles that overflow VMEM.  Each compiles a
kernel at a deployment's shape (an 8-slot x 131,072-bucket window carry, a
16,384-record micro-batch) with the carry tile the engine picks, and checks
that the compiled step holds the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import, and every
test of this kind stays in this one file: only one process at a time may
load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_fold.kernel import fused_streaming_fold
from repro.kernels.hash_combine.kernel import hash_combine

N_SLOTS, BUCKETS, BATCH = 8, 131072, 16384


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_fold(sharding, *, host_wire=False, **kw):
    rows = _shape(sharding, (BATCH, 4 if host_wire else 5))
    carry = _shape(sharding, (N_SLOTS * BUCKETS, 2))
    minw = _shape(sharding, (), jnp.int32)

    def step(r, c, m):
        return fused_streaming_fold(
            r, c, None if host_wire else m, n_slots=N_SLOTS,
            num_buckets=BUCKETS, carry_buckets=BUCKETS, host_wire=host_wire,
            interpret=False, **kw)

    return jax.jit(step, donate_argnums=(1,)).lower(rows, carry, minw) \
        .compile().as_text()


@pytest.mark.parametrize("kind", ["sum", "max"])
def test_fused_fold_device_wire_compiles(one_chip, kind):
    """Sliding windows (fan-out 4) on the device wire: the fan-out loop
    and the masked extremum both lower without a reshape."""
    assert "tpu_custom_call" in _compile_fold(one_chip, fanout=4, kind=kind)


def test_fused_fold_host_wire_compiles(one_chip):
    """The host wire (fan-out 1) at the full carry: the engine's carry
    tile keeps the one-hot inside VMEM."""
    assert "tpu_custom_call" in _compile_fold(one_chip, fanout=1,
                                              host_wire=True, kind="sum")


def test_fused_fold_hashed_keys_compile(one_chip):
    """The hashed key space runs the murmur finalizer in-kernel."""
    assert "tpu_custom_call" in _compile_fold(one_chip, fanout=4,
                                              hashed=True, kind="sum")


def test_hash_combine_compiles(one_chip):
    """The batch shuffle's combiner: 65,536 records into 4,096 buckets."""
    n = 65536
    keys = _shape(one_chip, (n,), jnp.int32)
    vals = _shape(one_chip, (n,))
    valid = _shape(one_chip, (n,), jnp.bool_)
    hlo = jax.jit(lambda k, v, ok: hash_combine(
        k, v, ok, num_buckets=4096, interpret=False)) \
        .lower(keys, vals, valid).compile().as_text()
    assert "tpu_custom_call" in hlo


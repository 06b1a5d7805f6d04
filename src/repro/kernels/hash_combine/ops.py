"""jit'd public wrapper for the hash_combine kernel.

``combine(..., use_pallas=False)`` routes to the XLA segment-sum reference
(the default, and what the dry-run's cost_analysis sees); ``use_pallas=True``
targets the TPU kernel.  ``interpret=None`` asks
``engine.compile.default_pallas_interpret``: Mosaic on a TPU, the kernel
body as jax ops everywhere else.

The signature matches the ``combine_fn`` hook of
``repro.core.shuffle.shuffle_aggregate``.
"""

from __future__ import annotations

import jax

from ...engine.compile import default_pallas_interpret
from .kernel import hash_combine as hash_combine_pallas
from .ref import hash_combine_ref


def combine(keys: jax.Array, values: jax.Array, num_buckets: int,
            valid: jax.Array | None = None, *, use_pallas: bool = False,
            interpret: bool | None = None, block_n: int = 512) -> jax.Array:
    if use_pallas:
        if interpret is None:
            interpret = default_pallas_interpret()
        return hash_combine_pallas(keys, values, valid,
                                   num_buckets=num_buckets, block_n=block_n,
                                   interpret=interpret)
    return hash_combine_ref(keys, values, num_buckets, valid)


def make_combine_fn(use_pallas: bool = False, interpret: bool | None = None,
                    block_n: int = 512):
    """Factory returning a ``combine_fn(keys, values, num_buckets, valid)``
    for ``shuffle_aggregate`` / ``core.mapreduce``."""

    def fn(keys, values, num_buckets, valid=None):
        return combine(keys, values, num_buckets, valid,
                       use_pallas=use_pallas, interpret=interpret,
                       block_n=block_n)

    return fn

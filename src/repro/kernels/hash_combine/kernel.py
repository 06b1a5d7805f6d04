"""hash_combine — the Mapper's combiner as a Pallas TPU kernel.

Paper (§III-A.3, Figs. 7-8): the Mapper's dominant cost is sorting each output
buffer by key and running the combiner before spilling.  A comparison sort is
the right tool on CPU containers; on TPU it serializes on the VPU while the
MXU idles.  DESIGN.md §4.1: re-express sort+combine as *bucket accumulation
via one-hot matmul* —

    out[b, d] = Σ_n  [keys[n] == b] · values[n, d]
             ⇔ one_hot(keys)ᵀ @ values          (a (B×N)·(N×D) matmul)

which runs at MXU rate, needs no data-dependent control flow, and emits the
per-bucket partials already grouped ("born sorted") — the property the paper's
sorted spills exist to provide.

Tiling: grid over record tiles of ``block_n``; each step builds the
(block_n × num_buckets) one-hot in VMEM via broadcasted_iota comparison and
accumulates ``one_hotᵀ @ values`` into the (num_buckets × D) output block,
which stays resident in VMEM across grid steps (same block for every i —
Pallas keeps it and the accumulation is sequential on TPU).

VMEM budget per step: block_n·num_buckets (one-hot) + block_n·D (values)
+ num_buckets·D (accumulator), all fp32.  Defaults (block_n=512, B≤4096,
D≤256) stay well under 16 MB VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hash_combine_kernel(keys_ref, values_ref, valid_ref, out_ref, *,
                         num_buckets: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...]          # (block_n, 1)
    vals = values_ref[...]        # (block_n, D)
    valid = valid_ref[...]        # (block_n, 1)

    # one-hot via iota comparison — MXU-friendly, no gather/scatter; an
    # invalid record's key is −1, whose one-hot row is all zeros
    buckets = jax.lax.broadcasted_iota(jnp.int32, (keys.shape[0], num_buckets), 1)
    onehot = (jnp.where(valid != 0, keys, -1) == buckets).astype(vals.dtype)

    # (B, block_n) @ (block_n, D) on the MXU, accumulated in fp32; HIGHEST
    # keeps f32 values unrounded, as the dense jnp combiner does
    out_ref[...] += jax.lax.dot_general(
        onehot, vals, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("num_buckets", "block_n", "interpret"))
def hash_combine(keys: jax.Array, values: jax.Array,
                 valid: jax.Array | None = None, *, num_buckets: int,
                 block_n: int = 512, interpret: bool = False) -> jax.Array:
    """Bucket-accumulate ``values`` by ``keys`` → (num_buckets, D) sums.

    keys : (N,) int32 in [0, num_buckets); values : (N,) or (N, D) float;
    valid: (N,) bool (None = all valid).  N is padded to block_n internally.
    """
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    n, d = values.shape
    if valid is None:
        valid = jnp.ones((n,), dtype=jnp.bool_)

    # keys and valid ride as (N, 1) columns: Mosaic keeps every block 2-D
    keys = keys.astype(jnp.int32).reshape(n, 1)
    valid = valid.astype(jnp.int32).reshape(n, 1)
    n_pad = (-n) % block_n
    if n_pad:
        keys = jnp.pad(keys, ((0, n_pad), (0, 0)))
        values = jnp.pad(values, ((0, n_pad), (0, 0)))
        valid = jnp.pad(valid, ((0, n_pad), (0, 0)))
    n_total = n + n_pad
    grid = (n_total // block_n,)

    out = pl.pallas_call(
        functools.partial(_hash_combine_kernel, num_buckets=num_buckets),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_buckets, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_buckets, d), values.dtype),
        interpret=interpret,
    )(keys, values, valid)
    return out[:, 0] if squeeze else out

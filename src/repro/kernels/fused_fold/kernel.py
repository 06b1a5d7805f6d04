"""fused_fold — the engine's streaming fold as one Pallas TPU kernel.

The streaming hot loop (``engine/plan._stream_agg_device_body``) lowers
today through four XLA ops per micro-batch: ``device_hash``/``bucketize``
→ ``window_fanout`` (broadcast + iota) → ``segment_sum`` over the
flattened (slot, bucket) id space → carry add.  Each materializes its
fanout-expanded intermediates in HBM.  This kernel fuses the chain: rows
stream through VMEM once per record tile, the hash / fan-out / watermark
masking happen in registers, and values scatter-accumulate straight into
the resident carry block — the carry is read from and written to HBM once
per batch instead of once per op.

Generalizes ``kernels/hash_combine`` (one-hot × MXU matmul bucket
accumulation, grid over record tiles, out block resident across steps) in
three directions:

* the id space is (window slot × bucket), flattened over the carry's
  bucket width, with the 1..fanout sliding-window replication and the
  ``min_window`` late-pair masking computed in-kernel from iota
  arithmetic (a late or uncovered pair gets flat id −1, whose one-hot row
  is all zeros — masking is free);
* the accumulator is the streaming *carry*: the output block seeds from
  the carry input at the first record tile (``input_output_aliases``
  makes the update in-place under donation) and the kernel returns the
  folded ``[late, folded, 0]`` counters the watermark books need;
* fold kinds ``sum``/``count`` take the MXU matmul path; ``min``/``max``
  keep a masked running extremum on the VPU (count channel still summed,
  so emptiness stays observable).

Grid: ``(carry tiles, record tiles)`` — record tiles iterate innermost,
so each carry tile stays resident in VMEM while every record tile streams
past it.  The wire enters transposed, ``(width, block_n)``, one record per
lane, and the fan-out is a static loop: copy j builds its own
``(block_s, block_n)`` one-hot, so Mosaic never sees a reshape.
``pick_block_s`` sizes the carry tile from a VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import FOLD_KINDS, HOST_ROW, DEVICE_ROW

INT32_MIN = -(2 ** 31)

#: VMEM bytes one grid step may plan for: under the 16 MiB default scoped
#: limit of a TPU v5e core, with room left for Mosaic's own scratch
VMEM_BUDGET = 12 * 2 ** 20


def pick_block_s(size: int, channels: int, block_n: int) -> int:
    """The carry tile: the largest divisor of ``size`` (the flat id space,
    ``n_slots * carry_buckets``) that is a multiple of 8 and whose VMEM
    footprint fits ``VMEM_BUDGET``.  Per carry row a step holds the carry block
    in and out, double-buffered, and the accumulator and update
    temporaries, each padded to 128 lanes, plus about four ``block_n``-wide
    f32 one-hot temporaries."""
    lanes = -(-channels // 128) * 128
    per_row = 4 * (8 * lanes + 4 * block_n)
    cap = VMEM_BUDGET // per_row
    if size <= cap:
        return size
    for d in range(cap - cap % 8, 7, -8):
        if size % d == 0:
            return d
    raise ValueError(f"no carry tile fits VMEM: n_slots * carry_buckets = "
                     f"{size} has no multiple-of-8 divisor <= {cap}")


def _bucketize(keys, num_buckets: int, hashed: bool):
    """In-kernel murmur3 finalizer — mirrors ``ref.murmur_bucket``."""
    keys = keys.astype(jnp.int32)
    if not hashed:
        return keys
    h = keys.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return (h % jnp.uint32(num_buckets)).astype(jnp.int32)


def _fused_fold_kernel(rows_ref, carry_ref, minw_ref, out_ref, stats_ref, *,
                       fanout: int, n_slots: int, num_buckets: int,
                       carry_buckets: int, channel_base: int, hashed: bool,
                       host_wire: bool, kind: str, block_s: int):
    s = pl.program_id(0)            # carry (flat id) tile
    i = pl.program_id(1)            # record tile — innermost, accumulates

    @pl.when(i == 0)
    def _seed():                    # out block = carry block + batch delta
        out_ref[...] = carry_ref[...]

    @pl.when((s == 0) & (i == 0))
    def _zero_stats():
        stats_ref[...] = jnp.zeros_like(stats_ref)

    # (width, block_n) transposed wire: one record per lane, so every field
    # is a lane-dense (1, block_n) row and nothing below needs a reshape
    rows = rows_ref[...]
    n = rows.shape[1]

    # -- decode + fan-out + watermark masking (registers, no HBM traffic) --
    # ``pairs`` holds one (flat id, live) row pair per fan-out copy j
    if host_wire:                   # host already expanded; fan-out 1
        slot = rows[0:1].astype(jnp.int32)
        bucket = _bucketize(rows[1:2], num_buckets, hashed)
        val = rows[2:3]
        pairs = [(slot * carry_buckets + bucket, rows[3:4] > 0)]
        late = jnp.zeros((), jnp.int32)
    else:
        last = rows[0:1].astype(jnp.int32)
        n_windows = rows[1:2].astype(jnp.int32)
        bucket = _bucketize(rows[2:3], num_buckets, hashed)
        val = rows[3:4]
        valid = rows[4:5] > 0
        minw = minw_ref[0, 0]
        pairs, late = [], jnp.zeros((), jnp.int32)
        for j in range(fanout):     # static: copy j covers window last - j
            widx = last - j
            covers = valid & (j < n_windows)
            late += jnp.sum((covers & (widx < minw)).astype(jnp.int32))
            slot = jnp.mod(widx, n_slots)
            pairs.append((slot * carry_buckets + bucket,
                          covers & (widx >= minw)))
    folded = sum(jnp.sum(live.astype(jnp.int32)) for _, live in pairs)

    # flat id of each carry row in this tile, against every record lane;
    # a dead pair's id is −1, whose one-hot column is all zeros.  One
    # (block_s, n) one-hot per fan-out copy, consumed before the next
    ids = jax.lax.broadcasted_iota(jnp.int32, (block_s, n), 0) + s * block_s
    channels = out_ref.shape[1]
    ch = jax.lax.broadcasted_iota(jnp.int32, (1, channels), 1)

    if kind in ("sum", "count"):
        # [Σ value-or-one, Σ 1] per flat id: one (block_s × n)·(n × 2)
        # matmul on the MXU per fan-out copy.  HIGHEST keeps the products
        # in f32 (a bf16 pass would round values), so the sums match the
        # XLA backends' bytes wherever f32 addition is exact
        contrib = jnp.ones_like(val) if kind == "count" else val
        pair = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (2, n), 0) == 0,
                         contrib, 1.0)
        acc = jnp.zeros((block_s, 2), jnp.float32)
        for flat, live in pairs:
            onehot = (ids == jnp.where(live, flat, -1)).astype(jnp.float32)
            acc += jax.lax.dot_general(
                onehot, pair, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        out_ref[...] += (
            jnp.where(ch == channel_base, acc[:, 0:1], 0.0)
            + jnp.where(ch == channel_base + 1, acc[:, 1:2], 0.0)
        ).astype(out_ref.dtype)
    else:                           # min / max: masked running extremum
        neutral = jnp.float32(jnp.inf if kind == "min" else -jnp.inf)
        pick = jnp.minimum if kind == "min" else jnp.maximum
        reduce = jnp.min if kind == "min" else jnp.max
        ext = jnp.full((block_s, 1), neutral)
        cnt = jnp.zeros((block_s, 1), jnp.float32)
        for flat, live in pairs:
            hit = ids == jnp.where(live, flat, -1)
            ext = pick(ext, reduce(jnp.where(hit, val, neutral), axis=1,
                                   keepdims=True))
            cnt += jnp.sum(hit.astype(jnp.float32), axis=1, keepdims=True)
        old = out_ref[...]
        old_v = old[:, channel_base:channel_base + 1]
        old_c = old[:, channel_base + 1:channel_base + 2]
        comb = pick(jnp.where(old_c > 0, old_v, neutral), ext)
        new_c = old_c + cnt
        new_v = jnp.where(new_c > 0, comb, 0.0)
        out_ref[...] = jnp.where(
            ch == channel_base, new_v,
            jnp.where(ch == channel_base + 1, new_c, old)
        ).astype(out_ref.dtype)

    @pl.when(s == 0)                # each record tile counted exactly once
    def _count():
        k = jax.lax.broadcasted_iota(jnp.int32, (1, 3), 1)
        stats_ref[...] += jnp.where(k == 0, late, jnp.where(k == 1, folded, 0))


@functools.partial(
    jax.jit,
    static_argnames=("fanout", "n_slots", "num_buckets", "carry_buckets",
                     "channel_base", "hashed", "host_wire", "kind",
                     "block_n", "block_s", "interpret"))
def fused_streaming_fold(rows, carry, min_window=None, *, fanout: int,
                         n_slots: int, num_buckets: int, carry_buckets: int,
                         channel_base: int = 0, hashed: bool = False,
                         host_wire: bool = False, kind: str = "sum",
                         block_n: int = 256, block_s: int | None = None,
                         interpret: bool = False):
    """One fused streaming fold: ``(rows, carry[, min_window]) →
    (carry', [late, folded, 0])``.

    rows : (N, 5) float32 device wire ``[last_window_index, n_windows,
    key, value, valid]`` (or (N, 4) host wire ``[window_slot, key, value,
    valid]`` with ``host_wire=True``); carry : the flattened
    ``(n_slots * carry_buckets, channels)`` slab.  N pads to ``block_n``
    (a multiple of 128 when compiled) internally (pad rows are invalid);
    ``block_s`` tiles the flat id space (default: ``pick_block_s``, the
    largest tile that fits the VMEM budget).  Bit-parity oracle:
    ``ref.fused_streaming_fold_ref``.
    """
    if kind not in FOLD_KINDS:
        raise ValueError(f"unknown fold kind {kind!r}")
    size, channels = carry.shape
    if size != n_slots * carry_buckets:
        raise ValueError(f"carry has {size} rows, expected "
                         f"n_slots*carry_buckets = {n_slots * carry_buckets}")
    width = HOST_ROW if host_wire else DEVICE_ROW
    if rows.shape[1] != width:
        raise ValueError(f"expected width-{width} wire rows, got "
                         f"{rows.shape}")
    block_s = block_s or pick_block_s(size, channels, block_n)
    if size % block_s or (block_s % 8 and block_s != size):
        raise ValueError(f"block_s={block_s} must be a multiple of 8 that "
                         f"divides n_slots * carry_buckets = {size}")
    minw = INT32_MIN if min_window is None else min_window
    minw = jnp.asarray(minw, jnp.int32).reshape(1, 1)

    n = rows.shape[0]
    n_pad = (-n) % block_n
    if n_pad:                       # zero rows decode as invalid
        rows = jnp.pad(rows, ((0, n_pad), (0, 0)))
    rows = rows.T                   # (width, N): records along the lanes
    grid = (size // block_s, (n + n_pad) // block_n)

    new_carry, stats = pl.pallas_call(
        functools.partial(
            _fused_fold_kernel, fanout=fanout, n_slots=n_slots,
            num_buckets=num_buckets, carry_buckets=carry_buckets,
            channel_base=channel_base, hashed=hashed, host_wire=host_wire,
            kind=kind, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((width, block_n), lambda s, i: (0, i)),
            pl.BlockSpec((block_s, channels), lambda s, i: (s, 0)),
            pl.BlockSpec((1, 1), lambda s, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_s, channels), lambda s, i: (s, 0)),
            pl.BlockSpec((1, 3), lambda s, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((size, channels), carry.dtype),
            jax.ShapeDtypeStruct((1, 3), jnp.int32),
        ],
        input_output_aliases={1: 0},    # carry updates in place when donated
        interpret=interpret,
    )(rows, carry, minw)
    return new_carry, stats[0]

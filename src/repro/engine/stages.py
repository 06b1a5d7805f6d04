"""Pure SPMD stage bodies — the device-side shuffle and window machinery.

Everything here is backend-agnostic: a stage is a pure function over one
worker's arrays that may call collectives on an axis name, equally valid
under ``jax.vmap`` (simulated workers) and ``shard_map`` (a real mesh axis).
``engine.compile.lower`` picks the substrate; ``engine.plan`` composes
stages into execution plans.

The shuffle stages re-express the paper's hash-partition + sorted-spill +
merge on a TPU mesh:

  * partition ``hash(key) % R``        →  the same hash, on int32 key ids
  * spill upload + reducer download    →  one ``jax.lax.all_to_all``
  * sorted spill runs + k-way merge    →  ``jax.lax.sort`` of the
                                          concatenated runs
  * combiner before spill              →  local bucket pre-reduction

Window stages keep streaming records on the fast path: a record crosses
host→device once and ``window_fanout`` replicates it into its
``ceil(size/slide)`` overlapping windows on-chip (broadcast + iota), so the
host never materializes the event × window expansion.  Key stages open the
key domain: ``bucketize`` hashes unbounded keys into a fixed bucket space
and ``distinct_keys_per_bucket`` does exact per-bucket collision accounting.

Keys are int32; values are float32/int32 arrays with leading axis = records.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

INVALID = jnp.int32(-1)
INT32_MAX = jnp.iinfo(jnp.int32).max


#: raw hashed-key ids must survive the float32 wire exactly
RAW_KEY_BITS = 24


def device_hash(keys: jax.Array) -> jax.Array:
    """murmur3 finalizer over int32 keys — stable, well-mixed, vectorized.

    The device analogue of the FNV-1a the host workers use on strings.
    """
    h = keys.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def fold_key24(key) -> int:
    """Stable host-side key → 24-bit raw id (FNV-1a 64, xor-folded).

    Small enough to ride the float32 wire exactly; the device hashes the
    raw id into buckets with ``device_hash``.  This is the single host
    entry point for open key domains — the streaming coordinator and any
    pipeline front end must fold keys here so labels and device buckets
    can never drift.
    """
    h = 0xCBF29CE484222325
    for b in str(key).encode():
        h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return (h ^ (h >> 24) ^ (h >> 48)) & ((1 << RAW_KEY_BITS) - 1)


def host_bucket(raw: int, num_buckets: int) -> int:
    """Host mirror of ``device_hash(raw) % num_buckets`` — bit-exact, so
    host-side bookkeeping (bucket labels, session cells) addresses the same
    bucket the device folds the record into."""
    h = raw & 0xFFFFFFFF
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & 0xFFFFFFFF
    h ^= h >> 16
    return h % num_buckets


def hash_partition(keys: jax.Array, n_partitions: int) -> jax.Array:
    """``hash(key) % R`` → destination partition (reducer) per record."""
    return (device_hash(keys) % jnp.uint32(n_partitions)).astype(jnp.int32)


def bucketize(keys: jax.Array, num_buckets: int, *,
              hashed: bool) -> jax.Array:
    """Raw int32 keys → bucket ids in ``[0, num_buckets)``.

    Dense key spaces pass through (the data layer already assigned dense
    ids); hashed key spaces fold an open domain into the bucket space with
    ``device_hash``, trading key identity for boundedness — collisions are
    accounted by ``distinct_keys_per_bucket``.
    """
    keys = keys.astype(jnp.int32)
    if hashed:
        return (device_hash(keys) % jnp.uint32(num_buckets)).astype(jnp.int32)
    return keys


# ---------------------------------------------------------------------------
# Local combine (the Mapper's sort+combiner, §III-A.3)
# ---------------------------------------------------------------------------

def local_combine_dense(keys: jax.Array, values: jax.Array, num_buckets: int,
                        valid: jax.Array | None = None) -> jax.Array:
    """Combine records into a dense per-bucket sum vector.

    TPU adaptation of the sorted spill + combiner: instead of comparison
    sorting, bucket-accumulate.  XLA lowers segment-sum as scatter-add; the
    Pallas ``hash_combine`` kernel does the same with one-hot MXU matmuls
    (see kernels/hash_combine).  Output is 'born sorted' by bucket id.
    """
    if valid is not None:
        vmask = valid.reshape((-1,) + (1,) * (values.ndim - 1))
        values = jnp.where(vmask, values, jnp.zeros_like(values))
        keys = jnp.where(valid, keys, 0)
    seg = jax.ops.segment_sum(values, keys.astype(jnp.int32),
                              num_segments=num_buckets)
    return seg


def sort_and_group(keys: jax.Array, values: jax.Array,
                   valid: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Key-sort records (invalid to the end) — the merged, grouped stream the
    Reducer consumes.  Returns (sorted_keys, sorted_values, group_starts) where
    ``group_starts[i]`` is 1 when a new key group begins at i."""
    if valid is None:
        valid = jnp.ones_like(keys, dtype=bool)
    sort_keys = jnp.where(valid, keys, INT32_MAX)
    order = jnp.argsort(sort_keys, stable=True)
    sk = sort_keys[order]
    sv = jnp.take(values, order, axis=0)
    starts = jnp.concatenate([
        jnp.ones((1,), dtype=jnp.int32),
        (sk[1:] != sk[:-1]).astype(jnp.int32),
    ])
    starts = jnp.where(sk == INT32_MAX, 0, starts)
    return sk, sv, starts


# ---------------------------------------------------------------------------
# Per-device accounting
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShuffleStats:
    """Per-device accounting, the analogue of the paper's bytes_in/bytes_out.

    ``bucket_collisions`` is present for hashed key spaces with collision
    tracking: per bucket, how many *extra* distinct raw keys share it
    (``distinct - 1``, clamped at 0) — exact, computed by a dedicated
    dedupe-and-count exchange (``distinct_keys_per_bucket``).
    """

    sent: jax.Array                      # records sent (valid, pre-exchange)
    dropped: jax.Array                   # records dropped by capacity overflow
    bucket_collisions: jax.Array | None = None

    @property
    def collisions(self):
        """Total colliding-key count over all buckets (0 when untracked)."""
        if self.bucket_collisions is None:
            return 0
        return jnp.sum(self.bucket_collisions)


jax.tree_util.register_pytree_node(
    ShuffleStats,
    lambda s: ((s.sent, s.dropped, s.bucket_collisions), None),
    lambda _, ch: ShuffleStats(*ch))


# ---------------------------------------------------------------------------
# The exchange (spill upload + download → all_to_all)
# ---------------------------------------------------------------------------

def build_send_buffers(keys: jax.Array, values: jax.Array, n_partitions: int,
                       capacity: int, valid: jax.Array | None = None
                       ) -> tuple[jax.Array, jax.Array, jax.Array, ShuffleStats]:
    """Pack records into fixed (n_partitions, capacity) send buffers.

    The device analogue of writing one spill file per reducer: records are
    sorted by destination partition (so each partition's slice is contiguous
    — a 'file'), padded/truncated to ``capacity``.  Returns (send_keys,
    send_values, send_valid, stats).
    """
    n = keys.shape[0]
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    dest = jnp.where(valid, hash_partition(keys, n_partitions),
                     jnp.int32(n_partitions))  # invalid → virtual partition R
    order = jnp.argsort(dest, stable=True)
    d_sorted = dest[order]
    k_sorted = keys[order]
    v_sorted = jnp.take(values, order, axis=0)
    # position of each record within its destination group
    counts = jnp.bincount(d_sorted, length=n_partitions + 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos_in_group = jnp.arange(n, dtype=jnp.int32) - offsets[d_sorted]
    in_cap = (pos_in_group < capacity) & (d_sorted < n_partitions)
    slot = jnp.where(in_cap, d_sorted * capacity + pos_in_group,
                     n_partitions * capacity)

    send_keys = jnp.full((n_partitions * capacity + 1,), INVALID,
                         dtype=keys.dtype)
    send_keys = send_keys.at[slot].set(jnp.where(in_cap, k_sorted, INVALID))
    val_shape = (n_partitions * capacity + 1,) + values.shape[1:]
    send_vals = jnp.zeros(val_shape, dtype=values.dtype)
    send_vals = send_vals.at[slot].set(
        jnp.where(in_cap.reshape((-1,) + (1,) * (values.ndim - 1)),
                  v_sorted, jnp.zeros_like(v_sorted)))
    send_valid = jnp.zeros((n_partitions * capacity + 1,), dtype=bool)
    send_valid = send_valid.at[slot].set(in_cap)

    sent = jnp.sum(counts[:n_partitions].astype(jnp.int32))
    kept = jnp.sum(send_valid[:-1].astype(jnp.int32))
    stats = ShuffleStats(sent=sent, dropped=sent - kept)
    return (send_keys[:-1].reshape(n_partitions, capacity),
            send_vals[:-1].reshape((n_partitions, capacity) + values.shape[1:]),
            send_valid[:-1].reshape(n_partitions, capacity),
            stats)


def exchange(send_keys: jax.Array, send_values: jax.Array,
             send_valid: jax.Array, axis_name: str
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The shuffle proper: one tiled all_to_all per tensor over the mesh axis.

    Row p of the send buffer goes to device p; row q of the result came from
    device q — i.e. every reducer receives one 'spill file' from every mapper,
    in a single ICI collective instead of 2·M·R object-store transfers.
    """
    a2a = partial(jax.lax.all_to_all, axis_name=axis_name,
                  split_axis=0, concat_axis=0, tiled=True)
    return a2a(send_keys), a2a(send_values), a2a(send_valid)


# ---------------------------------------------------------------------------
# Whole-shuffle compositions
# ---------------------------------------------------------------------------

def shuffle_group(keys: jax.Array, values: jax.Array, axis_name: str,
                  n_partitions: int, capacity: int,
                  valid: jax.Array | None = None
                  ) -> tuple[jax.Array, jax.Array, jax.Array, ShuffleStats]:
    """Grouping shuffle: exchange + merge.  Per device returns the key-sorted,
    group-marked record stream for this device's partition."""
    sk, sv, svalid, stats = build_send_buffers(keys, values, n_partitions,
                                               capacity, valid)
    rk, rv, rvalid = exchange(sk, sv, svalid, axis_name)
    rk = rk.reshape(-1)
    rv = rv.reshape((-1,) + rv.shape[2:])
    rvalid = rvalid.reshape(-1)
    out_k, out_v, starts = sort_and_group(rk, rv, rvalid)
    return out_k, out_v, starts, stats


def resolve_combine_fn(combine_fn):
    """Resolve a combiner spec to a callable: ``None`` → the dense jnp
    combiner, the string ``"pallas"`` → the ``kernels/hash_combine`` one-hot
    MXU kernel (interpret mode off-TPU), a callable passes through — so
    pipeline configs can name the kernel without importing it."""
    if combine_fn == "pallas":
        from ..kernels.hash_combine.ops import make_combine_fn
        return make_combine_fn(use_pallas=True)
    return combine_fn or local_combine_dense


def shuffle_aggregate(keys: jax.Array, values: jax.Array, axis_name: str,
                      num_buckets: int, valid: jax.Array | None = None,
                      combine_fn=None) -> jax.Array:
    """Aggregating shuffle: local combine (the combiner) + reduce_scatter.

    Each device returns its contiguous ``num_buckets / P`` slice of the fully
    reduced bucket vector — hash-partitioned ownership, exactly the paper's
    reducer assignment, fused into one collective.
    ``combine_fn(keys, values, num_buckets, valid)`` defaults to the dense jnp
    combiner; the Pallas ``hash_combine`` kernel slots in through this hook
    (pass its ``make_combine_fn(...)`` product, or just ``"pallas"``).
    """
    combine_fn = resolve_combine_fn(combine_fn)
    local = combine_fn(keys, values, num_buckets, valid)
    # reduce_scatter: sum over devices, scatter bucket ranges
    return jax.lax.psum_scatter(local, axis_name, scatter_dimension=0,
                                tiled=True)


def shuffle_aggregate_windowed(window_slots: jax.Array, keys: jax.Array,
                               values: jax.Array, axis_name: str,
                               n_slots: int, num_buckets: int,
                               valid: jax.Array | None = None,
                               combine_fn=None) -> jax.Array:
    """Windowed aggregating shuffle for the streaming engine.

    Records carry a *window slot* (a bounded ring index for an in-flight
    window) in addition to the bucket key.  The (slot, bucket) pair flattens
    into one dense id space of ``n_slots * num_buckets`` so the whole
    micro-batch still folds through a single fused ``reduce_scatter`` — the
    batch engine's combiner-in-the-collective, carried across batches.

    Each device returns its contiguous slice of the flattened
    ``(n_slots * num_buckets,) + values.shape[1:]`` update vector; the caller
    adds it to the carried window state (same layout).  Requires
    ``(n_slots * num_buckets) %`` axis size ``== 0``.
    """
    flat = window_slots.astype(jnp.int32) * num_buckets + keys.astype(jnp.int32)
    return shuffle_aggregate(flat, values, axis_name, n_slots * num_buckets,
                             valid=valid, combine_fn=combine_fn)


def bucket_owner(num_buckets: int, n_partitions: int) -> np.ndarray:
    """Host helper: which partition owns each bucket id under the aggregating
    shuffle's tiled scatter (contiguous ranges over the padded bucket
    space — see the aggregate padding in engine.plan)."""
    per = -(-num_buckets // n_partitions)
    return np.minimum(np.arange(num_buckets) // per, n_partitions - 1)


# ---------------------------------------------------------------------------
# Open key domains: exact collision accounting
# ---------------------------------------------------------------------------

def distinct_keys_per_bucket(raw_keys: jax.Array, valid: jax.Array | None,
                             axis_name: str, n_workers: int,
                             num_buckets: int) -> jax.Array:
    """Exact global per-bucket distinct-raw-key counts, as one fixed-shape
    SPMD stage.  ``bucket_collisions = max(counts - 1, 0)``.

    Three steps: (1) locally dedupe raw keys (sort + neighbor-compare);
    (2) route every locally-unique key to its owner worker
    (``hash(key) % W``) through the fixed-capacity exchange, so each distinct
    key is counted on exactly one worker; (3) dedupe again (the same key can
    arrive from several workers), bucket with the same hash the data path
    uses, scatter-add ones, and ``psum`` — ownership is disjoint, so the sum
    is exact.  ``INT32_MAX`` is reserved as the invalid sentinel.
    """
    n = raw_keys.shape[0]
    raw_keys = raw_keys.astype(jnp.int32)
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    sk = jnp.sort(jnp.where(valid, raw_keys, INT32_MAX))
    uniq = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    uniq = uniq & (sk != INT32_MAX)
    # capacity n: even if every locally-unique key hashes to one owner, the
    # per-destination buffer holds them all — the exchange cannot drop
    send_k, _, send_ok, _ = build_send_buffers(
        sk, jnp.zeros((n,), jnp.float32), n_workers, n, valid=uniq)
    a2a = partial(jax.lax.all_to_all, axis_name=axis_name,
                  split_axis=0, concat_axis=0, tiled=True)
    rk = a2a(send_k).reshape(-1)
    rok = a2a(send_ok).reshape(-1)
    rk = jnp.sort(jnp.where(rok, rk, INT32_MAX))
    owned = jnp.concatenate([jnp.ones((1,), bool), rk[1:] != rk[:-1]])
    owned = owned & (rk != INT32_MAX)
    buckets = bucketize(rk, num_buckets, hashed=True)
    buckets = jnp.where(owned, buckets, num_buckets)
    counts = jnp.zeros((num_buckets + 1,), jnp.int32).at[buckets].add(
        owned.astype(jnp.int32))[:num_buckets]
    return jax.lax.psum(counts, axis_name)


# ---------------------------------------------------------------------------
# Built-in segment reducers for grouping mode
# ---------------------------------------------------------------------------

#: built-in grouping reducer kinds — the single source of truth for
#: ``segment_reduce`` dispatch and config validation
SEGMENT_REDUCE_KINDS = ("sum", "max", "min", "count", "mean")


def segment_reduce(kind: str, keys: jax.Array, values: jax.Array,
                   starts: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Reduce a key-sorted, group-marked stream.

    Returns dense (group_keys, group_values, group_valid) of the same length
    as the input stream (padded with invalid groups) — static shapes, as TPU
    requires.  ``kind`` ∈ ``SEGMENT_REDUCE_KINDS``.
    """
    n = keys.shape[0]
    valid = keys != INT32_MAX
    seg = jnp.cumsum(starts) - 1
    seg = jnp.where(valid, seg, n)  # park invalid records on overflow row
    if kind in ("sum", "mean", "count"):
        sums = jax.ops.segment_sum(values, seg, num_segments=n + 1)
        counts = jax.ops.segment_sum(jnp.ones((n,), values.dtype), seg,
                                     num_segments=n + 1)
        if kind == "sum":
            out_v = sums
        elif kind == "count":
            out_v = counts.reshape((n + 1,) + (1,) * (values.ndim - 1)) \
                if values.ndim > 1 else counts
        else:
            out_v = sums / jnp.maximum(
                counts.reshape((-1,) + (1,) * (values.ndim - 1)), 1.0)
    elif kind == "max":
        out_v = jax.ops.segment_max(values, seg, num_segments=n + 1)
    elif kind == "min":
        out_v = jax.ops.segment_min(values, seg, num_segments=n + 1)
    else:
        raise ValueError(f"unknown segment reducer {kind!r}")

    group_keys = jnp.full((n + 1,), -1, dtype=jnp.int32).at[seg].max(
        jnp.where(valid, keys, -1))
    group_valid = group_keys[:n] >= 0
    out_v = out_v[:n]
    out_v = jnp.where(
        group_valid.reshape((-1,) + (1,) * (out_v.ndim - 1)),
        out_v, jnp.zeros_like(out_v))
    return group_keys[:n], out_v, group_valid


def apply_reduce_fn(reduce_fn, keys: jax.Array, values: jax.Array,
                    starts: jax.Array):
    """Dispatch a grouping reducer: built-in kind name or a user callable
    with the ``(keys, values, starts) -> (gk, gv, gvalid)`` contract."""
    if isinstance(reduce_fn, str):
        return segment_reduce(reduce_fn, keys, values, starts)
    return reduce_fn(keys, values, starts)


# ---------------------------------------------------------------------------
# Fixed-capacity top-k / heavy hitters over a dense aggregate
# ---------------------------------------------------------------------------

def bucket_rank_values(agg: jax.Array, kind: str) -> jax.Array:
    """Per-bucket ranking value from a ``(buckets, >=2)`` [sum, count]
    aggregate (or a 1-D sum vector): the quantity ``top_k_buckets`` orders
    by.  ``kind`` ∈ count | sum | mean (1-D input ranks by the vector)."""
    if agg.ndim == 1:
        return agg
    sums, counts = agg[..., 0], agg[..., 1]
    if kind == "count":
        return counts
    if kind == "sum":
        return sums
    if kind == "mean":
        return sums / jnp.maximum(counts, 1.0)
    raise ValueError(f"unknown top-k ranking kind {kind!r}")


def top_k_buckets(agg: jax.Array, k: int, kind: str = "sum"
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Exact top-k over a dense per-bucket aggregate — the heavy-hitters
    reduce as a fixed-capacity selection instead of a full sort + truncate.

    On closed (dense) key domains this is exact; on hashed domains it ranks
    buckets, i.e. heavy hitters up to collision merging.  Empty buckets
    (count 0, or value 0 for 1-D aggregates) never outrank occupied ones and
    come back invalid.  Ties break toward the lower bucket id
    (``jax.lax.top_k`` order), deterministically.

    Returns ``(bucket_ids, values, valid)`` of length ``k``.
    """
    values = bucket_rank_values(agg, kind)
    occupied = (agg[..., 1] > 0) if agg.ndim > 1 else (values != 0)
    masked = jnp.where(occupied, values, -jnp.inf)
    top_vals, top_ids = jax.lax.top_k(masked, k)
    valid = top_vals > -jnp.inf
    return (top_ids.astype(jnp.int32),
            jnp.where(valid, top_vals, 0.0), valid)


# ---------------------------------------------------------------------------
# Carry handoff (multi-stage chains + DAG fan-out: one plan's finalized
# windows feed one or more successor plans, one call per edge)
# ---------------------------------------------------------------------------

def carry_handoff_rows(agg: jax.Array, relabel: jax.Array,
                       last_window: jax.Array, n_windows: jax.Array,
                       kind: str, n_rows: int,
                       channel_base: int = 0) -> jax.Array:
    """One finalized window's dense aggregate → a successor plan's wire
    rows.  Pure per-edge function: a teed stage runs it once per out-edge
    with that edge's own ``relabel`` table, fanning the same slot into
    several downstream carries.

    ``agg`` is the (num_buckets, channels) slice of a finalized window;
    its ``[sum, count]`` pair lives at ``channel_base``.  Each occupied
    bucket becomes one device-fan-out wire row ``[last_window, n_windows,
    key, value, valid]`` for the *next* stage's plan: ``relabel`` maps
    this plan's bucket ids to the next key space (a dense id or a raw
    hashed-wire id; ``< 0`` marks unassigned buckets), ``last_window`` /
    ``n_windows`` are the re-windowed span of the finalized window's
    timestamp (scalars — every row of one handoff shares them), and the
    value is the finalized aggregate per ``kind`` (count | sum | mean).
    Output is padded to ``n_rows`` with invalid rows, so the next plan's
    step compiles once.  The emitted aggregates never visit the host —
    this is the reduce → map → window → reduce seam of a multi-stage
    chain.
    """
    sums = agg[:, channel_base]
    counts = agg[:, channel_base + 1]
    if kind == "count":
        value = counts
    elif kind == "sum":
        value = sums
    elif kind == "mean":
        value = sums / jnp.maximum(counts, 1.0)
    else:
        raise ValueError(f"unknown handoff aggregate kind {kind!r}")
    valid = (counts > 0) & (relabel >= 0)
    n = agg.shape[0]
    last = jnp.full((n,), last_window, jnp.float32)
    nw = jnp.full((n,), n_windows, jnp.float32)
    rows = jnp.stack([last, nw, relabel.astype(jnp.float32),
                      value.astype(jnp.float32),
                      valid.astype(jnp.float32)], axis=-1)
    return jnp.zeros((n_rows, 5), jnp.float32).at[:n].set(rows)


# ---------------------------------------------------------------------------
# On-device sliding-window fan-out (broadcast + iota)
# ---------------------------------------------------------------------------

def window_fanout(last_index: jax.Array, n_windows: jax.Array,
                  keys: jax.Array, values: jax.Array, valid: jax.Array,
                  fanout: int, n_slots: int, min_window: jax.Array):
    """Replicate each record into its overlapping windows on-chip.

    A record crosses host→device once, carrying only the index of the last
    (latest-starting) window containing it and how many consecutive windows
    do (1..fanout) — pure host float64 boundary math, no expansion.  The
    stage broadcasts every record ``fanout`` ways and masks with iota
    arithmetic: copy j covers window ``last_index - j`` and is live when
    ``j < n_windows``; windows below ``min_window`` already finalized, so
    those copies are masked late (and counted, for the watermark books).
    Ring slots are modular (``window % n_slots``) — the host tracker uses
    the same rule, so no slot table crosses the boundary.

    Returns flattened ``(n * fanout,)`` (slots, keys, values, live) plus
    scalar (late_pairs, admitted_pairs) counters.
    """
    n = last_index.shape[0]
    j = jax.lax.iota(jnp.int32, fanout)                       # (F,)
    widx = last_index.astype(jnp.int32)[:, None] - j[None, :]  # (n, F)
    covers = valid[:, None] & (j[None, :] < n_windows.astype(jnp.int32)[:, None])
    live = covers & (widx >= min_window)
    late = jnp.sum((covers & (widx < min_window)).astype(jnp.int32))
    slots = jnp.mod(widx, n_slots)
    keys_f = jnp.broadcast_to(keys.astype(jnp.int32)[:, None], (n, fanout))
    vshape = (n, fanout) + values.shape[1:]
    values_f = jnp.broadcast_to(values[:, None], vshape)
    return (slots.reshape(-1), keys_f.reshape(-1),
            values_f.reshape((n * fanout,) + values.shape[1:]),
            live.reshape(-1), late, jnp.sum(live.astype(jnp.int32)))


# ---------------------------------------------------------------------------
# Windowed group-mode record buffers (fixed-capacity, carried across batches)
# ---------------------------------------------------------------------------

def append_window_records(keys_buf: jax.Array, vals_buf: jax.Array,
                          counts: jax.Array, flat_keys: jax.Array,
                          values: jax.Array, valid: jax.Array,
                          n_slots: int, capacity: int, num_buckets: int):
    """Append exchanged (slot, bucket) records into per-slot ring buffers.

    ``keys_buf`` (n_slots, capacity) int32 (INVALID = empty), ``vals_buf``
    (n_slots, capacity), ``counts`` (n_slots,) — this worker's slice of the
    grouping carry.  Incoming records are slot-sorted so each record's write
    position is ``counts[slot] + rank_within_slot``; overflow beyond
    ``capacity`` is dropped and counted (the spill-file size bound).
    Returns (keys_buf, vals_buf, counts, dropped).
    """
    m = flat_keys.shape[0]
    slot = jnp.where(valid, flat_keys // num_buckets, jnp.int32(n_slots))
    key = jnp.mod(flat_keys, num_buckets)
    order = jnp.argsort(slot, stable=True)
    s = slot[order]
    k = key[order]
    v = jnp.take(values, order, axis=0)
    per_slot = jnp.bincount(s, length=n_slots + 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(per_slot)[:-1].astype(jnp.int32)])
    base = jnp.concatenate([counts, jnp.zeros((1,), counts.dtype)])
    pos = base[s] + (jnp.arange(m, dtype=jnp.int32) - offsets[s])
    ok = (s < n_slots) & (pos < capacity)
    dst = jnp.where(ok, s * capacity + pos, n_slots * capacity)

    kb = jnp.concatenate([keys_buf.reshape(-1), jnp.full((1,), INVALID)])
    kb = kb.at[dst].set(jnp.where(ok, k, INVALID))
    vb = jnp.concatenate(
        [vals_buf.reshape((-1,) + vals_buf.shape[2:]),
         jnp.zeros((1,) + vals_buf.shape[2:], vals_buf.dtype)])
    vb = vb.at[dst].set(jnp.where(
        ok.reshape((-1,) + (1,) * (v.ndim - 1)), v, jnp.zeros_like(v)))
    new_counts = jnp.minimum(counts + per_slot[:n_slots].astype(counts.dtype),
                             capacity)
    dropped = jnp.sum(per_slot[:n_slots]).astype(jnp.int32) - \
        jnp.sum(ok.astype(jnp.int32))
    return (kb[:-1].reshape(n_slots, capacity),
            vb[:-1].reshape((n_slots, capacity) + vals_buf.shape[2:]),
            new_counts, dropped)


def gather_window_group(keys_buf: jax.Array, vals_buf: jax.Array,
                        slot, axis_name: str, reduce_fn):
    """Finalize one window of the grouping carry: gather the slot's buffered
    records from every worker (the Finalizer's stream-concat, as one
    ``all_gather``), merge-sort, and run the grouping reducer over each
    key's full value list.  Replicated output."""
    k = jax.lax.dynamic_slice_in_dim(keys_buf, slot, 1, axis=0)[0]
    v = jax.lax.dynamic_slice_in_dim(vals_buf, slot, 1, axis=0)[0]
    gk = jax.lax.all_gather(k, axis_name, tiled=True)
    gv = jax.lax.all_gather(v, axis_name, tiled=True)
    sk, sv, starts = sort_and_group(gk, gv, valid=gk >= 0)
    return apply_reduce_fn(reduce_fn, sk, sv, starts)


def clear_window_group(keys_buf: jax.Array, vals_buf: jax.Array,
                       counts: jax.Array, slot):
    """Reset one slot of the grouping carry so its ring slot can be reused."""
    keys_buf = jax.lax.dynamic_update_slice_in_dim(
        keys_buf, jnp.full((1,) + keys_buf.shape[1:], INVALID), slot, axis=0)
    vals_buf = jax.lax.dynamic_update_slice_in_dim(
        vals_buf, jnp.zeros((1,) + vals_buf.shape[1:], vals_buf.dtype),
        slot, axis=0)
    counts = jax.lax.dynamic_update_slice(
        counts, jnp.zeros((1,), counts.dtype), (slot,))
    return keys_buf, vals_buf, counts

"""Execution plans — one declarative layer over batch and streaming.

A MapReduce job on the device plane is a point in a small product space:

    ``KeySpace``  ×  ``WindowSpec``  ×  ``ReduceSpec``  →  backend lowering

``KeySpace`` says how raw keys become bucket ids (dense pre-assigned ids, or
hashed open domains with exact collision accounting).  ``WindowSpec`` says
whether records carry event-time windows and whether the sliding-window
fan-out happens on-device (broadcast + iota in ``stages.window_fanout``) or
was already done by the host.  ``ReduceSpec`` says how values reduce: the
*aggregate* mode (commutative/associative — combiner fused into one
``reduce_scatter``) or the *group* mode (arbitrary ``reduce_fn`` over each
key's full value list via the fixed-capacity ``all_to_all``).

``ExecutionPlan.compile`` lowers one plan to one of three backends
(``vmap`` — simulated workers on one device, ``shard_map`` — a real mesh
axis, ``pallas`` — the streaming aggregate fold as one fused
``kernels/fused_fold`` kernel over a single flat carry slab) and returns a
compiled object: ``run`` for one-shot batch jobs, or
``init_carry`` / ``step`` / ``read_slot`` / ``finalize_slot`` /
``clear_slot`` for streaming.  Batch one-shot, streaming incremental,
aggregate, and group are all lowerings of this one layer — there is no
second engine.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import stages
from .compile import lower
from .stages import ShuffleStats

P = jax.sharding.PartitionSpec

INT32_MIN = -(2 ** 31)
INT32_MAX = 2 ** 31 - 1

#: host→device wire formats for streaming micro-batch rows
HOST_FANOUT_ROW = 4     # [window_slot, key, value, valid]
DEVICE_FANOUT_ROW = 5   # [last_window_index, n_windows, key, value, valid]


# ---------------------------------------------------------------------------
# The plan vocabulary
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class KeySpace:
    """How raw map keys become bucket ids in ``[0, num_buckets)``.

    ``dense`` — keys already are bucket ids (the data layer assigned them);
    exceeding ``num_buckets`` is a caller error.  ``hashed`` — keys come
    from an open, unbounded domain and are folded in with ``device_hash``;
    distinct keys may collide, and with ``track_collisions`` the engine
    counts them exactly per bucket (``ShuffleStats.bucket_collisions``), so
    unbounded key sets degrade gracefully instead of raising.
    """

    num_buckets: int
    mode: str = "dense"             # "dense" | "hashed"
    track_collisions: bool = True

    @classmethod
    def dense(cls, num_buckets: int) -> "KeySpace":
        return cls(num_buckets, "dense")

    @classmethod
    def hashed(cls, num_buckets: int,
               track_collisions: bool = True) -> "KeySpace":
        return cls(num_buckets, "hashed", track_collisions)

    @property
    def is_hashed(self) -> bool:
        return self.mode == "hashed"

    def padded(self, n_workers: int) -> int:
        """Bucket space padded to a multiple of the axis size so the tiled
        reduce_scatter divides evenly; pad rows stay zero."""
        return -(-self.num_buckets // n_workers) * n_workers


@dataclass(frozen=True)
class WindowSpec:
    """Event-time windowing as the device engine sees it.

    ``kind="fixed"`` (tumbling/sliding): ``slide=None`` means tumbling
    (fan-out 1).  ``fanout_on_device=True`` ships one 5-column row per
    record and replicates it into its ``ceil(size/slide)`` windows on-chip;
    ``False`` is the legacy host fan-out wire format (one 4-column row per
    record × window).  Ring slots are addressed modularly — window ``w``
    lives in slot ``w % n_slots`` on host and device alike.  Window
    *indices* on the wire are caller-rebased (the coordinator subtracts a
    per-batch base that is a multiple of ``n_slots``), so they stay exact
    in float32 regardless of absolute event time; the fan-out stage only
    ever sees the rebased values.

    ``kind="session"``: data-dependent gap windows.  Session boundaries are
    inherently host-side (they depend on the observed event times per key),
    so session plans use the host wire format with fan-out 1; the host maps
    each open session to a carry *cell* — a (ring slot, bucket) pair — and
    merges bridged sessions with the cell ops on the compiled plan
    (``merge_cell`` / ``clear_cell``).  ``gap`` is the inactivity gap that
    closes a session.
    """

    size: float
    slide: float | None = None
    n_slots: int = 2
    fanout_on_device: bool = True
    kind: str = "fixed"             # "fixed" | "session"
    gap: float = 0.0

    @classmethod
    def session(cls, gap: float, n_slots: int = 8) -> "WindowSpec":
        """Gap-based session windows — a new plan variant, not a new
        engine: the aggregate fold and carry are unchanged, only cell
        addressing and finalization differ."""
        return cls(size=0.0, slide=None, n_slots=n_slots,
                   fanout_on_device=False, kind="session", gap=gap)

    @property
    def is_session(self) -> bool:
        return self.kind == "session"

    @property
    def fanout(self) -> int:
        """Max windows per record — the on-chip replication factor."""
        if self.slide is None:
            return 1
        return math.ceil(self.size / self.slide)


@dataclass(frozen=True)
class ReduceSpec:
    """How values reduce within a (window ×) key group.

    ``aggregate`` — commutative/associative; ``combine_fn(keys, values,
    num_buckets, valid)`` pre-reduces locally (dense jnp combiner by
    default, the Pallas kernel slots in here) and one ``reduce_scatter``
    finishes.  ``group`` — arbitrary ``reduce_fn`` (a segment-reducer kind
    name or a ``(keys, values, starts) -> (gk, gv, gvalid)`` callable) over
    each key's full, exchanged value list; ``capacity`` bounds the
    per-partition record buffers (the spill-file size bound).  ``top_k`` —
    the aggregate fold plus a fixed-capacity heavy-hitters selection at
    finalization (``stages.top_k_buckets``); ``k`` bounds the output.

    ``channels`` / ``channel_base`` let several plans share one aggregate
    carry: each plan folds its ``[value, 1]`` pair into channels
    ``[channel_base, channel_base + 1]`` of a ``channels``-wide carry and
    leaves the rest untouched — the windowed-join wiring, where the left
    and right stream are two compiled plans over disjoint channel pairs of
    the same carry.  ``carry_buckets`` widens the carry's *bucket* axis
    past the plan's own key space (0 → the key space width): plans with
    asymmetric per-side key spaces (join key-space asymmetry) each
    bucketize within their own ``KeySpace.num_buckets`` but flatten window
    slots over the shared ``carry_buckets`` width, so both sides address
    one carry without their bucket ranges drifting.
    """

    mode: str = "aggregate"         # "aggregate" | "group" | "top_k"
    reduce_fn: str | Callable = "sum"
    combine_fn: str | Callable | None = None  # "pallas" names the kernel
    capacity: int = 0
    k: int = 0                      # top_k mode: selection capacity
    channels: int = 2               # carry width (2 per resident plan)
    channel_base: int = 0           # this plan's [sum, count] offset
    carry_buckets: int = 0          # shared carry bucket width (0 → own)

    @classmethod
    def top_k(cls, k: int) -> "ReduceSpec":
        return cls(mode="top_k", k=k)

    @property
    def folds_as_aggregate(self) -> bool:
        """top_k folds with the aggregate machinery; only finalization
        differs."""
        return self.mode in ("aggregate", "top_k")


@dataclass(frozen=True)
class ExecutionPlan:
    """One device MapReduce job, declaratively.  ``compile()`` lowers it."""

    key_space: KeySpace
    reduce: ReduceSpec
    n_workers: int
    window: WindowSpec | None = None
    axis_name: str = "workers"

    @property
    def carry_buckets(self) -> int:
        """Bucket width of the carry this plan folds into — the plan's own
        key space unless ``ReduceSpec.carry_buckets`` widens it (per-side
        key-space asymmetry over one shared carry)."""
        return self.reduce.carry_buckets or self.key_space.num_buckets

    def compile(self, map_fn: Callable | None = None, *,
                backend: str = "vmap",
                mesh: jax.sharding.Mesh | None = None,
                data_spec=None, finalize: bool = True, jit: bool = True):
        """Lower to an executable.  Batch plans (``window=None``) return a
        ``CompiledBatchPlan``; windowed plans return a streaming plan with a
        carry (``CompiledStreamAggregate`` or ``CompiledStreamGroup``)."""
        rs = self.reduce
        if rs.mode not in ("aggregate", "group", "top_k"):
            raise ValueError(f"unknown reduce mode {rs.mode!r}")
        if rs.mode == "group" and rs.capacity <= 0:
            raise ValueError("grouping mode needs a positive capacity")
        if rs.mode == "top_k" and rs.k < 1:
            raise ValueError("top_k mode needs k >= 1")
        if rs.mode == "top_k" and rs.channel_base != 0:
            raise ValueError("top_k ranks channels [0, 2) — it cannot "
                             "share a carry at a nonzero channel_base")
        if rs.channels < 2 or rs.channel_base + 2 > rs.channels:
            raise ValueError("channel window [base, base+2) must fit the "
                             "carry's channel count")
        if rs.carry_buckets and rs.carry_buckets < self.key_space.num_buckets:
            raise ValueError("carry_buckets must cover the plan's own key "
                             "space (carry width >= num_buckets)")
        if self.window is not None and self.window.is_session:
            if self.window.gap <= 0:
                raise ValueError("session windows need a positive gap")
            if self.window.fanout_on_device or rs.mode != "aggregate":
                raise ValueError("session windows lower to the host-wire "
                                 "aggregate fold (fan-out 1) only")
        if backend == "pallas" and self.window is not None:
            if rs.mode == "group":
                raise ValueError("backend='pallas' fuses the aggregate "
                                 "fold; group-mode plans (record buffers + "
                                 "all_to_all) lower via vmap/shard_map")
            if rs.combine_fn is not None:
                raise ValueError("backend='pallas' is already the fused "
                                 "combiner; combine_fn does not apply")
        if self.window is None:
            if map_fn is None:
                raise ValueError("batch plans need a map_fn")
            if rs.mode == "top_k" and not finalize:
                raise ValueError("batch top_k selects over the finalized "
                                 "bucket vector; finalize=False is "
                                 "contradictory")
            return CompiledBatchPlan(self, map_fn, backend, mesh, data_spec,
                                     finalize, jit)
        if self.window.fanout_on_device and self.window.size <= 0:
            raise ValueError("on-device fan-out needs a positive window size")
        if rs.mode == "group":
            if self.window.fanout_on_device is False:
                raise ValueError("windowed group mode runs with on-device "
                                 "fan-out only")
            return CompiledStreamGroup(self, backend, mesh, jit)
        return CompiledStreamAggregate(self, map_fn, backend, mesh, jit)


def streaming_record_map(shard):
    """Host-fan-out wire decode: shard is a (records, 4) float32 array of
    [window_slot, key, value, valid] rows.  Emits (sum, count) value
    channels so count / sum / mean all come out of one carried state."""
    slots = shard[:, 0].astype(jnp.int32)
    keys = shard[:, 1].astype(jnp.int32)
    valid = shard[:, 3] > 0
    values = jnp.stack([shard[:, 2], jnp.ones_like(shard[:, 2])], axis=-1)
    return slots, keys, values, valid


def _decode_device_rows(rows):
    """Device-fan-out wire decode: (records, 5) float32 rows of
    [last_window_index, n_windows, key, value, valid]."""
    return (rows[:, 0].astype(jnp.int32), rows[:, 1].astype(jnp.int32),
            rows[:, 2].astype(jnp.int32), rows[:, 3], rows[:, 4] > 0)


# ---------------------------------------------------------------------------
# Batch lowering (one-shot jobs)
# ---------------------------------------------------------------------------

def _batch_body(shard, *, plan: ExecutionPlan, map_fn, finalize: bool):
    ks, rs = plan.key_space, plan.reduce
    keys, values, valid = map_fn(shard)
    raw = keys.astype(jnp.int32)
    buckets = stages.bucketize(raw, ks.num_buckets, hashed=ks.is_hashed)
    if ks.is_hashed and ks.track_collisions:
        distinct = stages.distinct_keys_per_bucket(
            raw, valid, plan.axis_name, plan.n_workers, ks.num_buckets)
        collisions = jnp.maximum(distinct - 1, 0)
    else:
        collisions = None

    if rs.folds_as_aggregate:
        part = stages.shuffle_aggregate(
            buckets, values, plan.axis_name, ks.padded(plan.n_workers),
            valid=valid, combine_fn=rs.combine_fn)
        sent = jax.lax.psum(jnp.sum(valid.astype(jnp.int32)), plan.axis_name)
        stats = ShuffleStats(sent, jnp.zeros((), jnp.int32), collisions)
        if finalize:
            # Finalizer: concatenate every reducer's slice into one object —
            # all_gather is the collective form of §III-A.5's stream-concat.
            return jax.lax.all_gather(part, plan.axis_name, tiled=True), stats
        return part, stats

    out_k, out_v, starts, xstats = stages.shuffle_group(
        buckets, values, plan.axis_name, plan.n_workers, rs.capacity,
        valid=valid)
    gk, gv, gvalid = stages.apply_reduce_fn(rs.reduce_fn, out_k, out_v, starts)
    stats = ShuffleStats(jax.lax.psum(xstats.sent, plan.axis_name),
                         jax.lax.psum(xstats.dropped, plan.axis_name),
                         collisions)
    if finalize:
        gather = partial(jax.lax.all_gather, axis_name=plan.axis_name,
                         tiled=True)
        return (gather(gk), gather(gv), gather(gvalid)), stats
    return (gk, gv, gvalid), stats


class CompiledBatchPlan:
    """One-shot lowering: ``run(data) -> (result, ShuffleStats)``.

    Aggregate result is the (padded) dense bucket vector; group result is
    the ``(group_keys, group_values, group_valid)`` triple.  ``finalize``
    gathers every reducer's slice into one replicated object.
    """

    def __init__(self, plan, map_fn, backend, mesh, data_spec, finalize, jit):
        self.plan = plan
        body = partial(_batch_body, plan=plan, map_fn=map_fn,
                       finalize=finalize)
        axis = plan.axis_name
        in_spec = data_spec if data_spec is not None else P(axis)
        rspec = P() if finalize else P(axis)
        if plan.reduce.folds_as_aggregate:
            out_specs = (rspec, P())
        else:
            out_specs = ((rspec, rspec, rspec), P())
        self._fn = lower(body, axis_name=axis, in_specs=(in_spec,),
                         out_specs=out_specs, backend=backend, mesh=mesh,
                         jit=jit)

    def run(self, data):
        out, stats = self._fn(data)
        if self.plan.reduce.mode == "top_k":
            # the heavy-hitters selection over the (unpadded) bucket vector
            rank_kind = self.plan.reduce.reduce_fn \
                if isinstance(self.plan.reduce.reduce_fn, str) else "sum"
            out = _select_top_k(out, self.plan.key_space.num_buckets,
                                self.plan.reduce.k, rank_kind)
        return out, stats


# ---------------------------------------------------------------------------
# Streaming lowerings (carried window state, one fused collective per batch)
# ---------------------------------------------------------------------------

def _stream_agg_host_body(shard, carry_slice, *, plan: ExecutionPlan, map_fn):
    """Legacy wire format: the host already expanded records into (slot,
    key) rows; the device folds one micro-batch into the carry."""
    ks = plan.key_space
    slots, keys, values, valid = map_fn(shard)
    buckets = stages.bucketize(keys, ks.num_buckets, hashed=ks.is_hashed)
    part = stages.shuffle_aggregate_windowed(
        slots, buckets, values, plan.axis_name, plan.window.n_slots,
        plan.carry_buckets, valid=valid, combine_fn=plan.reduce.combine_fn)
    folded = jax.lax.psum(jnp.sum(valid.astype(jnp.int32)), plan.axis_name)
    stats = jnp.stack([jnp.zeros((), jnp.int32), folded,
                       jnp.zeros((), jnp.int32)])
    return carry_slice + part, stats


def _embed_channels(vals: jax.Array, n_channels: int,
                    base: int) -> jax.Array:
    """Place a record's ``[value, 1]`` pair at channels ``[base, base+1]``
    of an ``n_channels``-wide value vector, zero elsewhere — how plans
    sharing a carry (windowed joins) stay out of each other's channels."""
    cols = [jnp.zeros_like(vals)] * n_channels
    cols[base] = vals
    cols[base + 1] = jnp.ones_like(vals)
    return jnp.stack(cols, axis=-1)


def _stream_agg_device_body(rows, carry_slice, min_window, *,
                            plan: ExecutionPlan):
    """Fan-out-on-device wire format: one row per record; the stage
    replicates it into its windows on-chip and folds in the same fused
    reduce_scatter."""
    ks, ws = plan.key_space, plan.window
    last, nw, keys, vals, valid = _decode_device_rows(rows)
    buckets = stages.bucketize(keys, ks.num_buckets, hashed=ks.is_hashed)
    values = _embed_channels(vals, plan.reduce.channels,
                             plan.reduce.channel_base)
    slots, keys_f, vals_f, live, late, expanded = stages.window_fanout(
        last, nw, buckets, values, valid, ws.fanout, ws.n_slots, min_window)
    part = stages.shuffle_aggregate_windowed(
        slots, keys_f, vals_f, plan.axis_name, ws.n_slots, plan.carry_buckets,
        valid=live, combine_fn=plan.reduce.combine_fn)
    stats = jnp.stack([jax.lax.psum(late, plan.axis_name),
                       jax.lax.psum(expanded, plan.axis_name),
                       jnp.zeros((), jnp.int32)])
    return carry_slice + part, stats


@partial(jax.jit, static_argnums=(2,))
def _gather_flat_slot(flat: jax.Array, slot, num_buckets: int) -> jax.Array:
    start = (slot * num_buckets,) + (0,) * (flat.ndim - 1)
    return jax.lax.dynamic_slice(flat, start,
                                 (num_buckets,) + flat.shape[1:])


@partial(jax.jit, static_argnums=(2,))
def _clear_flat_slot(flat: jax.Array, slot, num_buckets: int) -> jax.Array:
    zeros = jnp.zeros((num_buckets,) + flat.shape[1:], flat.dtype)
    start = (slot * num_buckets,) + (0,) * (flat.ndim - 1)
    return jax.lax.dynamic_update_slice(flat, zeros, start)


@partial(jax.jit, static_argnums=(3,))
def _gather_flat_cell(flat: jax.Array, slot, bucket,
                      num_buckets: int) -> jax.Array:
    start = (slot * num_buckets + bucket,) + (0,) * (flat.ndim - 1)
    return jax.lax.dynamic_slice(flat, start, (1,) + flat.shape[1:])[0]


@partial(jax.jit, static_argnums=(4,))
def _merge_flat_cell(flat: jax.Array, src_slot, dst_slot, bucket,
                     num_buckets: int) -> jax.Array:
    src = (src_slot * num_buckets + bucket,) + (0,) * (flat.ndim - 1)
    dst = (dst_slot * num_buckets + bucket,) + (0,) * (flat.ndim - 1)
    row_shape = (1,) + flat.shape[1:]
    src_row = jax.lax.dynamic_slice(flat, src, row_shape)
    dst_row = jax.lax.dynamic_slice(flat, dst, row_shape)
    flat = jax.lax.dynamic_update_slice(flat, src_row + dst_row, dst)
    return jax.lax.dynamic_update_slice(
        flat, jnp.zeros(row_shape, flat.dtype), src)


@partial(jax.jit, static_argnums=(3,))
def _clear_flat_cell(flat: jax.Array, slot, bucket,
                     num_buckets: int) -> jax.Array:
    start = (slot * num_buckets + bucket,) + (0,) * (flat.ndim - 1)
    return jax.lax.dynamic_update_slice(
        flat, jnp.zeros((1,) + flat.shape[1:], flat.dtype), start)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _select_top_k(agg: jax.Array, num_buckets: int, k: int, kind: str):
    return stages.top_k_buckets(agg[:num_buckets], k, kind)


def _carry_placement(backend: str, mesh, axis: str):
    """Where a fresh carry lives: split over the mesh axis under shard_map,
    as every step returns it (so the first step's donation is usable and
    no device holds the whole slab); the default device otherwise."""
    if backend == "shard_map":
        return jax.sharding.NamedSharding(mesh, P(axis))
    return None


def _flat_carry(carry: jax.Array) -> tuple[jax.Array, tuple]:
    """View a (possibly vmap-batched) aggregate carry as its flattened
    (n_slots * num_buckets, channels) id space."""
    shape = carry.shape
    flat = carry.reshape((-1,) + shape[2:]) if carry.ndim == 3 else carry
    return flat, shape


def gather_window_slot(carry: jax.Array, slot: int,
                       num_buckets: int) -> np.ndarray:
    """Gather one finalized window's dense (num_buckets, channels) aggregate
    from the scattered carry.  Slices on device so only the window's rows —
    not the whole carry — cross to the host."""
    flat, _ = _flat_carry(carry)
    return np.asarray(_gather_flat_slot(flat, jnp.int32(slot), num_buckets))


def clear_window_slot_carry(carry: jax.Array, slot: int,
                            num_buckets: int) -> jax.Array:
    """Zero a finalized window's slice so its ring slot can be reused."""
    flat, shape = _flat_carry(carry)
    flat = _clear_flat_slot(flat, jnp.int32(slot), num_buckets)
    return flat.reshape(shape)


def read_window_cell(carry: jax.Array, slot: int, bucket: int,
                     num_buckets: int) -> np.ndarray:
    """Read one (slot, bucket) cell's (channels,) aggregate — a finalized
    session's entire state, since a session holds exactly one key."""
    flat, _ = _flat_carry(carry)
    return np.asarray(_gather_flat_cell(flat, jnp.int32(slot),
                                        jnp.int32(bucket), num_buckets))


def merge_window_cell_carry(carry: jax.Array, src_slot: int, dst_slot: int,
                            bucket: int, num_buckets: int) -> jax.Array:
    """Fold one cell's aggregate into another and zero the source — how a
    bridging event merges two open sessions of the same key without the
    carry ever leaving the device."""
    flat, shape = _flat_carry(carry)
    flat = _merge_flat_cell(flat, jnp.int32(src_slot), jnp.int32(dst_slot),
                            jnp.int32(bucket), num_buckets)
    return flat.reshape(shape)


def clear_window_cell_carry(carry: jax.Array, slot: int, bucket: int,
                            num_buckets: int) -> jax.Array:
    """Zero one (slot, bucket) cell so a finalized session's cell frees."""
    flat, shape = _flat_carry(carry)
    flat = _clear_flat_cell(flat, jnp.int32(slot), jnp.int32(bucket),
                            num_buckets)
    return flat.reshape(shape)


class CompiledStreamAggregate:
    """Streaming aggregate lowering: a scattered dense carry over the
    flattened (window_slot, bucket) id space, folded once per micro-batch
    by a single fused ``reduce_scatter``.

    ``step(rows, carry[, min_window]) -> (carry, stats)`` where stats is an
    int32 ``[late_pairs, folded_pairs, 0]`` vector (device-fan-out plans
    mask+count late (record, window) pairs on-chip).  Built once per stream
    so XLA compiles one program for every batch.

    ``backend="pallas"`` swaps the XLA body for the fused
    ``kernels/fused_fold`` kernel: hash, window fan-out, watermark masking
    and the scatter-accumulate all happen in one kernel over a single flat
    ``(n_slots * carry_buckets, channels)`` carry slab (the shard_map wire
    layout, so the coordinator and handoff edges need no new cases); the
    donated-carry step becomes a true in-place update via the kernel's
    ``input_output_aliases``.  Bit-parity with the XLA backends is
    test-enforced.  Interpret-vs-compile follows
    ``compile.default_pallas_interpret`` (interpret off-TPU).
    """

    def __init__(self, plan, map_fn, backend, mesh, jit):
        ws = plan.window
        carry_b = plan.carry_buckets
        if (ws.n_slots * carry_b) % plan.n_workers != 0:
            raise ValueError("n_slots * carry bucket width must divide by "
                             "n_workers")
        self.plan = plan
        self.backend = backend
        self._placement = _carry_placement(backend, mesh, plan.axis_name)
        self._per_worker = (ws.n_slots * carry_b) // plan.n_workers
        axis = plan.axis_name
        if backend == "pallas":
            if not ws.fanout_on_device and map_fn is not None:
                raise ValueError("backend='pallas' decodes the standard "
                                 "host wire in-kernel; a custom map_fn "
                                 "does not apply")
            from ..kernels.fused_fold.ops import make_fold_step
            self._lower_step = partial(
                make_fold_step,
                fanout=ws.fanout if ws.fanout_on_device else 1,
                n_slots=ws.n_slots,
                num_buckets=plan.key_space.num_buckets,
                carry_buckets=carry_b,
                channel_base=plan.reduce.channel_base,
                hashed=plan.key_space.is_hashed,
                host_wire=not ws.fanout_on_device)
        else:
            if ws.fanout_on_device:
                body = partial(_stream_agg_device_body, plan=plan)
                in_specs = (P(axis), P(axis), P())
            else:
                body = partial(_stream_agg_host_body, plan=plan,
                               map_fn=map_fn or streaming_record_map)
                in_specs = (P(axis), P(axis))
            self._lower_step = partial(lower, body, axis_name=axis,
                                       in_specs=in_specs,
                                       out_specs=(P(axis), P()),
                                       backend=backend, mesh=mesh, jit=jit)
        self._step = self._lower_step()
        self._step_donating: Callable | None = None  # lowered on first use
        self._handoffs: dict[tuple, Callable] = {}  # (kind, rows) → handoff

    def init_carry(self, n_channels: int | None = None,
                   dtype=jnp.float32) -> jax.Array:
        """Zeroed carried window state in the scattered layout ``step``
        expects.  Defaults to the plan's channel width, so plans sharing a
        carry (joins) and single-plan streams use the same call."""
        plan = self.plan
        if n_channels is None:
            n_channels = plan.reduce.channels
        if self.backend == "vmap":
            return jnp.zeros((plan.n_workers, self._per_worker, n_channels),
                             dtype)
        return jnp.zeros(
            (plan.window.n_slots * plan.carry_buckets, n_channels), dtype,
            device=self._placement)

    def step(self, rows, carry, min_window: int | None = None, *,
             donate: bool = False):
        """One micro-batch fold.  ``donate=True`` hands the carry buffer to
        XLA for in-place reuse (``donate_argnums``) — the caller must treat
        the passed carry as consumed and keep only the returned one, which
        every streaming drive loop already does (``stage.carry = step(...)``).
        """
        fn = self._donating_step() if donate else self._step
        if self.plan.window.fanout_on_device:
            return fn(rows, carry, jnp.int32(min_window))
        return fn(rows, carry)

    def _donating_step(self) -> Callable:
        """The same lowered step with the carry argument (index 1) donated,
        built lazily so non-streaming users never pay the second trace."""
        if self._step_donating is None:
            self._step_donating = self._lower_step(donate_argnums=(1,))
        return self._step_donating

    def read_slot(self, carry, slot: int) -> np.ndarray:
        return gather_window_slot(carry, slot, self.plan.carry_buckets)

    def clear_slot(self, carry, slot: int) -> jax.Array:
        return clear_window_slot_carry(carry, slot, self.plan.carry_buckets)

    # -- cell ops (session windows: one key per window) ----------------------
    def read_cell(self, carry, slot: int, bucket: int) -> np.ndarray:
        return read_window_cell(carry, slot, bucket,
                                self.plan.carry_buckets)

    def merge_cell(self, carry, src_slot: int, dst_slot: int,
                   bucket: int) -> jax.Array:
        return merge_window_cell_carry(carry, src_slot, dst_slot, bucket,
                                       self.plan.carry_buckets)

    def clear_cell(self, carry, slot: int, bucket: int) -> jax.Array:
        return clear_window_cell_carry(carry, slot, bucket,
                                       self.plan.carry_buckets)

    # -- fixed-capacity heavy hitters ----------------------------------------
    def top_k_slot(self, carry, slot: int, kind: str | None = None
                   ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Select the plan's top-k buckets of one finalized window on
        device: gather the slot's dense aggregate, rank per ``kind``
        (default: the plan's ``reduce_fn`` kind), and keep the k largest.
        Returns ``(bucket_ids, values, valid)`` of length ``plan.reduce.k``.
        """
        rs = self.plan.reduce
        if rs.k < 1:
            raise ValueError("plan has no top-k capacity (reduce.k < 1)")
        if kind is None:
            kind = rs.reduce_fn if isinstance(rs.reduce_fn, str) else "sum"
        flat, _ = _flat_carry(carry)
        agg = _gather_flat_slot(flat, jnp.int32(slot),
                                self.plan.carry_buckets)
        ids, vals, valid = _select_top_k(agg, self.plan.key_space.num_buckets,
                                         rs.k, kind)
        return np.asarray(ids), np.asarray(vals), np.asarray(valid)

    # -- carry handoff (multi-stage chains and DAG fan-out edges) ------------
    def handoff_rows(self, carry, slot: int, relabel: jax.Array,
                     last_window: int, n_windows: int, kind: str,
                     dst_rows: int) -> jax.Array:
        """One finalized window's aggregates as a *successor* plan's wire
        rows — the reduce → map → window → reduce seam, entirely on
        device.  A teed stage calls this once per out-edge with that
        edge's own ``relabel`` table (and the destination's ``dst_rows``),
        so one finalized slot fans out to several downstream carries
        without ever visiting the host.

        Gathers the slot's dense aggregate, re-keys each occupied bucket
        through the ``relabel`` lookup (this plan's bucket id → the
        destination plan's key id, ``< 0`` = unassigned), stamps the
        re-windowed span ``[last_window, n_windows]`` (already rebased by
        the caller), and values each row with the finalized ``kind``
        aggregate.  Returns device-fan-out rows padded to ``dst_rows`` in
        the destination backend's wire layout: vmap gets the batched
        (workers, per, 5) shape, shard_map keeps the flat (rows, 5) global
        layout.  The (kind, dst_rows) jit cache keys one compiled handoff
        per distinct edge shape.
        """
        fn = self._handoffs.get((kind, dst_rows))
        if fn is None:
            fn = jax.jit(partial(self._handoff_impl, kind=kind,
                                 num_buckets=self.plan.carry_buckets,
                                 channel_base=self.plan.reduce.channel_base,
                                 dst_rows=dst_rows,
                                 n_workers=self.plan.n_workers
                                 if self.backend == "vmap" else 0))
            self._handoffs[(kind, dst_rows)] = fn
        return fn(carry, jnp.int32(slot), relabel,
                  jnp.float32(last_window), jnp.float32(n_windows))

    @staticmethod
    def _handoff_impl(carry, slot, relabel, last_window, n_windows, *,
                      kind, num_buckets, channel_base, dst_rows, n_workers):
        flat, _ = _flat_carry(carry)
        agg = _gather_flat_slot(flat, slot, num_buckets)
        rows = stages.carry_handoff_rows(agg, relabel, last_window,
                                         n_windows, kind, dst_rows,
                                         channel_base=channel_base)
        if n_workers:                   # vmap: batch the worker axis
            return rows.reshape(n_workers, dst_rows // n_workers, 5)
        return rows                     # shard_map: flat global wire


def _stream_group_body(rows, carry, min_window, *, plan: ExecutionPlan):
    """Windowed group-mode fold: fan out on-chip, exchange records to their
    (slot, bucket) owner over the flattened id space, append into the
    fixed-capacity per-slot buffers carried across batches."""
    ks, ws, rs = plan.key_space, plan.window, plan.reduce
    last, nw, keys, vals, valid = _decode_device_rows(rows)
    buckets = stages.bucketize(keys, ks.num_buckets, hashed=ks.is_hashed)
    slots, keys_f, vals_f, live, late, expanded = stages.window_fanout(
        last, nw, buckets, vals, valid, ws.fanout, ws.n_slots, min_window)
    flat = slots * ks.num_buckets + keys_f
    # per-destination capacity = all expanded records: the exchange cannot
    # drop; only the per-slot window buffers bound capacity
    sk, sv, sok, _ = stages.build_send_buffers(
        flat, vals_f, plan.n_workers, flat.shape[0], valid=live)
    rk, rv, rok = stages.exchange(sk, sv, sok, plan.axis_name)
    kb, vb, counts, dropped = stages.append_window_records(
        carry["keys"], carry["vals"], carry["counts"], rk.reshape(-1),
        jnp.where(rok.reshape(-1), rv.reshape(-1), 0.0), rok.reshape(-1),
        ws.n_slots, rs.capacity, ks.num_buckets)
    stats = jnp.stack([jax.lax.psum(late, plan.axis_name),
                       jax.lax.psum(expanded, plan.axis_name),
                       jax.lax.psum(dropped, plan.axis_name)])
    return {"keys": kb, "vals": vb, "counts": counts}, stats


def _stream_group_finalize_body(carry, slot, *, plan: ExecutionPlan):
    return stages.gather_window_group(carry["keys"], carry["vals"], slot,
                                      plan.axis_name, plan.reduce.reduce_fn)


class CompiledStreamGroup:
    """Streaming group-mode lowering: the carry is a fixed-capacity record
    buffer per (worker, window slot); arbitrary ``reduce_fn`` runs over each
    key's full value list at window finalization (``finalize_slot``), the
    same contract as batch group mode.
    """

    def __init__(self, plan, backend, mesh, jit):
        self.plan = plan
        self.backend = backend
        axis = plan.axis_name
        self._placement = _carry_placement(backend, mesh, axis)
        self._lower_step = partial(lower, partial(_stream_group_body,
                                                  plan=plan),
                                   axis_name=axis,
                                   in_specs=(P(axis), P(axis), P()),
                                   out_specs=(P(axis), P()), backend=backend,
                                   mesh=mesh, jit=jit)
        self._step = self._lower_step()
        self._step_donating = None
        self._finalize = lower(partial(_stream_group_finalize_body, plan=plan),
                               axis_name=axis, in_specs=(P(axis), P()),
                               out_specs=(P(), P(), P()), backend=backend,
                               mesh=mesh, jit=jit)
        self._clear = jax.jit(partial(self._clear_impl,
                                      n_slots=plan.window.n_slots))

    def init_carry(self, dtype=jnp.float32):
        """Zeroed per-(worker, window slot) record buffers.  Like the
        aggregate carry, the layout follows the backend: vmap batches the
        worker axis, shard_map shards the flattened (worker, slot) rows so
        each worker's slice matches what the stage body sees under vmap."""
        plan = self.plan
        n_slots, cap = plan.window.n_slots, plan.reduce.capacity
        if self.backend == "vmap":
            shape = (plan.n_workers, n_slots, cap)
        else:
            shape = (plan.n_workers * n_slots, cap)
        at = self._placement
        return {"keys": jnp.full(shape, stages.INVALID, jnp.int32, device=at),
                "vals": jnp.zeros(shape, dtype, device=at),
                "counts": jnp.zeros(shape[:-1], jnp.int32, device=at)}

    def step(self, rows, carry, min_window: int | None = None, *,
             donate: bool = False):
        """One micro-batch fold; ``donate=True`` donates the carry pytree's
        buffers for in-place reuse (see ``CompiledStreamAggregate.step``)."""
        if donate:
            if self._step_donating is None:
                self._step_donating = self._lower_step(donate_argnums=(1,))
            return self._step_donating(rows, carry, jnp.int32(min_window))
        return self._step(rows, carry, jnp.int32(min_window))

    def finalize_slot(self, carry, slot: int):
        """Gather + merge + reduce one window's buffered records across all
        workers.  Returns dense (group_keys, group_values, group_valid)."""
        gk, gv, gvalid = self._finalize(carry, jnp.int32(slot))
        return np.asarray(gk), np.asarray(gv), np.asarray(gvalid)

    @staticmethod
    def _clear_impl(carry, slot, *, n_slots):
        cap = carry["keys"].shape[-1]
        keys = carry["keys"].reshape(-1, n_slots, cap)
        vals = carry["vals"].reshape(-1, n_slots, cap)
        counts = carry["counts"].reshape(-1, n_slots)
        onehot = (jnp.arange(n_slots, dtype=jnp.int32) == slot)
        keys = jnp.where(onehot[None, :, None], stages.INVALID, keys)
        vals = jnp.where(onehot[None, :, None], 0.0, vals)
        counts = jnp.where(onehot[None, :], 0, counts)
        return {"keys": keys.reshape(carry["keys"].shape),
                "vals": vals.reshape(carry["vals"].shape),
                "counts": counts.reshape(carry["counts"].shape)}

    def clear_slot(self, carry, slot: int):
        return self._clear(carry, jnp.int32(slot))

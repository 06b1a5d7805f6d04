"""Backend lowering: one SPMD stage body, three execution substrates.

Every ``ExecutionPlan`` variant bottoms out here.  A *body* is a pure
function over per-worker arrays that may call collectives (``psum``,
``all_to_all``, ``psum_scatter``, ...) on ``axis_name``; ``lower`` turns it
into an executable either by vmapping the worker axis (simulating W workers
on one device — the CI path) or by shard_mapping it over a mesh axis (real
SPMD, the production path).  Placement is written once, as PartitionSpecs;
the vmap backend derives its in/out axes from them (``P(axis)`` → batched
at axis 0, ``P()`` → replicated), so both backends share one spec language
and the stage bodies in ``stages.py`` never mention a backend.

The third substrate, ``backend="pallas"``, does not lower a generic body
at all: the streaming aggregate fold dispatches to the fused Pallas
kernel (``kernels/fused_fold`` — hash → window fan-out →
scatter-accumulate in one kernel over the flat carry) inside
``plan.CompiledStreamAggregate``, with a single-slab carry in the
shard_map (flat) wire layout.  ``lower`` only knows enough about it to
say so in its error; ``default_pallas_interpret`` is the one switch every
pallas caller consults for compile-vs-interpret.

This module is also the one place that calls ``jax.shard_map``: callers
(``core.mapreduce``, ``runtime.train_step``) route through
``make_shard_map`` (reprolint RL101 keeps it so), and the entry points
place JAX's persistent compilation cache through ``enable_compile_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Callable

import jax


#: backends ``ExecutionPlan.compile`` accepts; "pallas" is valid only for
#: plan shapes the fused fold covers (see plan.CompiledStreamAggregate)
BACKENDS = ("vmap", "shard_map", "pallas")


def default_pallas_interpret() -> bool:
    """Interpret Pallas kernel bodies unless a real TPU can compile them —
    the CI/container answer is always interpret (CPU executes the kernel
    body as jax ops, bit-identically), the production answer is Mosaic."""
    return jax.default_backend() != "tpu"


#: where ``enable_compile_cache`` keeps compiled programs when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed path, since the path is
#: part of the cache key and a directory that moves never hits
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point
    (scripts, benchmarks, examples — never tests) and return its directory.
    A ``JAX_COMPILATION_CACHE_DIR`` set outside wins and is left as JAX
    reads it; otherwise the cache goes to ``<repo>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_shard_map(body: Callable, mesh: jax.sharding.Mesh, in_specs,
                   out_specs) -> Callable:
    """``jax.shard_map`` with the replication checker off — finalized
    outputs are all_gather/psum results, replicated by construction, which
    the static checker can't always prove."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _vmap_axes(specs: Any, axis_name: str):
    """PartitionSpec (tree) → vmap axes: sharded on ``axis_name`` ↦ axis 0,
    replicated ↦ None.  Nested tuples mirror multi-output bodies; a single
    spec acts as a prefix over a pytree output (both backends broadcast)."""
    if isinstance(specs, jax.sharding.PartitionSpec):
        return 0 if axis_name in tuple(specs) else None
    if isinstance(specs, (tuple, list)):
        return tuple(_vmap_axes(s, axis_name) for s in specs)
    raise TypeError(f"expected PartitionSpec or tuple thereof, got {specs!r}")


def lower(body: Callable, *, axis_name: str, in_specs, out_specs,
          backend: str = "vmap", mesh: jax.sharding.Mesh | None = None,
          jit: bool = True,
          donate_argnums: tuple[int, ...] | None = None) -> Callable:
    """Lower an SPMD stage body to an executable for ``backend``.

    ``in_specs`` is a tuple with one PartitionSpec per body argument (a spec
    applies uniformly to a pytree argument); ``out_specs`` mirrors the body's
    output structure.  ``backend="vmap"`` needs no mesh; ``"shard_map"``
    shards/replicates per the same specs over ``mesh``.

    ``donate_argnums`` marks arguments whose buffers XLA may reuse for the
    outputs (``jax.jit`` donation) — streaming steps donate the carry so a
    long-lived fold updates one buffer in place instead of copying it every
    micro-batch.  The caller must not read a donated argument after the
    call; with ``jit=False`` donation is unavailable and silently skipped
    (an un-jitted body cannot alias buffers anyway).
    """
    if backend == "vmap":
        fn = jax.vmap(body, in_axes=_vmap_axes(tuple(in_specs), axis_name),
                      out_axes=_vmap_axes(out_specs, axis_name),
                      axis_name=axis_name)
    elif backend == "shard_map":
        if mesh is None:
            raise ValueError("shard_map backend needs a mesh")
        fn = make_shard_map(body, mesh, tuple(in_specs), out_specs)
    elif backend == "pallas":
        # the fused kernel replaces the body wholesale; only the streaming
        # aggregate plan knows how, so generic bodies cannot lower here
        raise ValueError(
            "backend='pallas' lowers the streaming aggregate fold only "
            "(the fused kernels/fused_fold kernel, dispatched inside "
            "CompiledStreamAggregate) — this plan shape has no pallas "
            "lowering; use 'vmap' or 'shard_map'")
    else:
        raise ValueError(f"unknown backend {backend!r} "
                         f"(expected one of {BACKENDS})")
    if not jit:
        return fn
    return jax.jit(fn, donate_argnums=donate_argnums or ())

"""The training step as a MapReduce round (DESIGN.md §2).

  map      — per-device forward/backward on its batch shard
  combine  — local microbatch gradient accumulation (``lax.scan``), the
             paper's combiner: pre-reduce before any communication
  shuffle+reduce — the gradient all-reduce.  Under ``jax.jit`` + sharded
             batch this is implicit (XLA inserts reduce-scatter/all-reduce);
             under ``shard_map`` it is explicit ``psum`` — optionally the
             int8 ``compressed_psum`` (smaller spill files)
  finalize — optimizer update (+ async checkpoint, in the Trainer)

Both distribution styles are provided:
  * ``make_train_step`` — jit/GSPMD path (what the multi-pod dry-run lowers);
  * ``make_shardmap_train_step`` — explicit-collective path used to
    demonstrate gradient compression on the wire.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..engine.compile import make_shard_map
from ..models import ModelConfig, init_params, loss_fn
from ..optim import AdamW, TrainState, apply_updates
from ..optim.compression import compressed_psum


def init_train_state(key: jax.Array, cfg: ModelConfig,
                     opt: AdamW) -> TrainState:
    params = init_params(key, cfg)
    return TrainState(params=params, opt_state=opt.init(params),
                      step=jnp.zeros((), jnp.int32))


def make_train_step(cfg: ModelConfig, opt: AdamW, microbatches: int = 1,
                    loss: Callable | None = None, donate: bool = True,
                    grad_specs=None):
    """jit-able ``train_step(state, batch) -> (state, metrics)``.

    ``microbatches > 1``: batch leading axis is (microbatches, B/mb, S) and
    gradients accumulate locally before the (implicit) reduction — the
    combiner.  Shardings are attached by the caller (launch/dryrun or
    launch/train) via in_shardings/out_shardings at jit time.

    ``grad_specs`` (optional PartitionSpec tree): sharding constraint for the
    fp32 gradient accumulator — under the ZeRO-2 layout the accumulator is
    FSDP-sharded even though parameters are replicated over data, so each
    microbatch's gradients arrive as a reduce-scatter instead of an
    all-reduce (EXPERIMENTS.md §Perf).
    """
    loss = loss or loss_fn
    grad_fn = jax.value_and_grad(loss, has_aux=True)

    def _constrain(tree):
        if grad_specs is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            grad_specs)

    def train_step(state: TrainState, batch: dict[str, jax.Array]):
        if microbatches == 1:
            (_loss, metrics), grads = grad_fn(state.params, batch, cfg)
            grads = _constrain(grads)
        else:
            def body(carry, mb):
                acc = carry
                (_loss, metrics), g = grad_fn(state.params, mb, cfg)
                acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                   acc, g)
                return _constrain(acc), metrics

            zeros = _constrain(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params))
            grads, ms = jax.lax.scan(body, zeros, batch)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            metrics = jax.tree.map(lambda m: m[-1], ms)
        updates, opt_state, stats = opt.update(grads, state.opt_state,
                                               state.params)
        params = apply_updates(state.params, updates)
        new_state = TrainState(params, opt_state, state.step + 1)
        return new_state, {**metrics, **stats}

    return train_step


def make_shardmap_train_step(cfg: ModelConfig, opt: AdamW,
                             mesh: jax.sharding.Mesh, axis_name: str = "data",
                             compress_grads: bool = False,
                             loss: Callable | None = None):
    """Explicit-collective train step: per-device grads + psum (optionally
    int8-compressed) over ``axis_name``.  Params/opt-state replicated over
    the axis; batch sharded on it."""
    loss = loss or loss_fn
    grad_fn = jax.value_and_grad(loss, has_aux=True)
    P = jax.sharding.PartitionSpec

    def worker(state: TrainState, batch):
        (_loss, metrics), grads = grad_fn(state.params, batch, cfg)
        if compress_grads:
            grads = compressed_psum(grads, axis_name)   # int8 on the wire
        else:
            grads = jax.lax.pmean(grads, axis_name)
        metrics = jax.lax.pmean(metrics, axis_name)
        updates, opt_state, stats = opt.update(grads, state.opt_state,
                                               state.params)
        params = apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), \
            {**metrics, **stats}

    def train_step(state, batch):
        # shard_map lives behind engine/compile.py (reprolint RL101)
        fn = make_shard_map(
            worker, mesh,
            (jax.tree.map(lambda _: P(), state),
             jax.tree.map(lambda _: P(axis_name), batch)),
            (jax.tree.map(lambda _: P(), state), P()))
        return fn(state, batch)

    return train_step


def make_eval_step(cfg: ModelConfig, loss: Callable | None = None):
    loss = loss or loss_fn

    def eval_step(params, batch):
        _, metrics = loss(params, batch, cfg)
        return metrics

    return eval_step

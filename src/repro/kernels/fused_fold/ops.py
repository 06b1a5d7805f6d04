"""jit'd public wrapper for the fused streaming-fold kernel.

``fold(..., use_pallas=False)`` routes to the XLA segment-sum reference
(``ref.py``); ``use_pallas=True`` targets the Pallas kernel.  ``interpret``
defaults to ``engine.compile.default_pallas_interpret``: compiled lowering
on TPU, interpret mode (the kernel body as jax ops) everywhere else.

``make_fold_step`` builds the streaming-step callable
``CompiledStreamAggregate`` dispatches to for ``backend="pallas"``: the
plan's static geometry is closed over once, the result is jit'd (with the
carry optionally donated — ``input_output_aliases`` in the kernel turns
donation into a true in-place carry update), and the call signature
matches the lowered XLA step exactly, so the coordinator cannot tell the
backends apart except by speed.
"""

from __future__ import annotations

import jax

from ...engine.compile import default_pallas_interpret
from .kernel import fused_streaming_fold
from .ref import fused_streaming_fold_ref


def fold(rows, carry, min_window=None, *, fanout, n_slots, num_buckets,
         carry_buckets, channel_base=0, hashed=False, host_wire=False,
         kind="sum", use_pallas=True, interpret=None, block_n=256,
         block_s=None):
    if use_pallas:
        if interpret is None:
            interpret = default_pallas_interpret()
        return fused_streaming_fold(
            rows, carry, min_window, fanout=fanout, n_slots=n_slots,
            num_buckets=num_buckets, carry_buckets=carry_buckets,
            channel_base=channel_base, hashed=hashed, host_wire=host_wire,
            kind=kind, block_n=block_n, block_s=block_s,
            interpret=interpret)
    return fused_streaming_fold_ref(
        rows, carry, min_window, fanout=fanout, n_slots=n_slots,
        num_buckets=num_buckets, carry_buckets=carry_buckets,
        channel_base=channel_base, hashed=hashed, host_wire=host_wire,
        kind=kind)


def make_fold_step(*, fanout, n_slots, num_buckets, carry_buckets,
                   channel_base=0, hashed=False, host_wire=False,
                   kind="sum", use_pallas=True, interpret=None, block_n=256,
                   block_s=None, donate_argnums=()):
    """Factory for the pallas-backend streaming step.

    Returns ``step(rows, carry, min_window) -> (carry', stats)`` for the
    device wire, or ``step(rows, carry)`` for the host wire — the exact
    signatures ``CompiledStreamAggregate.step`` calls on its lowered fn.
    """
    kw = dict(fanout=fanout, n_slots=n_slots, num_buckets=num_buckets,
              carry_buckets=carry_buckets, channel_base=channel_base,
              hashed=hashed, host_wire=host_wire, kind=kind,
              use_pallas=use_pallas, interpret=interpret, block_n=block_n,
              block_s=block_s)
    if host_wire:
        def step(rows, carry):
            return fold(rows, carry, None, **kw)
    else:
        def step(rows, carry, min_window):
            return fold(rows, carry, min_window, **kw)
    return jax.jit(step, donate_argnums=donate_argnums or ())

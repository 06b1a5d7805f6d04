"""Kernel micro-benchmarks: XLA reference path timings on CPU (wall time is
hardware-bound here; the TPU story is the §Roofline analysis) plus
combiner-volume derived metrics that mirror the paper's combiner claim."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.ops import chunked_attention
from repro.kernels.flash_attention.ref import decode_ref
from repro.kernels.hash_combine.ref import hash_combine_ref
from repro.kernels.mamba_scan.ref import selective_scan_ref

from .common import fmt_csv


def _time(fn, *args, n=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def run(print_rows=True) -> list[str]:
    rows = []
    rng = np.random.default_rng(0)

    # combiner: volume reduction factor at paper-like key skew
    n, buckets = 1 << 16, 4096
    keys = jnp.asarray(np.minimum(rng.zipf(1.3, n), buckets) - 1, jnp.int32)
    vals = jnp.ones((n,), jnp.float32)
    f = jax.jit(lambda k, v: hash_combine_ref(k, v, buckets))
    us = _time(f, keys, vals)
    uniques = int(len(np.unique(np.asarray(keys))))
    rows.append(fmt_csv("kernels/hash_combine/64k_records", us,
                        f"volume_reduction={n/uniques:.1f}x"))

    # flash attention fwd (chunked XLA path)
    q = jnp.asarray(rng.normal(size=(1, 8, 1024, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 1024, 128)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 1024, 128)), jnp.float32)
    f = jax.jit(lambda a, b, c: chunked_attention(a, b, c, causal=True,
                                                  chunk=256))
    us = _time(f, q, k, v)
    flops = 4 * 1 * 8 * 1024 * 1024 * 128
    rows.append(fmt_csv("kernels/flash_attention/b1_h8_s1024_d128", us,
                        f"gflops_per_s={flops/us/1e3:.1f}"))

    # flash decode against a 16k cache
    qd = jnp.asarray(rng.normal(size=(4, 8, 128)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(4, 2, 16384, 128)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(4, 2, 16384, 128)), jnp.float32)
    lens = jnp.full((4,), 16000, jnp.int32)
    f = jax.jit(lambda a, b, c, ln: decode_ref(a, b, c, ln))
    us = _time(f, qd, kc, vc, lens)
    rows.append(fmt_csv("kernels/flash_decode/b4_h8_s16k", us,
                        f"bytes_touched={2*4*2*16384*128*4}"))

    # fused streaming fold: the pallas backend's one-kernel hash → window
    # fan-out → (slot, bucket) scatter-accumulate vs the XLA chain it
    # replaces, at several batch × slots × buckets shapes.  On this CPU the
    # XLA ref JITs to native code while the kernel runs under the pallas
    # interpreter, so the pair tracks decode/dispatch overhead, not the TPU
    # win — that is the roofline streaming-fold row.  Interpret timing is
    # only taken at the smallest shape to keep the bench budget flat.
    from repro.kernels.fused_fold.ops import fold
    for n, n_slots, nb in [(4096, 8, 64), (16384, 8, 256), (16384, 16, 1024)]:
        cols = [rng.integers(0, 3 * n_slots, n), rng.integers(1, 5, n),
                rng.integers(0, 1 << 20, n), rng.integers(0, 100, n),
                np.ones(n)]
        frows = jnp.asarray(np.stack(cols, axis=1), jnp.float32)
        carry = jnp.zeros((n_slots * nb, 2), jnp.float32)
        kwf = dict(fanout=4, n_slots=n_slots, num_buckets=nb,
                   carry_buckets=nb, hashed=True, kind="sum")
        us = _time(lambda r, c: fold(r, c, 0, use_pallas=False, **kwf),
                   frows, carry)
        derived = (f"pairs_per_s={4 * n / us * 1e6:.0f};"
                   f"carry_cells={n_slots * nb}")
        if n == 4096:
            us_pal = _time(lambda r, c: fold(r, c, 0, use_pallas=True,
                                             interpret=True, **kwf),
                           frows, carry, n=2)
            derived += f";pallas_interpret_us={us_pal:.0f}"
        rows.append(fmt_csv(
            f"kernels/fused_fold/n{n}_s{n_slots}_b{nb}", us, derived))

    # mamba selective scan
    b, L, d, ns = 1, 1024, 512, 16
    u = jnp.asarray(rng.normal(size=(b, L, d)), jnp.float32)
    delta = jnp.asarray(np.abs(rng.normal(size=(b, L, d))) * 0.1, jnp.float32)
    A = -jnp.asarray(np.abs(rng.normal(size=(d, ns))) + 0.5, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(b, L, ns)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, L, ns)), jnp.float32)
    D = jnp.ones((d,), jnp.float32)
    f = jax.jit(lambda *a: selective_scan_ref(*a)[0])
    us = _time(f, u, delta, A, Bm, C, D)
    rows.append(fmt_csv("kernels/mamba_scan/b1_L1024_d512_n16", us,
                        f"tokens_per_s={b*L/us*1e6:.0f}"))

    if print_rows:
        for r in rows:
            print(r)
    return rows


if __name__ == "__main__":
    from repro.engine.compile import enable_compile_cache
    enable_compile_cache()
    run()

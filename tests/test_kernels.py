"""Per-kernel correctness: shape/dtype sweeps, Pallas (interpret=True) vs the
pure-jnp oracle in each kernel's ref.py."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention, flash_decode
from repro.kernels.fused_fold.kernel import fused_streaming_fold, pick_block_s
from repro.kernels.fused_fold.ref import fused_streaming_fold_ref
from repro.kernels.flash_attention.ops import chunked_attention
from repro.kernels.flash_attention.ref import decode_ref, mha_ref
from repro.kernels.hash_combine.kernel import hash_combine
from repro.kernels.hash_combine.ref import hash_combine_ref
from repro.kernels.mamba_scan.kernel import selective_scan
from repro.kernels.mamba_scan.ops import decode_step
from repro.kernels.mamba_scan.ref import selective_scan_ref

RNG = np.random.default_rng(0)


def t(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype=dtype)


# -- hash_combine ---------------------------------------------------------------

@pytest.mark.parametrize("n,d,buckets,block_n", [
    (256, 1, 32, 128), (1000, 4, 64, 512), (4096, 16, 256, 512),
    (777, 8, 128, 256),
])
def test_hash_combine_sweep(n, d, buckets, block_n):
    keys = jnp.asarray(RNG.integers(0, buckets, n), jnp.int32)
    vals = t((n, d))
    valid = jnp.asarray(RNG.random(n) > 0.2)
    got = hash_combine(keys, vals, valid, num_buckets=buckets,
                       block_n=block_n, interpret=True)
    want = hash_combine_ref(keys, vals, buckets, valid)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hash_combine_dtypes(dtype):
    keys = jnp.asarray(RNG.integers(0, 32, 512), jnp.int32)
    vals = t((512,), dtype)
    got = hash_combine(keys, vals, num_buckets=32, interpret=True)
    want = hash_combine_ref(keys, vals, 32)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2)


# -- flash attention ---------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,causal,window,cap", [
    (1, 4, 4, 256, 256, 64, True, None, None),
    (2, 8, 2, 256, 256, 128, True, None, None),      # GQA
    (1, 2, 1, 256, 256, 64, True, 128, None),        # sliding window
    (1, 4, 4, 256, 256, 64, True, None, 50.0),       # softcap (gemma2)
    (2, 4, 2, 256, 256, 64, False, None, None),      # bidirectional
    (1, 4, 2, 128, 384, 64, True, None, None),       # skv > sq
])
def test_flash_attention_sweep(b, hq, hkv, sq, skv, d, causal, window, cap):
    q, k, v = t((b, hq, sq, d)), t((b, hkv, skv, d)), t((b, hkv, skv, d))
    got = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          interpret=True)
    want = mha_ref(q, k, v, causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


def test_flash_attention_bf16():
    q, k, v = (t((1, 4, 256, 64), jnp.bfloat16) for _ in range(3))
    got = flash_attention(q, k, v, interpret=True)
    want = mha_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_chunked_attention_matches_kernel_path():
    q, k, v = t((2, 4, 256, 64)), t((2, 2, 256, 64)), t((2, 2, 256, 64))
    a = chunked_attention(q, k, v, causal=True, chunk=64)
    b_ = mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(a, b_, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("b,hq,hkv,smax,d,window,cap", [
    (2, 8, 2, 1024, 64, None, None),
    (1, 4, 4, 512, 128, None, None),
    (2, 8, 4, 2048, 64, 512, None),                  # windowed decode
    (1, 16, 8, 1024, 64, None, 30.0),
])
def test_flash_decode_sweep(b, hq, hkv, smax, d, window, cap):
    q = t((b, hq, d))
    kc, vc = t((b, hkv, smax, d)), t((b, hkv, smax, d))
    lengths = jnp.asarray(RNG.integers(smax // 4, smax, b), jnp.int32)
    got = flash_decode(q, kc, vc, lengths, window=window, softcap=cap,
                       interpret=True)
    want = decode_ref(q, kc, vc, lengths, window=window, softcap=cap)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


# -- mamba selective scan ---------------------------------------------------------

@pytest.mark.parametrize("b,L,d,n,bd,bl", [
    (2, 512, 256, 16, 128, 256), (1, 256, 512, 16, 256, 128),
    (2, 128, 128, 8, 128, 64),
])
def test_selective_scan_sweep(b, L, d, n, bd, bl):
    u = t((b, L, d))
    delta = jnp.asarray(np.abs(RNG.normal(size=(b, L, d))) * 0.1 + 0.01,
                        jnp.float32)
    A = -jnp.asarray(np.abs(RNG.normal(size=(d, n))) + 0.5, jnp.float32)
    B, C, D = t((b, L, n)), t((b, L, n)), t((d,))
    y_k, h_k = selective_scan(u, delta, A, B, C, D, block_d=bd, block_l=bl,
                              interpret=True)
    y_r, h_r = selective_scan_ref(u, delta, A, B, C, D)
    np.testing.assert_allclose(y_k, y_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h_k, h_r, rtol=1e-4, atol=1e-4)


def test_scan_decode_step_matches_full_scan():
    """Running decode_step over a sequence equals the full scan — the
    serving-path invariant behind long_500k."""
    b, L, d, n = 1, 16, 32, 8
    u = t((b, L, d))
    delta = jnp.asarray(np.abs(RNG.normal(size=(b, L, d))) * 0.1 + 0.01,
                        jnp.float32)
    A = -jnp.asarray(np.abs(RNG.normal(size=(d, n))) + 0.5, jnp.float32)
    B, C, D = t((b, L, n)), t((b, L, n)), t((d,))
    y_full, h_full = selective_scan_ref(u, delta, A, B, C, D)
    h = jnp.zeros((b, d, n), jnp.float32)
    ys = []
    for i in range(L):
        y_t, h = decode_step(h, u[:, i], delta[:, i], A, B[:, i], C[:, i], D)
        ys.append(y_t)
    np.testing.assert_allclose(jnp.stack(ys, 1), y_full, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h, h_full, rtol=1e-4, atol=1e-4)


def test_ssd_chunked_matches_recurrence():
    """Mamba-2 SSD chunked form vs the naive recurrence."""
    from repro.models.mamba import _ssd_chunked
    b, slen, h, p, n, chunk = 1, 64, 4, 8, 16, 16
    x = t((b, slen, h, p))
    dt = jnp.asarray(np.abs(RNG.normal(size=(b, slen, h))) * 0.1 + 0.01,
                     jnp.float32)
    A = -jnp.asarray(np.abs(RNG.normal(size=(h,))) + 0.3, jnp.float32)
    B, C = t((b, slen, n)), t((b, slen, n))
    y, s_final = _ssd_chunked(x, dt, A, B, C, chunk)
    # naive
    s = np.zeros((b, h, n, p), np.float32)
    ys = np.zeros((b, slen, h, p), np.float32)
    xn, dtn, Bn, Cn = map(np.asarray, (x, dt, B, C))
    An = np.asarray(A)
    for i in range(slen):
        decay = np.exp(dtn[:, i] * An[None])                     # (b, h)
        dBx = np.einsum("bh,bn,bhp->bhnp", dtn[:, i], Bn[:, i], xn[:, i])
        s = decay[..., None, None] * s + dBx
        ys[:, i] = np.einsum("bn,bhnp->bhp", Cn[:, i], s)
    np.testing.assert_allclose(y, ys, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s_final, s, rtol=2e-4, atol=2e-4)


# -- fused_fold (hash -> window fan-out -> scatter-accumulate) ------------------

def _device_rows(n, *, fanout, n_slots, keymax, rng):
    """5-col device wire [last_window, n_windows, key, value, valid] with
    integer-valued payloads so fp32 sums are exact in any order."""
    cols = [rng.integers(0, 3 * n_slots, n), rng.integers(1, fanout + 1, n),
            rng.integers(0, keymax, n), rng.integers(-20, 100, n),
            rng.random(n) > 0.15]
    return jnp.asarray(np.stack(cols, axis=1), jnp.float32)


def _host_rows(n, *, n_slots, keymax, rng):
    """4-col host wire [window_slot, key, value, valid] (fan-out 1)."""
    cols = [rng.integers(0, n_slots, n), rng.integers(0, keymax, n),
            rng.integers(-20, 100, n), rng.random(n) > 0.15]
    return jnp.asarray(np.stack(cols, axis=1), jnp.float32)


@pytest.mark.parametrize("kind", ["sum", "count", "min", "max"])
@pytest.mark.parametrize("hashed", [False, True], ids=["dense", "hashed"])
def test_fused_fold_device_wire_sweep(kind, hashed):
    """Kernel (interpret) vs the XLA ref on the 5-col device wire: fan-out
    4, a min_window that drops some fan-outs as late, a ragged batch that
    exercises the zero-pad tail, and multi-tile carry grid (block_s)."""
    rng = np.random.default_rng(7)
    n_slots, nb = 6, 24
    kw = dict(fanout=4, n_slots=n_slots, num_buckets=nb, carry_buckets=nb,
              hashed=hashed, kind=kind)
    rows = _device_rows(999, fanout=4, n_slots=n_slots,
                        keymax=(1 << 20) if hashed else nb, rng=rng)
    carry = jnp.asarray(rng.integers(0, 5, (n_slots * nb, 2)), jnp.float32)
    if kind in ("min", "max"):
        carry = carry.at[:, 0].set(          # honour the carry contract:
            jnp.where(carry[:, 1] > 0, carry[:, 0], 0.0))   # count 0 -> 0.0
    got_c, got_s = fused_streaming_fold(rows, carry, 2, block_n=256,
                                        block_s=48, interpret=True, **kw)
    want_c, want_s = fused_streaming_fold_ref(rows, carry, 2, **kw)
    assert np.array_equal(np.asarray(got_c), np.asarray(want_c))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s))
    assert int(got_s[0]) > 0                 # min_window really dropped some


@pytest.mark.parametrize("kind", ["sum", "count", "min", "max"])
def test_fused_fold_host_wire_sweep(kind):
    rng = np.random.default_rng(11)
    n_slots, nb = 4, 16
    kw = dict(fanout=1, n_slots=n_slots, num_buckets=nb, carry_buckets=nb,
              hashed=True, host_wire=True, kind=kind)
    rows = _host_rows(500, n_slots=n_slots, keymax=1 << 20, rng=rng)
    carry = jnp.zeros((n_slots * nb, 2), jnp.float32)
    got_c, got_s = fused_streaming_fold(rows, carry, block_n=128,
                                        interpret=True, **kw)
    want_c, want_s = fused_streaming_fold_ref(rows, carry, **kw)
    assert np.array_equal(np.asarray(got_c), np.asarray(want_c))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s))


def test_fused_fold_channel_embedding_leaves_neighbours():
    """A shared carry (joins): fold into channels [2, 4) of a 6-channel
    slab over carry_buckets > num_buckets — the other channels and the
    out-of-range bucket rows must come back bit-identical."""
    rng = np.random.default_rng(13)
    n_slots, nb, cb = 4, 12, 16
    kw = dict(fanout=2, n_slots=n_slots, num_buckets=nb, carry_buckets=cb,
              channel_base=2, kind="sum")
    rows = _device_rows(300, fanout=2, n_slots=n_slots, keymax=nb, rng=rng)
    carry = jnp.asarray(rng.integers(0, 9, (n_slots * cb, 6)), jnp.float32)
    got_c, _ = fused_streaming_fold(rows, carry, block_n=128,
                                    interpret=True, **kw)
    want_c, _ = fused_streaming_fold_ref(rows, carry, **kw)
    assert np.array_equal(np.asarray(got_c), np.asarray(want_c))
    got, old = np.asarray(got_c), np.asarray(carry)
    assert np.array_equal(got[:, [0, 1, 4, 5]], old[:, [0, 1, 4, 5]])
    untouched = np.arange(n_slots * cb) % cb >= nb    # buckets [nb, cb)
    assert np.array_equal(got[untouched], old[untouched])


def test_fused_fold_tiling_invariance():
    """The grid decomposition is an implementation detail: any
    (block_n, block_s) pair must produce the same bytes."""
    rng = np.random.default_rng(17)
    n_slots, nb = 8, 32
    kw = dict(fanout=3, n_slots=n_slots, num_buckets=nb, carry_buckets=nb,
              hashed=True, kind="sum")
    rows = _device_rows(700, fanout=3, n_slots=n_slots, keymax=1 << 20,
                        rng=rng)
    carry = jnp.zeros((n_slots * nb, 2), jnp.float32)
    ref = None
    for block_n, block_s in [(128, None), (256, 64), (512, 128), (1024, 32)]:
        c, s = fused_streaming_fold(rows, carry, 1, block_n=block_n,
                                    block_s=block_s, interpret=True, **kw)
        if ref is None:
            ref = (np.asarray(c), np.asarray(s))
        assert np.array_equal(np.asarray(c), ref[0]), (block_n, block_s)
        assert np.array_equal(np.asarray(s), ref[1]), (block_n, block_s)


@pytest.mark.parametrize("size,channels,want", [
    (144, 2, 144),                 # a small carry is one resident tile
    (8 * 131072, 2, 1024),         # the deployment carry: a VMEM-sized tile
    (8 * 131072, 6, 1024),         # a shared join carry pads to 128 lanes too
    (6 * 100001 * 8, 2, 528),      # largest multiple-of-8 divisor under the cap
])
def test_fused_fold_carry_tile(size, channels, want):
    """The carry tile divides the flat id space, is a multiple of 8 when
    it is not the whole carry, and is the largest such under the budget."""
    assert pick_block_s(size, channels, 256) == want


def test_fused_fold_carry_tile_rejects():
    """No tile fits: a large carry without a multiple-of-8 divisor under
    the budget; and an explicit tile Mosaic could not lay out."""
    with pytest.raises(ValueError, match="no carry tile fits"):
        pick_block_s(6 * 100001, 2, 256)
    rows = jnp.zeros((8, 5), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 8"):
        fused_streaming_fold(rows, jnp.zeros((144, 2), jnp.float32),
                             fanout=1, n_slots=6, num_buckets=24,
                             carry_buckets=24, block_s=12, interpret=True)


def test_fused_fold_in_kernel_hash_matches_engine():
    """The kernel duplicates the murmur bucketizer rather than importing
    the engine (kernels stay dependency-free) — pin the two to the same
    bits so they cannot drift apart."""
    from repro.engine.stages import device_hash
    from repro.kernels.fused_fold.ref import murmur_bucket
    keys = jnp.asarray(np.random.default_rng(19).integers(0, 1 << 24, 4096),
                       jnp.float32)
    want = (device_hash(keys.astype(jnp.uint32)) % jnp.uint32(64)
            ).astype(jnp.int32)
    got = murmur_bucket(keys, 64, True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_shuffle_aggregate_pallas_combiner_parity():
    """`combine_fn="pallas"` routes the batch shuffle through the
    hash_combine kernel (vmap-of-pallas, interpret off-TPU) — same bytes
    as the default dense jnp combiner, through the reduce_scatter."""
    import jax
    from repro.core.shuffle import resolve_combine_fn, shuffle_aggregate
    rng = np.random.default_rng(23)
    W, n, nb = 4, 256, 32
    keys = jnp.asarray(rng.integers(0, nb, (W, n)), jnp.int32)
    vals = jnp.asarray(rng.integers(0, 50, (W, n)), jnp.float32)
    valid = jnp.asarray(rng.random((W, n)) > 0.2)

    def run(combine_fn):
        f = jax.vmap(lambda k, v, ok: shuffle_aggregate(
            k, v, "w", nb, valid=ok, combine_fn=combine_fn), axis_name="w")
        return np.asarray(f(keys, vals, valid))

    assert np.array_equal(run("pallas"), run(None))
    # the resolved callable is the kernel product, not the jnp fallback
    from repro.engine.stages import local_combine_dense
    assert resolve_combine_fn("pallas") is not local_combine_dense
    assert resolve_combine_fn(None) is local_combine_dense

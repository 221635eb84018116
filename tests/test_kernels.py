"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape)
    return jnp.asarray(x, dtype)


# ---------------------------------------------------------------- paged attn
PA_CASES = [
    # B, KVH, G, D, page_size, P, max_pages, dtype
    (1, 1, 1, 128, 16, 8, 2, jnp.float32),
    (2, 2, 4, 128, 64, 16, 4, jnp.float32),
    (3, 4, 2, 128, 32, 12, 3, jnp.float32),
    (2, 1, 8, 256, 16, 8, 4, jnp.float32),        # MQA, gemma head_dim
    (2, 2, 5, 128, 16, 8, 3, jnp.float32),        # odd group (qwen3 G=5)
    (2, 2, 4, 64, 16, 8, 3, jnp.float32),         # musicgen head_dim
    (2, 2, 4, 128, 64, 16, 4, jnp.bfloat16),
]


@pytest.mark.parametrize("B,KVH,G,D,psz,P,maxp,dtype", PA_CASES)
def test_paged_attention_sweep(B, KVH, G, D, psz, P, maxp, dtype):
    q = _rand((B, KVH, G, D), dtype)
    k = _rand((P, psz, KVH, D), dtype)
    v = _rand((P, psz, KVH, D), dtype)
    table = jnp.asarray(RNG.integers(0, P, size=(B, maxp)), jnp.int32)
    seq_lens = jnp.asarray(RNG.integers(1, maxp * psz + 1, size=(B,)), jnp.int32)
    out = ops.paged_attention(q, k, v, table, seq_lens)
    want = ref.paged_attention_ref(q, k, v, table, seq_lens)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_paged_attention_seq_len_edge():
    """seq_len exactly on a page boundary, and length-1."""
    B, KVH, G, D, psz, P, maxp = 2, 1, 2, 128, 16, 6, 3
    q = _rand((B, KVH, G, D), jnp.float32)
    k = _rand((P, psz, KVH, D), jnp.float32)
    v = _rand((P, psz, KVH, D), jnp.float32)
    table = jnp.asarray(RNG.integers(0, P, size=(B, maxp)), jnp.int32)
    for lens in ([psz, 1], [maxp * psz, psz - 1]):
        seq_lens = jnp.asarray(lens, jnp.int32)
        out = ops.paged_attention(q, k, v, table, seq_lens)
        want = ref.paged_attention_ref(q, k, v, table, seq_lens)
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- page copy
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("P,psz,KVH,D", [(8, 16, 2, 128), (12, 64, 1, 256), (6, 16, 4, 64)])
def test_page_copy_sweep(P, psz, KVH, D, dtype):
    pool = _rand((P, psz, KVH, D), jnp.float32).astype(dtype)
    n = P // 2 - 1
    perm = RNG.permutation(P)
    src = jnp.asarray(perm[:n], jnp.int32)
    dst = jnp.asarray(perm[n : 2 * n], jnp.int32)
    got = ops.page_copy(pool, src, dst)
    want = ref.page_copy_ref(pool, src, dst)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------- delta diff/apply
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("N,C", [(8, 128), (37, 256), (64, 512), (1, 128)])
def test_delta_roundtrip_sweep(N, C, dtype):
    old = _rand((N, C), jnp.float32).astype(dtype)
    n_dirty = max(1, N // 3)
    rows = jnp.asarray(RNG.choice(N, size=n_dirty, replace=False), jnp.int32)
    new = old.at[rows].add(jnp.ones((n_dirty, C), dtype))
    dirty = ops.delta_diff(old, new)
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(ref.delta_diff_ref(old, new)))
    cap = int(np.asarray(dirty).sum()) + 2
    data, idx, count = ops.delta_compact(new, dirty, cap)
    rebuilt = ops.delta_apply(old, data, idx)
    np.testing.assert_array_equal(np.asarray(rebuilt), np.asarray(new))


def test_delta_compact_overflow_drops():
    """More dirty chunks than capacity: extras dropped, no corruption."""
    old = jnp.zeros((16, 64), jnp.float32)
    new = old + 1.0                       # all dirty
    dirty = ops.delta_diff(old, new)
    data, idx, count = ops.delta_compact(new, dirty, 4)
    assert int(count) == 16               # true count reported
    assert int((np.asarray(idx) >= 0).sum()) == 4
    rebuilt = ops.delta_apply(old, data, idx)
    # exactly 4 rows updated
    assert int((np.asarray(rebuilt).sum(axis=1) > 0).sum()) == 4


@settings(max_examples=20, deadline=None)
@given(
    st.integers(2, 40),
    st.integers(1, 8),
    st.floats(0.0, 1.0),
)
def test_delta_roundtrip_property(n_chunks, c_scale, dirty_frac):
    """encode(old→new) ∘ apply(old) == new for random dirt patterns."""
    C = 64 * c_scale
    rng = np.random.default_rng(n_chunks * 1000 + c_scale)
    old = jnp.asarray(rng.standard_normal((n_chunks, C)), jnp.float32)
    mask = rng.random(n_chunks) < dirty_frac
    new = np.asarray(old).copy()
    new[mask] += 1.0
    new = jnp.asarray(new)
    data, idx, count = ops.delta_encode(old, new, max_changed=n_chunks)
    rebuilt = ops.delta_apply(old, data, idx)
    np.testing.assert_array_equal(np.asarray(rebuilt), np.asarray(new))
    assert int(count) == int(mask.sum())


# ------------------------------------------------------------- fused encode
@pytest.mark.parametrize("N,C,block", [(8, 128, 8), (37, 256, 8), (5, 64, 8), (64, 512, 16)])
def test_fused_encode_sweep(N, C, block):
    """Fused diff+compact+checksum kernel vs the jnp oracle, bit for bit."""
    from repro.kernels.delta_fused import delta_fused

    old = _rand((N, C), jnp.float32)
    n_dirty = max(1, N // 3)
    rows = jnp.asarray(RNG.choice(N, size=n_dirty, replace=False), jnp.int32)
    new = old.at[rows].add(jnp.ones((n_dirty, C), jnp.float32))
    cap = n_dirty + 2
    data, idx, count, sums = delta_fused(
        old, new, max_changed=cap, chunk_block=block, interpret=True
    )
    rdata, ridx, rcount, rsums = ref.fused_encode_ref(old, new, cap)
    assert int(count) == int(rcount) == n_dirty
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(data), np.asarray(rdata))
    np.testing.assert_array_equal(np.asarray(sums), np.asarray(rsums))
    # and the delta still applies back to new
    rebuilt = ops.delta_apply(old, data, idx)
    np.testing.assert_array_equal(np.asarray(rebuilt), np.asarray(new))


def test_fused_encode_overflow_signals_count():
    old = jnp.zeros((16, 64), jnp.float32)
    new = old + 1.0                       # all dirty
    data, idx, count, sums = ops.fused_encode(old, new, 4)
    assert int(count) == 16               # true count: caller detects overflow
    assert int((np.asarray(idx) >= 0).sum()) == 4


def test_fused_checksums_match_host_mirror():
    """Device checksum lanes == numpy mirror over the fetched bytes.

    The checksum contract is over uint8 byte-grids — exactly what the dump
    pipeline feeds the fused kernel (ChunkedView grids are always uint8)."""
    rng = np.random.default_rng(7)
    old = jnp.asarray(rng.integers(0, 256, (12, 256), dtype=np.uint8))
    new_np = np.asarray(old).copy()
    new_np[[1, 4, 7]] ^= 0xA5
    new = jnp.asarray(new_np)
    data, idx, count, sums = ops.fused_encode(old, new, 6)
    valid = np.asarray(idx) >= 0
    want = ops.chunk_checksums_host(np.asarray(data)[valid])
    np.testing.assert_array_equal(want, np.asarray(sums)[valid])
    # corrupting one byte breaks at least one lane
    tampered = np.asarray(data)[valid].copy()
    tampered[0, 0] ^= 0x01
    assert (ops.chunk_checksums_host(tampered) != want).any()


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 40), st.integers(1, 6), st.floats(0.0, 1.0))
def test_fused_encode_property(n_chunks, c_scale, dirty_frac):
    """fused_encode == delta_encode + checksums for random dirt patterns
    over uint8 byte-grids (the pipeline's actual input shape)."""
    C = 64 * c_scale
    rng = np.random.default_rng(n_chunks * 7919 + c_scale)
    old_np = rng.integers(0, 256, (n_chunks, C), dtype=np.uint8)
    mask = rng.random(n_chunks) < dirty_frac
    new_np = old_np.copy()
    new_np[mask] ^= 0x5A
    old, new = jnp.asarray(old_np), jnp.asarray(new_np)
    fdata, fidx, fcount, fsums = ops.fused_encode(old, new, n_chunks)
    udata, uidx, ucount = ops.delta_encode(old, new, max_changed=n_chunks)
    assert int(fcount) == int(ucount) == int(mask.sum())
    np.testing.assert_array_equal(np.asarray(fidx), np.asarray(uidx))
    np.testing.assert_array_equal(np.asarray(fdata), np.asarray(udata))
    valid = np.asarray(fidx) >= 0
    if valid.any():
        np.testing.assert_array_equal(
            np.asarray(fsums)[valid],
            ops.chunk_checksums_host(np.asarray(fdata)[valid]),
        )


# ------------------------------------------------- row layout edge cases
@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.bfloat16, jnp.float32])
def test_delta_kernels_split_rows(monkeypatch, dtype):
    """Rows larger than one block split over the inner grid axis: dirtiness,
    checksum lanes and the scatter still match the oracles bit for bit."""
    from repro.kernels import words
    from repro.kernels.delta_apply import delta_apply
    from repro.kernels.delta_diff import delta_diff
    from repro.kernels.delta_fused import delta_fused

    monkeypatch.setattr(words, "BLOCK_BYTES", 16 * 1024)
    N, C = 5, 40 * 1024 // jnp.dtype(dtype).itemsize
    old = (_rand((N, C), jnp.float32) * 50).astype(dtype)
    new = old.at[1, -1].add(jnp.asarray(1, dtype))    # last sub-block only
    new = new.at[3, 7].add(jnp.asarray(1, dtype))      # first sub-block only
    assert words.block_plan(N, C, dtype)[1] < words.block_plan(N, C, dtype)[2]

    dirty = delta_diff(old, new, interpret=True)
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(ref.delta_diff_ref(old, new)))
    got = delta_fused(old, new, max_changed=3, interpret=True)
    want = ref.fused_encode_ref(old, new, 3)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    rebuilt = delta_apply(old, got[0], got[1], interpret=True)
    np.testing.assert_array_equal(np.asarray(rebuilt), np.asarray(new))


@pytest.mark.parametrize(
    "idx", [[-1, 3, -1, -1], [-1, -1, 0, 2], [4, -1, 1, -1], [-1, -1, -1, -1]]
)
def test_delta_apply_padding_anywhere(idx):
    """-1 rows never change the base, wherever they sit in the index."""
    from repro.kernels.delta_apply import delta_apply

    base = _rand((6, 256), jnp.float32)
    data = _rand((4, 256), jnp.float32)
    idx = jnp.asarray(idx, jnp.int32)
    got = delta_apply(base, data, idx, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.delta_apply_ref(base, data, idx))
    )


@pytest.mark.parametrize(
    "dtype,shape,counts",
    [
        (jnp.float32, (64, 256), (2, 2)),
        (jnp.bfloat16, (64, 512), (2, 2)),
        (jnp.uint8, (128, 256), (2, 1)),
        (jnp.float32, (2, 16, 256), (2, 2, 1)),
    ],
)
def test_tile_dirty_matches_jnp(dtype, shape, counts):
    """Block-native per-tile diff kernel == the jnp reduce it replaces, and
    block_encode agrees with its jnp path row for row."""
    from repro.kernels import tile_diff

    tile = tuple(s // c for s, c in zip(shape, counts))
    assert tile_diff.kernel_fits(tile, dtype)
    rng = np.random.default_rng(9)
    old = jnp.asarray(rng.integers(0, 100, shape), dtype)
    new = old.at[(0,) * len(shape)].add(jnp.asarray(1, dtype))
    new = new.at[tuple(s - 1 for s in shape)].add(jnp.asarray(3, dtype))
    got = tile_diff.tile_dirty(old, new, counts, tile, interpret=True)
    inter = [d for c, t in zip(counts, tile) for d in (c, t)]
    want = jnp.any(
        (old != new).reshape(inter), axis=tuple(2 * i + 1 for i in range(len(shape)))
    ).reshape(-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    cap = int(np.prod(counts))
    k = tile_diff.block_encode(old, new, counts, tile, cap, use_kernel=True, interpret=True)
    j = tile_diff.block_encode(old, new, counts, tile, cap, use_kernel=False)
    for a, b in zip(k, j):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

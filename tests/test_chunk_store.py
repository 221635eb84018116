"""ChunkStore: refcounting, dedupe, accounting invariants."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import make_local_tiers
from repro.core.chunk_store import ChunkCorruptionError, ChunkStore


def test_put_get_roundtrip():
    cs = ChunkStore(chunk_bytes=16)
    cid = cs.put(b"hello world")
    assert cs.get(cid) == b"hello world"
    assert cs.refs(cid) == 1


def test_dedupe_hits():
    cs = ChunkStore(chunk_bytes=16, dedupe=True)
    a = cs.put(b"same-bytes")
    b = cs.put(b"same-bytes")
    assert a == b
    assert cs.refs(a) == 2
    assert cs.stats.dedup_hits == 1
    assert cs.stats.physical_bytes == len(b"same-bytes")


def test_no_dedupe_when_disabled():
    cs = ChunkStore(chunk_bytes=16, dedupe=False)
    a = cs.put(b"same-bytes")
    b = cs.put(b"same-bytes")
    assert a != b
    assert cs.stats.physical_bytes == 2 * len(b"same-bytes")


def test_decref_frees():
    cs = ChunkStore(chunk_bytes=16)
    cid = cs.put(b"x" * 10)
    cs.incref(cid)
    cs.decref(cid)
    assert cid in cs
    cs.decref(cid)
    assert cid not in cs
    assert cs.stats.physical_bytes == 0
    assert cs.stats.chunks_alive == 0


def test_decref_underflow_raises():
    cs = ChunkStore()
    cid = cs.put(b"x")
    cs.decref(cid)
    with pytest.raises(Exception):
        cs.decref(cid)


def _joined(cs, ids, shape, dtype):
    """The reference read: join every chunk's logical bytes, slice, copy."""
    raw = b"".join(cs.get(c)[: len(cs.get(c)) - cs.pad_of(c)] for c in ids)
    nbytes = np.dtype(dtype).itemsize * int(np.prod(shape, dtype=np.int64))
    return np.frombuffer(raw[:nbytes], dtype=dtype).reshape(shape).copy()


ARRAYS = {
    "int64": np.arange(1000, dtype=np.int64),                 # 125 whole 64-byte chunks
    "float32-tail": np.arange(1, 334, dtype=np.float32),      # 1332 bytes: a short tail
    "float16-3d": np.arange(1, 106, dtype=np.float16).reshape(3, 5, 7),
    "int8-one-chunk": np.arange(1, 38, dtype=np.int8),        # 37 bytes in one chunk
    "bool": np.arange(50) % 3 == 0,
    "big-endian": np.arange(1, 25, dtype=">i4").reshape(2, 3, 4),
    "complex64": (np.arange(10) + 1j).astype(np.complex64),
    "0-d": np.array(3.5, np.float64),
}


@pytest.mark.parametrize("padded", [True, False], ids=["padded", "unpadded"])
@pytest.mark.parametrize("name", sorted(ARRAYS))
def test_array_roundtrip(name, padded):
    """``get_array`` fills one fresh, writable array: equal to the joined
    chunk bytes whether the tail chunk is stored zero-padded (``put_array``)
    or short (one ``put`` per piece)."""
    arr = ARRAYS[name]
    cs = ChunkStore(chunk_bytes=64)
    if padded:
        ids = cs.put_array(arr)
        assert len(ids) == -(-arr.nbytes // 64)
    else:
        raw = arr.tobytes()
        ids = tuple(cs.put(raw[o : o + 64]) for o in range(0, len(raw), 64))
    out = cs.get_array(ids, arr.shape, arr.dtype)
    assert out.dtype == arr.dtype and out.shape == arr.shape
    np.testing.assert_array_equal(out, arr)
    np.testing.assert_array_equal(out, _joined(cs, ids, arr.shape, arr.dtype))
    assert out.flags.writeable and out.flags.c_contiguous
    out.fill(0)                                   # the array is the caller's own
    np.testing.assert_array_equal(cs.get_array(ids, arr.shape, arr.dtype), arr)
    with pytest.raises(ValueError):               # more bytes than the chunks hold
        cs.get_array(ids, (arr.size + 64,), arr.dtype)


@pytest.mark.parametrize("case", ["verified", "healed", "quarantined", "demoted"])
def test_get_array_keeps_the_read_contract(case, tmp_path):
    """Verified reads re-hash every chunk (healing from a repair source or
    quarantining), demoted chunks fault back in, and a quarantined chunk
    fails the read on the fast path too."""
    arr = np.arange(1, 334, dtype=np.float32)
    tiers = make_local_tiers(str(tmp_path / "tiers")) if case == "demoted" else None
    cs = ChunkStore(chunk_bytes=256, verify_reads=case != "demoted", tiers=tiers)
    ids = cs.put_array(arr)
    good = cs.get(ids[1])
    if case == "verified":
        before = cs.repair_stats.verified_gets
        np.testing.assert_array_equal(cs.get_array(ids, arr.shape, arr.dtype), arr)
        assert cs.repair_stats.verified_gets - before == len(ids)
    elif case == "healed":
        cs.corrupt_chunk_for_test(ids[1])
        cs.attach_repair_source(lambda cid, digest, pad: good if cid == ids[1] else None)
        np.testing.assert_array_equal(cs.get_array(ids, arr.shape, arr.dtype), arr)
        assert cs.repair_stats.repaired == 1
    elif case == "quarantined":
        cs.corrupt_chunk_for_test(ids[1])
        with pytest.raises(ChunkCorruptionError):
            cs.get_array(ids, arr.shape, arr.dtype)
        assert cs.quarantined_ids() == [ids[1]]
        cs.verify_reads = False
        with pytest.raises(ChunkCorruptionError):
            cs.get_array(ids, arr.shape, arr.dtype)
    else:
        for cid in ids:
            assert cs.demote(cid)
        np.testing.assert_array_equal(cs.get_array(ids, arr.shape, arr.dtype), arr)
        assert all(cs.tier_of(cid) == "hot" for cid in ids)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.binary(min_size=1, max_size=64), min_size=1, max_size=40))
def test_accounting_invariant(blobs):
    """physical_bytes == sum of live unique chunks; logical tracks refs."""
    cs = ChunkStore(chunk_bytes=32, dedupe=True)
    ids = [cs.put(b) for b in blobs]
    live = {}
    for cid in ids:
        live[cid] = live.get(cid, 0) + 1
    expected_physical = sum(len(cs.get(cid)) for cid in set(ids))
    assert cs.stats.physical_bytes == expected_physical
    # drop all references; store must empty
    for cid in ids:
        cs.decref(cid)
    assert cs.stats.physical_bytes == 0
    assert len(cs) == 0

"""Each kernel's operation and byte count against a count by hand."""
import os

from bench.common import load_module

KERNELS = os.path.join(os.path.dirname(__file__), "..", "..", "bench", "kernels")


def kernel(name):
    return load_module(os.path.join(KERNELS, name + ".py"))


def test_fused_encode_reads_both_grids_and_writes_dirty_rows():
    # 8 rows of 1 KiB, capacity 4, 3 dirty: 2 x 8 KiB read, 3 KiB written,
    # 5 int32 of index and count
    flops, nbytes = kernel("fused_encode").cost(
        {"rows": 8, "row_bytes": 1024, "cap": 4, "count": 3})
    assert flops == 0.0
    assert nbytes == 2 * 8 * 1024 + 3 * 1024 + 4 * 5


def test_fused_encode_overflow_counts_capacity():
    _, nbytes = kernel("fused_encode").cost({"rows": 8, "row_bytes": 1024, "cap": 4, "count": 6})
    assert nbytes == 2 * 8 * 1024 + 4 * 1024 + 4 * 5


def test_paged_attention_counts_live_pages():
    # contexts 17 and 16 with 16-token pages: 2 + 1 pages; 2 kv heads of 4
    # dims in bf16; 4 query heads
    call = {"contexts": [17, 16], "page_size": 16, "heads": 4, "kv_heads": 2,
            "head_dim": 4, "itemsize": 2}
    flops, nbytes = kernel("paged_attention").cost(call)
    kv = 2 * (3 * 16 * 2 * 4 * 2)          # K and V of 3 pages
    q_out = 2 * (2 * 4 * 4 * 2)            # query in, output out, per sequence
    assert nbytes == kv + q_out
    assert flops == 4 * (17 + 16) * 4 * 4


def test_match_names():
    assert kernel("paged_attention").match("paged_attention", "jit_decode_step")
    assert not kernel("paged_attention").match("fusion.12", "jit_decode_step")

"""fused_encode: diff two row grids, compact the dirty rows, checksum them.

The work the call needs at its shapes: both grids read once (``rows`` x
``row_bytes`` each), the dirty rows written once to the compacted output,
and the row index and count written; no arithmetic worth counting.
"""


def match(op: str, module: str) -> bool:
    """The kernel's own operations: the Mosaic call in the encode program."""
    return "fused_encode" in module and ("custom" in op or "fused_encode" in op)


def cost(call):
    rows, row_bytes = call["rows"], call["row_bytes"]
    dirty = min(call["count"], call["cap"])
    return 0.0, 2.0 * rows * row_bytes + dirty * row_bytes + 4.0 * (call["cap"] + 1)

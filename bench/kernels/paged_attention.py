"""paged_attention: one query token per sequence over its paged KV cache.

The work the call needs at its shapes, per sequence of ``c`` live
positions: the K and V of its live pages read once
(``ceil(c / page_size)`` pages), the query read and the output written;
``4 * c * heads * head_dim`` FLOPs (QK^T and PV).
"""


def match(op: str, module: str) -> bool:
    return "decode_step" in module and "paged_attention" in op


def cost(call):
    psz, kvh, hd, h = call["page_size"], call["kv_heads"], call["head_dim"], call["heads"]
    isz = call["itemsize"]
    flops = nbytes = 0.0
    for c in call["contexts"]:
        pages = -(-c // psz)
        nbytes += 2.0 * pages * psz * kvh * hd * isz + 2.0 * h * hd * isz
        flops += 4.0 * c * h * hd
    return flops, nbytes

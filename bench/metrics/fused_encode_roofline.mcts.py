"""fused_encode_roofline.mcts: the dump-encode kernel's share of its roofline."""
from bench.readers import roofline


def read(run):
    return roofline(run, "fused_encode", run.kernel_calls.get("fused_encode", []))

"""ckpt_p90_ms.mcts: 90th percentile of the synchronous checkpoint call."""
from bench.readers import p90


def read(run):
    return p90(run, "ckpt")

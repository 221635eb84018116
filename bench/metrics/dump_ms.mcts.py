"""dump_ms.mcts: median wall time of the dumps that landed by the end of the window."""
from bench.readers import median


def read(run):
    return median(run.values.get("dump_wall_ms", []))

"""mcts.cr_share: share of the window spent inside checkpoint and rollback calls."""
from bench.readers import samples


def read(run):
    xs = samples(run, "ckpt", "rollback")
    return 100.0 * sum(xs) / 1e3 / run.window_s if xs else None

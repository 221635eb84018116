"""restore_wait_ms.mcts: mean time a slow restore waits for its dump image
(``deltacr.image_wait``), per slow ``deltacr.restore``."""
from bench.spans import per_slow_restore_ms


def read(run):
    return per_slow_restore_ms(run, "deltacr.image_wait")

"""restore_decode_ms.mcts: mean time decoding the dump image to host arrays
(``deltacr.decode``), per slow ``deltacr.restore``."""
from bench.spans import per_slow_restore_ms


def read(run):
    return per_slow_restore_ms(run, "deltacr.decode")

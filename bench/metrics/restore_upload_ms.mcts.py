"""restore_upload_ms.mcts: mean time putting the decoded pages back into the page pool
(``deltacr.upload``), per slow ``deltacr.restore``."""
from bench.spans import per_slow_restore_ms


def read(run):
    return per_slow_restore_ms(run, "deltacr.upload")

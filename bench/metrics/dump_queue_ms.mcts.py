"""dump_queue_ms.mcts: median time a dump waited in the worker's FIFO (``queued_ms``
of the ``dump`` spans started in the window)."""
from bench.readers import median
from bench.spans import started


def read(run):
    return median([float(sp["args"]["queued_ms"]) for sp in started(run, "dump")])

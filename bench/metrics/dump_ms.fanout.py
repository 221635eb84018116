"""dump_ms.fanout: median wall time of the ``dump`` spans that ended in the window."""
from bench.readers import median
from bench.spans import ended, ms


def read(run):
    return median([ms(sp) for sp in ended(run, "dump")])

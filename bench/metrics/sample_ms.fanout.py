"""sample_ms.fanout: mean host time sampling the batch's next tokens (``engine.sample``)
per ``engine.step`` of the window."""
from bench.spans import ms, started


def read(run):
    steps = started(run, "engine.step", main=True)
    if not steps:
        return None
    return sum(ms(sp) for sp in started(run, "engine.sample", main=True)) / len(steps)

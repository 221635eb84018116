"""rollback_slow_ms: median host time of a slow-path rollback (dump wait, image decode, upload)."""
from bench.readers import median, samples


def read(run):
    return median(samples(run, "rollback_slow"))

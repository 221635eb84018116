"""rollback_p90_ms: 90th percentile of every rollback in the window, fast and slow."""
from bench.readers import p90


def read(run):
    return p90(run, "rollback_fast", "rollback_slow")

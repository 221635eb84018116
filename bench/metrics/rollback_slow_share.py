"""rollback_slow_share: rollbacks that missed the template pool, of all rollbacks."""


def read(run):
    slow = run.rec.counts.get("rollbacks_slow", 0)
    fast = run.rec.counts.get("rollbacks_fast", 0)
    return 100.0 * slow / (slow + fast) if slow + fast else None

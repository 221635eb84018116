"""dump_backlog.fanout: checkpoints with a dump (``deltacr.checkpoint`` spans with ``dump`` set)
started in the window whose ``dump`` span, joined by ``cr`` and ``ckpt``, did not end in it."""
from bench.spans import ended, of_run, started


def read(run):
    ps = of_run(run)
    if not ps or not ps["spans"]:
        return None                  # a program that emits no spans
    landed = {(sp["args"]["cr"], sp["args"]["ckpt"]) for sp in ended(run, "dump")}
    return sum(1 for sp in started(run, "deltacr.checkpoint")
               if sp["args"].get("dump") and (sp["args"]["cr"], sp["args"]["ckpt"]) not in landed)

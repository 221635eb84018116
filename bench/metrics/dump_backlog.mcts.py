"""dump_backlog.mcts: dumps submitted in the window and not landed at its end."""


def read(run):
    return run.values.get("dump_backlog")

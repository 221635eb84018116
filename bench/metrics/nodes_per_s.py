"""nodes_per_s: search nodes expanded in the window, over the window."""
from bench.readers import per_window


def read(run):
    return per_window(run, "nodes")

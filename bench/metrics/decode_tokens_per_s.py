"""decode_tokens_per_s: tokens decoded by all rollouts in the window, over the window."""
from bench.readers import per_window


def read(run):
    return per_window(run, "tokens")

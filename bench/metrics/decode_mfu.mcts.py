"""decode_mfu.mcts: model FLOPs decoded in the window as a share of the peak."""
from bench.readers import decode_mfu as read  # noqa: F401

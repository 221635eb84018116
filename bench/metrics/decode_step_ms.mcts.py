"""decode_step_ms.mcts: host time of one Engine.step, over the steps of the window."""
from bench.readers import decode_step_ms as read  # noqa: F401

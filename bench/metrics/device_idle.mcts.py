"""device_idle.mcts: share of the traced window in which no operation ran on the device."""
from bench.readers import device_idle as read  # noqa: F401

"""The model family of a configuration: where the benchmark's
model-specific pieces come from.

A configuration file (``bench/configs/<name>.json``) names its family under
``"model"``; the family is the module ``bench/models/<model>.py`` of the
run's checkout, loaded by path.  Nothing else in ``bench/`` names a family,
so a family added later is a new file under ``bench/models/`` plus a
configuration file that names it.

Every family module defines:

* ``dims(config)``: the sizes the harness reads, with at least ``.vocab``,
  ``.dtype`` (the served dtype's name), and ``.heads``, ``.kv_heads`` and
  ``.head_dim`` of its paged-attention layers;
* ``program_config(config)``: the program's ``ModelConfig`` for the file
  (the system under test), refusing a file the program cannot run as stated;
* ``make_weights(dims, seed)``: the weights from ``seed``, made on the
  device in one jitted call in the served dtype, as the program's
  parameter tree;
* ``decode_flops(dims, context) -> float``: model FLOPs of one decoded token
  at ``context`` live positions (``decode_mfu.*``);
* ``attention_layers(dims) -> int``: how many layers run paged attention,
  one kernel call each per decode step (``paged_attention_roofline.*``);
* ``Reference(dims, control=False)`` with ``.gaps(weights, prompt, rest,
  check)``: the plain reference, which imports nothing of the program, and
  with ``control`` its lower-precision control (``logit_gap``; see
  ``bench/models/dense.py`` for what ``gaps`` returns).
"""
from __future__ import annotations

import os
from typing import Any, Dict

from bench.common import load_module


def family(config: Dict[str, Any], root: str):
    """The family module that ``config`` names, from ``root/bench/models``."""
    name = config.get("model")
    if not name:
        raise KeyError(f"configuration {config.get('name')!r} names no model family (key 'model')")
    path = os.path.join(root, "bench", "models", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"configuration {config.get('name')!r} names model family {name!r}: "
            f"no file bench/models/{name}.py")
    return load_module(path)

"""The one generator of the benchmark's traffic, driven by a mix's data file.

A mix (``bench/traffic/<mix>.json``) gives the parameters: prompt lengths,
fan-out, turns, tree shape, and the sandbox's repository archetype.  Every
input is drawn from ``--seed``; the same seed gives the same inputs.  The
repository edits follow the SWE-bench trajectory archetypes of the paper
(Table 2): a repo of ``n_files`` tensors of ``file_kb`` KiB, of which a
mutating action rewrites ``edit_fraction`` of ``write_files_per_step``
files, and a read-only action (probability ``readonly_prob``) reads a few.
"""
from __future__ import annotations

import hashlib
from typing import Any, Dict, List

import numpy as np


def rng(seed: int, *salt: Any) -> np.random.Generator:
    """A generator for one purpose of one run: ``seed`` plus a salt."""
    h = hashlib.blake2b(repr((int(seed),) + salt).encode(), digest_size=16).digest()
    return np.random.default_rng(int.from_bytes(h, "little"))


def prompts(seed: int, n: int, length: int, vocab: int) -> List[List[int]]:
    """``n`` prompts of ``length`` token ids drawn uniformly from the vocab."""
    g = rng(seed, "prompts")
    return [[int(t) for t in g.integers(0, vocab, size=length)] for _ in range(n)]


def sub_seed(seed: int, *salt: Any) -> int:
    """A 31-bit seed for one rollout or action."""
    return int(rng(seed, *salt).integers(0, 2**31 - 1))


# ------------------------------------------------------------ repository
def build_repo(fs, repo: Dict[str, Any], seed: int) -> None:
    """Write the archetype's repository into a DeltaFS."""
    g = rng(seed, "repo")
    elems = int(repo["file_kb"]) * 1024 // 4
    for i in range(int(repo["n_files"])):
        fs.write(f"repo/file_{i:04d}", g.standard_normal(elems).astype(np.float32))


def edit_repo(fs, repo: Dict[str, Any], action_seed: int) -> bool:
    """One tool action on the repository; returns True if it wrote."""
    g = np.random.default_rng(action_seed)
    if g.random() < float(repo["readonly_prob"]):
        for k in fs.keys()[:4]:
            fs.read(k)
        return False
    ids = g.integers(0, int(repo["n_files"]), size=int(repo["write_files_per_step"]))
    for fid in ids:
        key = f"repo/file_{int(fid):04d}"
        arr = fs.read(key)
        n = max(1, int(arr.size * float(repo["edit_fraction"])))
        pos = int(g.integers(0, max(arr.size - n, 1)))
        arr[pos : pos + n] = g.standard_normal(n).astype(arr.dtype)
        fs.write(key, arr)
    return True


def leave_test_artifacts(fs, value_seed: int) -> None:
    """What running the tests leaves behind (undone by value isolation)."""
    fs.write("repo/__pycache__", np.full(256, value_seed & 0x7FFFFFFF, np.int32))

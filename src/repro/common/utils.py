"""Small shared utilities: padding, tree math, timing."""
from __future__ import annotations

import math
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


#: the repository checkout (this file is ``src/repro/common/utils.py``)
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         "..", ".."))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points (scripts, benchmarks, examples) call this once before
    compiling anything; importing the library never does.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
    changes.  Otherwise the cache lives at the fixed
    ``<repo>/.cache/jax_compile``, so every run of this checkout finds
    what an earlier one stored."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".cache", "jax_compile")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def scan_unroll(length: int) -> int:
    """Unroll factor for lax.scan loops.

    The dry-run sets REPRO_UNROLL_SCANS=1 so every scan fully unrolls into
    its (single-iteration) while body — XLA's cost_analysis counts while
    bodies exactly once, so this is what makes HLO_FLOPs and the parsed
    collective bytes reflect the *whole* step instead of one iteration.
    Normal execution keeps unroll=1 (compact HLO, fast compiles).
    """
    return length if os.environ.get("REPRO_UNROLL_SCANS") == "1" else 1


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def pad_to_multiple(x: int, m: int) -> int:
    """Round ``x`` up to the next multiple of ``m``."""
    return ceil_div(x, m) * m


def tree_size_bytes(tree: Any) -> int:
    """Total bytes of all array leaves (works on ShapeDtypeStruct too)."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    return total


def tree_param_count(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(l.shape)) for l in leaves if hasattr(l, "shape"))


class Timer:
    """Context timer used by benchmarks."""

    def __init__(self) -> None:
        self.elapsed = 0.0

    def __enter__(self) -> "Timer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.elapsed = time.perf_counter() - self._t0


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}PiB"


def human_flops(n: float) -> str:
    for unit in ("F", "KF", "MF", "GF", "TF", "PF"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}EF"

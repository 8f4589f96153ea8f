"""Shared small utilities: pytree helpers, dtype helpers, timing, the tiny
on-disk JSON cache used by kernel autotuning and routing calibration, the
one-process-per-TPU guard, and the persistent compile cache setup."""
from __future__ import annotations

import json
import os
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


def tree_size(tree: Any) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_cast(tree: Any, dtype) -> Any:
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def has_nan(tree: Any) -> bool:
    leaves = [jnp.any(jnp.isnan(x)) for x in jax.tree.leaves(tree) if jnp.issubdtype(x.dtype, jnp.floating)]
    if not leaves:
        return False
    return bool(jax.device_get(jnp.any(jnp.stack(leaves))))


def block_until_ready(tree: Any) -> Any:
    return jax.tree.map(lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x, tree)


def timeit(fn: Callable, *args, warmup: int = 1, iters: int = 3, **kwargs) -> tuple[float, Any]:
    """Wall-clock a jitted fn; returns (best seconds, last output)."""
    out = None
    for _ in range(warmup):
        out = block_until_ready(fn(*args, **kwargs))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = block_until_ready(fn(*args, **kwargs))
        best = min(best, time.perf_counter() - t0)
    return best, out


# -- on-disk JSON cache -----------------------------------------------------
#
# Both the kernel tile autotuner (kernels/autotune.py) and the routing
# calibration (core/routing.py) measure machine facts that outlive the
# process. They persist here: ${REPRO_CACHE_DIR:-~/.cache/repro-sven}/
# <kind>.json. Every entry key embeds whatever invalidates it (platform,
# device count, jax version, shape bucket) so one flat file per kind
# suffices. All failures — read-only HOME, corrupt JSON, races — degrade to
# "no cache", never to an exception on the solve path.

def cache_dir() -> Optional[Path]:
    """The persistent cache directory, or None when unwritable."""
    root = os.environ.get("REPRO_CACHE_DIR") or os.path.join(
        os.path.expanduser("~"), ".cache", "repro-sven")
    try:
        p = Path(root)
        p.mkdir(parents=True, exist_ok=True)
        return p
    except OSError:
        return None


def disk_cache_load(kind: str) -> dict:
    """Read `<cache_dir>/<kind>.json`; {} on any failure."""
    d = cache_dir()
    if d is None:
        return {}
    try:
        with open(d / f"{kind}.json", encoding="utf-8") as f:
            out = json.load(f)
        return out if isinstance(out, dict) else {}
    except (OSError, ValueError):
        return {}


def disk_cache_update(kind: str, entries: dict) -> bool:
    """Merge `entries` into `<cache_dir>/<kind>.json` atomically
    (write-temp + rename, so concurrent processes see old or new, never
    torn). Returns False when persistence is unavailable."""
    d = cache_dir()
    if d is None:
        return False
    merged = disk_cache_load(kind)
    merged.update(entries)
    try:
        fd, tmp = tempfile.mkstemp(dir=d, prefix=f".{kind}-", suffix=".json")
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        os.replace(tmp, d / f"{kind}.json")
        return True
    except OSError:
        return False


def refuse_on_tpu(what: str) -> None:
    """Raise before `what` starts child processes that import JAX.

    A TPU belongs to one process at a time: once this process has touched
    JAX it holds the chip, and a child that needs the chip fails or hangs.
    """
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what} starts child processes that need JAX, and a TPU belongs "
            f"to one process; run it on the CPU (JAX_PLATFORMS=cpu)")


# -- persistent compilation cache ------------------------------------------

#: where compiled executables persist when JAX_COMPILATION_CACHE_DIR is
#: unset: a fixed directory inside the checkout (listed in .gitignore)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    `JAX_COMPILATION_CACHE_DIR` wins when set, and then no other directory
    is configured. Otherwise the cache lives at `COMPILE_CACHE_DIR`. The
    path is part of each entry's key, so it is never derived from a temp
    name, a pid or the time: a directory that moves never hits.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def pretty_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}PiB"

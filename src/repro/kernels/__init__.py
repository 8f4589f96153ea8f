"""Pallas TPU kernels for the SVEN hot spots, with pure-jnp oracles and a
per-backend registry.

Public surface:

  - `ops` — the jitted entry points (`shifted_gram`, `hinge_hessian_matvec`,
    `hinge_stats`): backend resolution and tiling/padding/precision
    handling;
  - `registry` — the op -> body table and the `backend` enum
    (`resolve_kernel_backend`, `lookup`, `kernel_backends`);
  - `autotune` — per-(body, shape-bucket) tile selection with an on-disk
    winner cache (`tiles_for`, `resolve_tiles`);
  - `ref` — the pure-jnp oracles, the correctness ground truth every kernel
    is parity-tested against (`tests/test_kernels.py`,
    `tests/test_kernels_surface.py`).

The ops are re-exported at package level and load on first use: only
`registry`, the backend and precision names `core/sven.py` validates
against, loads with the package. `core/sven.py` selects the ops via
`SvenConfig(backend=...)`. Raw kernel bodies (`gram`, `hinge`,
`hinge_stats` modules) are implementation detail — call through `ops`,
which owns backend lookup, tiling and padding.
"""
import importlib

from repro.kernels import registry
from repro.kernels.registry import resolve_kernel_backend

_OPS = ("shifted_gram", "sharded_shifted_gram", "hinge_hessian_matvec",
        "hinge_stats", "resolve_interpret")

__all__ = ["ops", "ref", "registry", "autotune", *_OPS,
           "resolve_kernel_backend"]


def __getattr__(name: str):
    # The first use of anything but `registry` imports `ops`, which imports
    # and registers every body. Pallas comes with it (about 1.7 s), which a
    # process that only names the backend enum never pays.
    ops = importlib.import_module(f"{__name__}.ops")
    # the ops shadow same-named submodules (`hinge_stats`)
    globals().update((fn, getattr(ops, fn)) for fn in _OPS)
    if name in globals():
        return globals()[name]
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

"""Per-backend kernel registry: one `backend` enum replaces the old
`use_pallas: bool` + `interpret: bool` two-flag maze (DESIGN.md §10).

Every logical op (`shifted_gram`, `hinge_stats`, `hinge_xtv`/`hinge_xd`,
`sharded_shifted_gram`) resolves to exactly one of three BODIES:

    "tpu"   the Pallas TPU kernel (kernels/gram.py, hinge.py, hinge_stats.py)
    "gpu"   the Pallas GPU (Triton) kernel (kernels/gram_gpu.py,
            hinge_stats_gpu.py) — k-loop inside the program, no TPU scratch
    "ref"   the pure-jnp oracle (kernels/ref.py) — also the XLA escape hatch

and a RESOLVED backend names a body plus how it executes:

    "tpu" | "gpu"                      compiled Pallas for that platform
    "tpu_interpret" | "gpu_interpret"  the same body under Pallas interpret
                                       mode (how CPU CI exercises both code
                                       paths without an accelerator)
    "ref"                              the jnp oracle under plain XLA

Resolution is OPERAND-DRIVEN, never trace-time backend sniffing (the §9.3
bugfix): `resolve_kernel_backend(None, *arrays)` reads the platform of the
first concrete operand's committed devices — tpu -> "tpu", gpu -> "gpu",
cpu -> "tpu_interpret" (the historical CPU default) — with the process
default backend only as the numpy/tracer fallback. An explicit resolved
backend always wins. Traced call sites thread `SvenConfig.backend`, pinned
pre-trace by `core.sven.resolve_backend`, so the choice is part of the
static jit key.

`lookup` serves the "ref" oracle in place of a missing body only for the
pairs in `REF_FALLBACKS`, the README "Backends & precision" matrix: the
hinge Hessian mat-vec has no Triton body (GEMV-shaped, memory-bound;
cuBLAS under XLA is the honest choice), so "gpu" serves it from the
oracle. Any other missing body raises — in particular no op resolved to
"tpu" silently runs the oracle. `kernel_backends(op)` reports what is
actually registered.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax

#: the three kernel bodies a logical op may register
BODIES = ("tpu", "gpu", "ref")

#: every resolved backend value accepted by the ops layer / SvenConfig
RESOLVED_BACKENDS = ("tpu", "gpu", "tpu_interpret", "gpu_interpret", "ref")

#: platform -> resolved backend (the "auto" rule)
_PLATFORM_DEFAULT = {
    "tpu": "tpu",
    "gpu": "gpu",
    "cuda": "gpu",
    "rocm": "gpu",
    "cpu": "tpu_interpret",
}

#: (op, body) pairs the README matrix serves from the "ref" oracle
REF_FALLBACKS = frozenset({("hinge_xtv", "gpu"), ("hinge_xd", "gpu")})

_REGISTRY: dict[tuple[str, str], Callable] = {}


def register(op: str, body: str):
    """Class the decorated callable as `op`'s kernel body for `body`."""
    if body not in BODIES:
        raise ValueError(f"register: body must be one of {BODIES}, got {body!r}")

    def deco(fn: Callable) -> Callable:
        _REGISTRY[(op, body)] = fn
        return fn

    return deco


def lookup(op: str, backend: str) -> tuple[Callable, str, bool]:
    """Resolve (impl, body, interpret) for a RESOLVED backend.

    Falls back to the "ref" body only where `REF_FALLBACKS` (the README
    "Backends & precision" matrix) says the platform has no kernel for this
    op; any other missing body is a KeyError.
    """
    if backend not in RESOLVED_BACKENDS:
        raise ValueError(
            f"lookup({op!r}): backend must be resolved "
            f"({RESOLVED_BACKENDS}), got {backend!r} — call "
            f"resolve_kernel_backend first")
    body, interpret = split_backend(backend)
    if (op, body) in _REGISTRY:
        return _REGISTRY[(op, body)], body, interpret
    if (op, body) in REF_FALLBACKS and (op, "ref") in _REGISTRY:
        return _REGISTRY[(op, "ref")], "ref", False
    raise KeyError(f"no kernel body registered for op {op!r} "
                   f"(backend {backend!r}); registered: {kernel_backends(op)}")


def split_backend(backend: str) -> tuple[str, bool]:
    """Resolved backend -> (body, interpret) pair."""
    if backend.endswith("_interpret"):
        return backend[: -len("_interpret")], True
    return backend, False


def kernel_backends(op: str) -> tuple[str, ...]:
    """The bodies registered for `op` (subset of BODIES)."""
    return tuple(b for b in BODIES if (op, b) in _REGISTRY)


def registered_ops() -> tuple[str, ...]:
    return tuple(sorted({op for op, _ in _REGISTRY}))


def resolve_kernel_backend(backend: Optional[str], *arrays) -> str:
    """Pin the kernel backend for a launch (the one-enum successor of
    `resolve_interpret`).

    An explicit RESOLVED backend always wins. `None` / `"auto"` / the
    deprecated `"pallas"` resolve from the platform(s) the first CONCRETE
    array operand is committed to — the devices the kernel will actually
    run on — not from the process default backend (wrong for arrays placed
    on a non-default device, meaningless inside a trace). Tracers and
    numpy inputs carry no device, so the process default platform remains
    the last-resort fallback only.
    """
    if backend is not None and backend not in ("auto", "pallas"):
        if backend not in RESOLVED_BACKENDS:
            raise ValueError(
                f"resolve_kernel_backend: unknown backend {backend!r} "
                f"(expected one of {RESOLVED_BACKENDS} or 'auto')")
        return backend
    for a in arrays:
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
            try:
                platforms = {d.platform for d in a.devices()}
            except Exception:  # noqa: BLE001 — abstract/deleted arrays
                continue
            if len(platforms) == 1:
                return _PLATFORM_DEFAULT.get(platforms.pop(), "ref")
            if platforms:
                return "ref"           # mixed placements: oracle is safe
    return _PLATFORM_DEFAULT.get(jax.default_backend(), "ref")

"""Per-backend kernel registry: one `backend` enum names the kernel body
and how it executes (DESIGN.md §10).

Every logical op (`shifted_gram`, `hinge_stats`, `hinge_xtv`/`hinge_xd`,
`sharded_shifted_gram`) resolves to exactly one of two BODIES:

    "tpu"   the Pallas TPU kernel (kernels/gram.py, hinge.py, hinge_stats.py)
    "ref"   the pure-jnp oracle (kernels/ref.py) — also the XLA escape hatch

and a RESOLVED backend names a body plus how it executes:

    "tpu"            compiled Pallas for the TPU
    "tpu_interpret"  the same body under Pallas interpret mode (how the CPU
                     test suite exercises it without an accelerator)
    "ref"            the jnp oracle under plain XLA

Resolution is OPERAND-DRIVEN, never trace-time backend sniffing (the §9.3
bugfix): `resolve_kernel_backend(None, *arrays)` reads the platform of the
first concrete operand's committed devices — tpu -> "tpu", cpu ->
"tpu_interpret", any other platform -> "ref" — with the process default
backend only as the numpy/tracer fallback. An explicit resolved backend
always wins. Traced call sites thread `SvenConfig.backend`, pinned
pre-trace by `core.sven.resolve_backend`, so the choice is part of the
static jit key.

`lookup` never substitutes one body for another: a missing body raises, so
no op resolved to "tpu" silently runs the oracle. `kernel_backends(op)`
reports what is actually registered.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax

#: the kernel bodies a logical op may register
BODIES = ("tpu", "ref")

#: every resolved backend value accepted by the ops layer / SvenConfig
RESOLVED_BACKENDS = ("tpu", "tpu_interpret", "ref")

#: kernel MAC/storage precisions accepted by the ops layer / SvenConfig
PRECISIONS = ("f32", "bf16")

#: platform -> resolved backend (the "auto" rule; other platforms -> "ref")
_PLATFORM_DEFAULT = {
    "tpu": "tpu",
    "cpu": "tpu_interpret",
}

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
    """Resolve (impl, body, interpret) for a RESOLVED backend; a missing
    body is a KeyError."""
    if backend not in RESOLVED_BACKENDS:
        raise ValueError(
            f"lookup({op!r}): backend must be resolved "
            f"({RESOLVED_BACKENDS}), got {backend!r} — call "
            f"resolve_kernel_backend first")
    body, interpret = split_backend(backend)
    if (op, body) in _REGISTRY:
        return _REGISTRY[(op, body)], body, interpret
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
    """Pin the kernel backend for a launch.

    An explicit RESOLVED backend always wins. `None` / `"auto"` resolve
    from the platform(s) the first CONCRETE array operand is committed to —
    the devices the kernel will actually run on — not from the process
    default backend (wrong for arrays placed on a non-default device,
    meaningless inside a trace). Tracers and
    numpy inputs carry no device, so the process default platform remains
    the last-resort fallback only.
    """
    if backend is not None and backend != "auto":
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

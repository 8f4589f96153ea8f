"""Tile autotuner for the Pallas kernel bodies.

Tile shapes (bm, bn, bk) that saturate one accelerator generation are
mediocre on the next; hardcoded defaults are how a "GPU-speed" claim decays.
This module picks tiles the same way GPU-SVM practice does (Rgtsvm tunes
its kernel-evaluation tile to the card): measure a few candidates ONCE per
(body, shape-bucket) on the chip at hand and reuse the winner.

Mechanics:

  * shapes are BUCKETED to the next power of two (capped, so a 1e6-row
    problem is tuned on a bounded probe) — tiles depend on how a problem
    fills the machine, not its exact dims, and buckets keep the candidate
    sweep from re-running per shape;
  * measurement happens only on the COMPILED backend ("tpu"). Interpret
    mode is a pure-Python emulator whose timings are pathological and
    meaningless, and the ref body has no tiles — both get the static
    defaults instantly;
  * winners cache in-process and persist via `utils.disk_cache_*` under the
    `autotune` kind, keyed (op, body, shape-bucket, dtype, jax version), so
    repeat processes skip the sweep the same way routing calibration skips
    its microbenchmark.

The ops layer (kernels/ops.py) consults `tiles_for(op, backend, n, p)` only
when the caller did not pin tiles explicitly — explicit tile kwargs always
win, which is also the escape hatch if a measured winner misbehaves.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import utils
from repro.kernels import registry

# candidates per body — orderings chosen so the FIRST entry is the static
# default used whenever measurement is unavailable. TPU tiles are MXU/VREG
# multiples.
GRAM_CANDIDATES = {
    "tpu": ((128, 128, 128), (256, 128, 128), (128, 128, 256),
            (128, 256, 128)),
    "ref": ((128, 128, 128),),
}
HINGE_STATS_CANDIDATES = {
    "tpu": ((512, 512), (256, 512), (512, 256), (256, 256)),
    "ref": ((512, 512),),
}
_TILE_NAMES = {"shifted_gram": ("bm", "bn", "bk"),
               "hinge_stats": ("bp", "bk")}
_CANDIDATES = {"shifted_gram": GRAM_CANDIDATES,
               "hinge_stats": HINGE_STATS_CANDIDATES}

#: probe caps: tuning happens on min(bucket, cap)-sized synthetic operands
_N_CAP = 8192
_P_CAP = 1024

_MEMORY: dict = {}


def shape_bucket(n: int, p: int) -> tuple[int, int]:
    """Next power of two per dim (floor 8, probe-capped)."""
    return min(_pow2(n), _N_CAP), min(_pow2(p), _P_CAP)


def _pow2(sz: int) -> int:
    b = 8
    while b < sz:
        b *= 2
    return b


def clear_autotune_cache() -> None:
    """Drop the in-process winners (the disk cache is left alone — delete
    `<cache_dir>/autotune.json` to force re-measurement across processes)."""
    _MEMORY.clear()


def _key(op: str, body: str, nb: int, pb: int, dtype) -> str:
    return f"{op}|{body}|{nb}x{pb}|{jnp.dtype(dtype).name}|jax{jax.__version__}"


def _clamp(tiles: tuple, op: str, nb: int, pb: int) -> tuple:
    """Shrink candidate tiles that exceed the bucket (tiny problems)."""
    names = _TILE_NAMES[op]
    dims = {"bm": pb, "bn": pb, "bp": pb, "bk": nb}
    return tuple(max(min(t, _pow2(dims[nm])), 8)
                 for t, nm in zip(tiles, names))


def _measure_candidate(op: str, body: str, tiles: tuple,
                       nb: int, pb: int, dtype) -> float:
    """Best-of-3 wall clock of one raw kernel body on bucket-sized ones()."""
    impl, got_body, interpret = registry.lookup(op, body)
    assert got_body == body and not interpret
    X = jnp.ones((nb, pb), dtype)
    v2d = jnp.ones((nb, 1), dtype if op == "shifted_gram" else jnp.float32)
    if op == "shifted_gram":
        bm, bn, bk = tiles
        scal = jnp.ones((1, 1), jnp.float32)
        fn = lambda: impl(X, v2d, scal, bm=bm, bn=bn, bk=bk)
    else:
        bp, bk = tiles
        scal = jnp.ones((2, 1), jnp.float32)
        fn = lambda: impl(X, v2d, v2d, scal, bp=bp, bk=bk)
    best, _ = utils.timeit(jax.jit(fn), warmup=1, iters=3)
    return best


def resolve_tiles(op: str, backend: str, n: int, p: int,
                  dtype=jnp.float32,
                  measure: Optional[Callable] = None) -> tuple[dict, str]:
    """(tiles, source) for one kernel launch.

    `backend` is a RESOLVED backend (registry.RESOLVED_BACKENDS). Source is
    one of "default" (static, no measurement possible), "memory", "disk",
    or "measured" (sweep ran here). `measure` overrides the timing probe —
    the test seam. Raises RuntimeError when no candidate compiles.
    """
    body, interpret = registry.split_backend(backend)
    if op not in _CANDIDATES:
        raise ValueError(f"resolve_tiles: unknown op {op!r} "
                         f"(expected one of {sorted(_CANDIDATES)})")
    cands = _CANDIDATES[op][body]
    nb, pb = shape_bucket(n, p)
    names = _TILE_NAMES[op]
    default = dict(zip(names, _clamp(cands[0], op, nb, pb)))
    if interpret or body == "ref" or len(cands) == 1:
        return default, "default"

    key = _key(op, body, nb, pb, dtype)
    if key in _MEMORY:
        return dict(zip(names, _MEMORY[key])), "memory"
    disk = utils.disk_cache_load("autotune")
    if key in disk and isinstance(disk[key], list):
        tiles = tuple(int(t) for t in disk[key])
        if len(tiles) == len(names):
            _MEMORY[key] = tiles
            return dict(zip(names, tiles)), "disk"

    probe = measure or _measure_candidate
    seen: dict[tuple, float] = {}
    errors: dict[tuple, Exception] = {}
    for cand in cands:
        tiles = _clamp(cand, op, nb, pb)
        if tiles in seen or tiles in errors:
            continue
        try:
            seen[tiles] = probe(op, body, tiles, nb, pb, dtype)
        except Exception as e:  # noqa: BLE001 — a candidate the compiler
            errors[tiles] = e   # rejects (register pressure, VMEM) drops out
    if not seen:
        # no tile compiles: the kernel itself is broken on this backend, and
        # handing back the default would only defer the same failure
        first = next(iter(errors.values()))
        raise RuntimeError(
            f"autotune: no {op} tile candidate compiled on backend "
            f"{backend!r} at bucket {nb}x{pb}; tried {sorted(errors)}; "
            f"first error: {first}") from first
    winner = min(seen, key=seen.get)
    _MEMORY[key] = winner
    utils.disk_cache_update("autotune", {key: list(winner)})
    return dict(zip(names, winner)), "measured"


def tiles_for(op: str, backend: str, n: int, p: int,
              dtype=jnp.float32) -> dict:
    """The tile kwargs for `op` on `backend` at shape (n, p) — what
    kernels/ops.py splices in when the caller didn't pin tiles."""
    return resolve_tiles(op, backend, n, p, dtype)[0]

"""Determinism rules: every random stream in the library is seeded and
injectable. Global-RNG draws make solver parity runs (the <=1e-10 gates in
the tests) unreproducible, and time-seeded RNGs make CI flakes
undiagnosable."""
from __future__ import annotations

import ast

from ..registry import RawFinding, Rule, RuleMeta, register

#: numpy.random entry points that are NOT the legacy global stream
_NP_RANDOM_OK = ("numpy.random.default_rng", "numpy.random.Generator",
                 "numpy.random.SeedSequence", "numpy.random.PCG64",
                 "numpy.random.PCG64DXSM", "numpy.random.Philox",
                 "numpy.random.MT19937", "numpy.random.RandomState")

_TIME_SOURCES = ("time.time", "time.time_ns", "time.perf_counter",
                 "time.perf_counter_ns", "time.monotonic",
                 "time.monotonic_ns")

_SEEDED_CTORS = ("numpy.random.default_rng", "numpy.random.SeedSequence",
                 "random.Random", "jax.random.PRNGKey", "jax.random.key")


@register
class GlobalRng(Rule):
    """DET001: draws from the process-global RNG.

    `np.random.rand(...)`-style legacy calls and stdlib `random.*` share
    hidden global state across tests and runs; the repo idiom is a
    seeded `np.random.default_rng(seed)` (or `jax.random.key`) passed down
    explicitly.
    """

    meta = RuleMeta(
        id="DET001", name="global-rng",
        summary="no process-global RNG draws (np.random legacy / random.*)",
        default_include=("src",))

    def check(self, ctx):
        for call in ctx.calls():
            name = ctx.resolve(call.func)
            if not name:
                continue
            if name.startswith("numpy.random.") and name not in _NP_RANDOM_OK:
                yield RawFinding(
                    call.lineno, call.col_offset,
                    f"`{name}` draws from the global numpy RNG — use a "
                    "seeded np.random.default_rng(seed) passed explicitly")
            elif name.startswith("random.") and name != "random.Random":
                yield RawFinding(
                    call.lineno, call.col_offset,
                    f"`{name}` draws from the global stdlib RNG — use a "
                    "seeded generator object")


@register
class UnseededRng(Rule):
    """DET002: RNG constructed without a seed, or seeded from the clock.

    `default_rng()` (OS entropy) and `default_rng(int(time.time()))` both
    make a run unrepeatable; seeds are explicit constants or flow from
    config/args.
    """

    meta = RuleMeta(
        id="DET002", name="unseeded-rng",
        summary="RNGs take explicit, non-clock seeds",
        default_include=("src",))

    def check(self, ctx):
        for call in ctx.calls():
            name = ctx.resolve(call.func)
            if name not in _SEEDED_CTORS:
                continue
            if not call.args and not call.keywords:
                yield RawFinding(
                    call.lineno, call.col_offset,
                    f"`{name}()` without a seed is entropy-seeded — pass an "
                    "explicit seed so runs replay")
                continue
            for arg in list(call.args) + [kw.value for kw in call.keywords]:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Call) and \
                            ctx.resolve(sub.func) in _TIME_SOURCES:
                        yield RawFinding(
                            call.lineno, call.col_offset,
                            f"`{name}` seeded from the clock — a replayed "
                            "run gets a different stream; use an explicit "
                            "seed")

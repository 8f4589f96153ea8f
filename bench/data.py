"""Seeded synthetic regression data, made on the device in one jitted call.

A copy of the repository's `data.synthetic.make_regression`, kept with the
benchmark so that the yardstick does not move when the program's own
generator changes. Same construction: a Gaussian design whose columns are
AR(1)-mixed along the feature axis (correlation `rho`, the "correlated
genes" setting), a `k_true`-sparse ground truth with N(0, 4) entries,
Gaussian noise, then standardized columns and a centered response.

It draws from `jax.random` instead of NumPy, so the values differ from the
program's generator for the same seed; only the distribution is shared.
Draws are made in float32 and cast to the configuration's dtype.

A problem comes from its data seed alone. A run solves a few fixed
problems, the traffic's `problems`, in every run (in an order drawn from
`--seed`), and one more from `--seed` itself after its window. On the TPU
any change to the arrays changes the solver's work: another problem moved
a GLI-85 path's cost by a sixth, and even scaling the response by a power
of two, which leaves every point's answer exact, moved it by up to half
(TPU v5e runs, PERF.md). So the timed work stays the same from seed to
seed, while the check sees a new problem in every run.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (64-bit seeds included)."""
    hi, lo = divmod(int(seed) % (1 << 62), 1 << 31)
    return jax.random.fold_in(jax.random.key(lo), hi)


@partial(jax.jit, static_argnames=("n", "p", "k_true", "rho", "noise",
                                   "dtype"))
def _generate(key, *, n, p, k_true, rho, noise, dtype):
    kz, kidx, kb, ke = jax.random.split(key, 4)
    z = jax.random.normal(kz, (n, p), jnp.float32).astype(dtype)
    if rho > 0:
        a = (1.0 - rho * rho) ** 0.5

        def step(prev, zj):
            xj = rho * prev + a * zj
            return xj, xj

        _, rest = jax.lax.scan(step, z[:, 0], z.T[1:])
        x = jnp.concatenate([z[:, :1], rest.T], axis=1)
    else:
        x = z
    k = min(k_true, p)
    idx = jax.random.choice(kidx, p, (k,), replace=False)
    vals = 2.0 * jax.random.normal(kb, (k,), jnp.float32).astype(dtype)
    beta = jnp.zeros((p,), dtype).at[idx].set(vals)
    e = jax.random.normal(ke, (n,), jnp.float32).astype(dtype)
    y = jnp.dot(x, beta, precision=jax.lax.Precision.HIGHEST) + noise * e
    mu = jnp.mean(x, axis=0)
    sd = jnp.sqrt(jnp.mean((x - mu) ** 2, axis=0))
    x = (x - mu) / (sd + 1e-12)
    return x, y - jnp.mean(y)


def make_regression(n: int, p: int, *, data_seed: int, k_true: int = 10,
                    rho: float = 0.3, noise: float = 0.1, dtype=jnp.float64):
    """(X, y) on the default device: X (n, p) standardized, y (n,) centered."""
    return _generate(key_from_seed(data_seed), n=n, p=p, k_true=k_true,
                     rho=rho, noise=noise, dtype=jnp.dtype(dtype))

"""The kernels package's PUBLIC surface (ISSUE 4 satellite): `repro.kernels`
re-exports the ops/ref entry points, and the Pallas kernels agree with the
pure-jnp oracles when forced through interpret mode — the explicit
ref-vs-pallas parity contract for `hinge_hessian_matvec` and `shifted_gram`
(test_kernels.py sweeps shapes/dtypes via the module paths; this file pins
the package-level API and the `backend=` enum's interpret-mode and "ref"
escape hatches)."""
import jax
import jax.numpy as jnp
import numpy as np

import repro.kernels as kernels
from repro.data.synthetic import make_regression


def _problem(n, p, seed=0):
    X, y, _ = make_regression(n, p, k_true=min(5, p), seed=seed,
                              dtype=jnp.float32)
    return X.astype(jnp.float32), y.astype(jnp.float32)


def test_public_surface_exports():
    for name in kernels.__all__:
        assert hasattr(kernels, name), f"missing export {name}"
    # the package-level ops ARE the ops-module entry points
    assert kernels.shifted_gram is kernels.ops.shifted_gram
    assert kernels.hinge_hessian_matvec is kernels.ops.hinge_hessian_matvec
    assert kernels.hinge_stats is kernels.ops.hinge_stats


def test_shifted_gram_pallas_interpret_matches_ref():
    X, y = _problem(72, 50, seed=1)
    t = 1.3
    K_pallas = kernels.shifted_gram(X, y, t, bm=32, bn=32, bk=32,
                                    backend="tpu_interpret")
    K_ref = kernels.ref.flatten_gram(kernels.ref.gram_blocks_ref(X, y, t))
    K_escape = kernels.shifted_gram(X, y, t, backend="ref")
    assert K_pallas.shape == (100, 100)
    scale = float(jnp.abs(K_ref).max())
    np.testing.assert_allclose(np.asarray(K_pallas), np.asarray(K_ref),
                               atol=3e-6 * scale)
    # escape hatch runs the same jnp oracle under jit: only fusion-level
    # f32 reassociation apart from K_ref
    np.testing.assert_allclose(np.asarray(K_escape), np.asarray(K_ref),
                               atol=1e-6 * scale)


def test_hinge_hessian_matvec_pallas_interpret_matches_ref():
    X, y = _problem(60, 44, seed=2)
    t, C = 0.9, 2.5
    v = jax.random.normal(jax.random.PRNGKey(3), (60,), jnp.float32)
    at = (jax.random.uniform(jax.random.PRNGKey(4), (44,)) > 0.5).astype(
        jnp.float32)
    ab = 1.0 - at
    hv_pallas = kernels.hinge_hessian_matvec(X, y, t, C, at, ab, v,
                                             bp=32, bn=32, bk=32,
                                             backend="tpu_interpret")
    hv_ref = kernels.ref.hessian_matvec_ref(X, y, t, C, at, ab, v)
    hv_escape = kernels.hinge_hessian_matvec(X, y, t, C, at, ab, v,
                                             backend="ref")
    scale = max(1.0, float(jnp.abs(hv_ref).max()))
    np.testing.assert_allclose(np.asarray(hv_pallas), np.asarray(hv_ref),
                               atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(hv_escape), np.asarray(hv_ref),
                               atol=2e-6 * scale)

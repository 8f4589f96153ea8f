"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracle
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.data.synthetic import make_regression
from repro.kernels import ref
from repro.kernels.ops import hinge_hessian_matvec, shifted_gram


def _problem(n, p, dtype, seed=0):
    X, y, _ = make_regression(n, p, k_true=min(5, p), seed=seed, dtype=jnp.float32)
    return X.astype(dtype), y.astype(dtype)


GRAM_SHAPES = [(64, 64), (128, 96), (96, 130), (33, 57), (130, 150), (256, 64)]


@pytest.mark.parametrize("n,p", GRAM_SHAPES)
@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 3e-6), (jnp.bfloat16, 2e-2)])
def test_gram_kernel_sweep(n, p, dtype, rtol):
    X, y = _problem(n, p, dtype)
    t = 0.9
    K = shifted_gram(X, y, t, bm=32, bn=32, bk=32)
    K_ref = ref.flatten_gram(ref.gram_blocks_ref(X.astype(jnp.float32), y.astype(jnp.float32), t))
    scale = float(jnp.abs(K_ref).max())
    np.testing.assert_allclose(np.asarray(K, np.float32), np.asarray(K_ref), atol=rtol * scale)


@pytest.mark.parametrize("blocks", [(8, 8, 8), (16, 32, 8), (64, 64, 64)])
def test_gram_kernel_block_shapes(blocks):
    bm, bn, bk = blocks
    X, y = _problem(96, 64, jnp.float32)
    K = shifted_gram(X, y, 1.7, bm=bm, bn=bn, bk=bk)
    K_ref = ref.flatten_gram(ref.gram_blocks_ref(X, y, 1.7))
    np.testing.assert_allclose(np.asarray(K), np.asarray(K_ref),
                               atol=3e-6 * float(jnp.abs(K_ref).max()))


def test_gram_block_layout_output():
    X, y = _problem(64, 48, jnp.float32)
    Kb = shifted_gram(X, y, 2.0, bm=16, bn=16, bk=16, flatten=False)
    assert Kb.shape == (2, 2, 48, 48)
    np.testing.assert_allclose(np.asarray(ref.flatten_gram(Kb)),
                               np.asarray(shifted_gram(X, y, 2.0, bm=16, bn=16, bk=16)),
                               atol=1e-5)


@settings(max_examples=12, deadline=None)
@given(st.integers(10, 140), st.integers(9, 140), st.floats(0.3, 4.0), st.integers(0, 99))
def test_gram_kernel_property(n, p, t, seed):
    X, y = _problem(n, p, jnp.float32, seed)
    K = shifted_gram(X, y, t, bm=32, bn=32, bk=32)
    K_ref = ref.flatten_gram(ref.gram_blocks_ref(X, y, t))
    np.testing.assert_allclose(np.asarray(K), np.asarray(K_ref),
                               atol=1e-5 * max(1.0, float(jnp.abs(K_ref).max())))


HINGE_SHAPES = [(64, 64), (130, 150), (57, 33), (200, 40), (48, 256)]


@pytest.mark.parametrize("n,p", HINGE_SHAPES)
@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_hinge_matvec_sweep(n, p, dtype, rtol):
    X, y = _problem(n, p, dtype)
    key = jax.random.PRNGKey(0)
    v = jax.random.normal(key, (n,), jnp.float32)
    at = (jax.random.uniform(jax.random.PRNGKey(1), (p,)) > 0.4).astype(jnp.float32)
    ab = (jax.random.uniform(jax.random.PRNGKey(2), (p,)) > 0.6).astype(jnp.float32)
    hv = hinge_hessian_matvec(X, y, 1.1, 2.5, at, ab, v, bp=32, bn=32, bk=32)
    Xf = X.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    hv_ref = ref.hessian_matvec_ref(Xf, yf, 1.1, 2.5, at, ab, v)
    scale = max(1.0, float(jnp.abs(hv_ref).max()))
    np.testing.assert_allclose(np.asarray(hv), np.asarray(hv_ref), atol=rtol * scale)


@settings(max_examples=12, deadline=None)
@given(st.integers(9, 150), st.integers(8, 150), st.integers(0, 99))
def test_hinge_matvec_property(n, p, seed):
    X, y = _problem(n, p, jnp.float32, seed)
    key = jax.random.PRNGKey(seed)
    v = jax.random.normal(key, (n,), jnp.float32)
    at = (jax.random.uniform(jax.random.PRNGKey(seed + 1), (p,)) > 0.5).astype(jnp.float32)
    ab = 1.0 - at  # complementary masks (the realistic SV pattern)
    hv = hinge_hessian_matvec(X, y, 0.8, 4.0, at, ab, v, bp=32, bn=32, bk=32)
    hv_ref = ref.hessian_matvec_ref(X, y, 0.8, 4.0, at, ab, v)
    np.testing.assert_allclose(np.asarray(hv), np.asarray(hv_ref),
                               atol=1e-5 * max(1.0, float(jnp.abs(hv_ref).max())))


def test_oracle_matches_reduction_module():
    """ref.gram_blocks_ref agrees with core.reduction.gram_reference."""
    from repro.core.reduction import gram_reference
    X, y, _ = make_regression(50, 40, seed=3)
    K1 = ref.flatten_gram(ref.gram_blocks_ref(X, y, 1.5))
    K2 = gram_reference(X, y, 1.5)
    np.testing.assert_allclose(np.asarray(K1), np.asarray(K2), atol=1e-9)


HSTAT_SHAPES = [(64, 64), (130, 150), (57, 33), (200, 40)]


@pytest.mark.parametrize("n,p", HSTAT_SHAPES)
def test_hinge_stats_sweep(n, p):
    from repro.kernels.ops import hinge_stats
    X, y = _problem(n, p, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32) * 0.1
    t, C = 1.3, 2.0
    margin, act, loss, galpha = hinge_stats(X, y, t, w, C, bp=32, bk=32)
    m_ref, a_ref, l_ref, g_ref = ref.hinge_stats_ref(X, y, t, w, C)
    scale = max(1.0, float(jnp.abs(m_ref).max()))
    np.testing.assert_allclose(np.asarray(margin), np.asarray(m_ref), atol=3e-6 * scale)
    np.testing.assert_array_equal(np.asarray(act), np.asarray(a_ref))
    np.testing.assert_allclose(float(loss), float(l_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(galpha), np.asarray(g_ref), atol=3e-6 * scale)


@settings(max_examples=10, deadline=None)
@given(st.integers(9, 120), st.integers(8, 120), st.integers(0, 99))
def test_hinge_stats_property(n, p, seed):
    from repro.kernels.ops import hinge_stats
    X, y = _problem(n, p, jnp.float32, seed)
    w = jax.random.normal(jax.random.PRNGKey(seed), (n,), jnp.float32) * 0.2
    margin, act, loss, galpha = hinge_stats(X, y, 0.9, w, 1.5, bp=32, bk=32)
    m_ref, a_ref, l_ref, g_ref = ref.hinge_stats_ref(X, y, 0.9, w, 1.5)
    scale = max(1.0, float(jnp.abs(m_ref).max()))
    np.testing.assert_allclose(np.asarray(margin), np.asarray(m_ref), atol=1e-5 * scale)
    np.testing.assert_allclose(float(loss), float(l_ref), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(galpha), np.asarray(g_ref), atol=1e-5 * scale)


# -- no silent fallback on the TPU path -----------------------------------

def test_resolve_tiles_raises_when_no_tpu_tile_compiles(tmp_path,
                                                        monkeypatch):
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_autotune_cache()
    tried = []

    def rejected(op, body, tiles, nb, pb, dtype):
        tried.append(tiles)
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    with pytest.raises(RuntimeError, match="Mosaic failed") as err:
        autotune.resolve_tiles("shifted_gram", "tpu", 4096, 256,
                               measure=rejected)
    assert "no shifted_gram tile candidate" in str(err.value)
    assert len(tried) == len(set(tried)) == len(
        autotune.GRAM_CANDIDATES["tpu"])
    autotune.clear_autotune_cache()


def test_registry_tpu_hinge_passes_serve_the_tpu_body():
    from repro.kernels import hinge, registry
    impl, body, interp = registry.lookup("hinge_xtv", "tpu")
    assert impl is hinge.hinge_xtv_raw and body == "tpu" and not interp
    impl, body, interp = registry.lookup("hinge_xd", "tpu_interpret")
    assert impl is hinge.hinge_xd_raw and body == "tpu" and interp


def test_registry_missing_body_raises_outside_the_matrix(monkeypatch):
    from repro.kernels import registry
    monkeypatch.setitem(registry._REGISTRY, ("probe_op", "ref"),
                        lambda *a: None)
    with pytest.raises(KeyError, match="probe_op"):
        registry.lookup("probe_op", "tpu")
    assert registry.lookup("probe_op", "ref")[1] == "ref"


def test_gram_kernel_rate_lets_a_compile_error_through(monkeypatch):
    from repro.core import routing
    from repro.kernels import ops, registry

    def broken(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(registry, "resolve_kernel_backend", lambda *a: "tpu")
    monkeypatch.setattr(ops, "shifted_gram", broken)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        routing._gram_kernel_rate(1e9)
    monkeypatch.setattr(registry, "resolve_kernel_backend",
                        lambda *a: "tpu_interpret")
    assert routing._gram_kernel_rate(1e9) == ("tpu_interpret", 1e9)

"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracle (the
"tpu_interpret" backend executes the TPU kernel body on CPU), the
per-backend registry, the tile autotuner, the routing calibration cache,
and the bf16 + iterative-refinement precision path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.data.synthetic import make_regression
from repro.kernels import autotune, ops, ref, registry
from repro.kernels.ops import hinge_hessian_matvec, shifted_gram


def _problem(n, p, dtype, seed=0):
    X, y, _ = make_regression(n, p, k_true=min(5, p), seed=seed, dtype=jnp.float32)
    return X.astype(dtype), y.astype(dtype)


GRAM_SHAPES = [(64, 64), (128, 96), (96, 130), (33, 57), (130, 150), (256, 64),
               (384, 32)]


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


HSTAT_SHAPES = [(64, 64), (130, 150), (57, 33), (200, 40), (384, 32)]


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
    from repro.kernels import hinge
    impl, body, interp = registry.lookup("hinge_xtv", "tpu")
    assert impl is hinge.hinge_xtv_raw and body == "tpu" and not interp
    impl, body, interp = registry.lookup("hinge_xd", "tpu_interpret")
    assert impl is hinge.hinge_xd_raw and body == "tpu" and interp


def test_registry_missing_body_raises_outside_the_matrix(monkeypatch):
    monkeypatch.setitem(registry._REGISTRY, ("probe_op", "ref"),
                        lambda *a: None)
    with pytest.raises(KeyError, match="probe_op"):
        registry.lookup("probe_op", "tpu")
    assert registry.lookup("probe_op", "ref")[1] == "ref"


def test_gram_kernel_rate_lets_a_compile_error_through(monkeypatch):
    from repro.core import routing

    def broken(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(registry, "resolve_kernel_backend", lambda *a: "tpu")
    monkeypatch.setattr(ops, "shifted_gram", broken)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        routing._gram_kernel_rate(1e9)
    monkeypatch.setattr(registry, "resolve_kernel_backend",
                        lambda *a: "tpu_interpret")
    assert routing._gram_kernel_rate(1e9) == ("tpu_interpret", 1e9)


# -- registry ---------------------------------------------------------------

def test_registry_tables():
    assert set(registry.registered_ops()) >= {
        "shifted_gram", "hinge_stats", "hinge_xtv", "hinge_xd"}
    for op in ("shifted_gram", "hinge_stats", "hinge_xtv", "hinge_xd"):
        assert registry.kernel_backends(op) == ("tpu", "ref"), op


def test_resolve_kernel_backend_cpu_default(monkeypatch):
    X = jnp.ones((8, 4))
    assert registry.resolve_kernel_backend(None, X) == "tpu_interpret"
    assert registry.resolve_kernel_backend("auto", X) == "tpu_interpret"
    # explicit resolved values pass through untouched
    for be in registry.RESOLVED_BACKENDS:
        assert registry.resolve_kernel_backend(be, X) == be
    # a platform with no kernel body (a GPU, say) runs the oracle
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert registry.resolve_kernel_backend(None, np.ones(4)) == "ref"


def test_split_backend():
    assert registry.split_backend("tpu_interpret") == ("tpu", True)
    assert registry.split_backend("tpu") == ("tpu", False)
    assert registry.split_backend("ref") == ("ref", False)


# -- low-precision storage --------------------------------------------------

@pytest.mark.parametrize("precision,tol", [("f32", 3e-6), ("bf16", 3e-2)])
def test_gram_low_precision_storage(precision, tol):
    # bf16 = reduced-precision storage with f32 accumulation
    X, y = _problem(96, 64, jnp.float32)
    K = ops.shifted_gram(X, y, 1.1, backend="tpu_interpret",
                         precision=precision)
    K_ref = ref.flatten_gram(ref.gram_blocks_ref(X, y, 1.1))
    np.testing.assert_allclose(
        np.asarray(K), np.asarray(K_ref),
        atol=tol * max(1.0, float(jnp.abs(K_ref).max())))


# -- bf16 + iterative refinement --------------------------------------------

def _check_bf16_refined(n, p, seed):
    """bf16-storage dual solve + one full-precision refinement re-solve
    lands within 1e-10 of the full-precision solve."""
    from repro.core.sven import SvenConfig, sven
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.standard_normal((n, p)) / np.sqrt(n))
    y = jnp.asarray(rng.standard_normal((n,)))
    t = 1.0 + 0.01 * seed
    beta_ref = sven(X, y, t, 0.5,
                    SvenConfig(mode="dual", backend="xla", tol=1e-12)).beta
    beta = sven(X, y, t, 0.5,
                SvenConfig(mode="dual", backend="tpu_interpret",
                           precision="bf16", tol=1e-12)).beta
    np.testing.assert_allclose(np.asarray(beta), np.asarray(beta_ref),
                               atol=1e-10)


@pytest.mark.parametrize("n,p,seed", [(120, 16, 0), (200, 24, 7),
                                      (256, 24, 0)])
def test_bf16_refined_solve_parity_fixed(n, p, seed):
    _check_bf16_refined(n, p, seed)


@settings(max_examples=6, deadline=None)
@given(st.integers(60, 200), st.integers(8, 24), st.integers(0, 99))
def test_bf16_refined_solve_parity(n, p, seed):
    _check_bf16_refined(n, p, seed)


def test_bf16_unrefined_would_fail_gate():
    """Sanity check the refinement is doing the work: the raw bf16 kernel
    deviates from f32 by far more than 1e-10, so a passing refined solve is
    evidence of refinement, not of bf16 being secretly exact."""
    X, y = _problem(128, 32, jnp.float32)
    K16 = ops.shifted_gram(X, y, 1.0, backend="tpu_interpret",
                           precision="bf16")
    K32 = ops.shifted_gram(X, y, 1.0, backend="tpu_interpret")
    assert float(jnp.max(jnp.abs(K16 - K32))) > 1e-6


# -- autotune ---------------------------------------------------------------

def test_shape_bucket_pow2_and_caps():
    assert autotune.shape_bucket(100, 60) == (128, 64)
    assert autotune.shape_bucket(8, 8) == (8, 8)
    assert autotune.shape_bucket(10**6, 10**5) == (8192, 1024)


def test_resolve_tiles_interpret_gets_static_default():
    tiles, source = autotune.resolve_tiles("shifted_gram", "tpu_interpret",
                                           512, 256)
    assert source == "default"
    assert tiles == {"bm": 128, "bn": 128, "bk": 128}
    tiles_ref, source_ref = autotune.resolve_tiles("hinge_stats", "ref",
                                                   512, 256)
    assert source_ref == "default"


def test_resolve_tiles_clamps_to_tiny_problems():
    tiles, _ = autotune.resolve_tiles("shifted_gram", "tpu_interpret",
                                      20, 10)
    assert tiles["bm"] <= 16 and tiles["bk"] >= 8
    tiles, _ = autotune.resolve_tiles("hinge_stats", "tpu_interpret", 3, 2)
    assert tiles == {"bp": 8, "bk": 8}


def test_resolve_tiles_measure_memory_disk(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_autotune_cache()
    calls = []

    def fake_measure(op, body, tiles, nb, pb, dtype):
        calls.append(tiles)
        return 1.0 if tiles != (128, 128, 256) else 0.1  # rig a winner

    tiles, source = autotune.resolve_tiles(
        "shifted_gram", "tpu", 4096, 512, measure=fake_measure)
    assert source == "measured" and tiles == {"bm": 128, "bn": 128,
                                              "bk": 256}
    assert len(calls) == len(autotune.GRAM_CANDIDATES["tpu"])

    tiles2, source2 = autotune.resolve_tiles(
        "shifted_gram", "tpu", 4096, 512, measure=fake_measure)
    assert source2 == "memory" and tiles2 == tiles
    assert len(calls) == len(autotune.GRAM_CANDIDATES["tpu"])  # no re-sweep

    autotune.clear_autotune_cache()
    tiles3, source3 = autotune.resolve_tiles(
        "shifted_gram", "tpu", 4096, 512, measure=fake_measure)
    assert source3 == "disk" and tiles3 == tiles
    autotune.clear_autotune_cache()


def test_resolve_tiles_all_candidates_failing_degrades(tmp_path, monkeypatch):
    # a body no tile compiles for is broken: the sweep raises instead of
    # handing back the static default
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_autotune_cache()

    def exploding(op, body, tiles, nb, pb, dtype):
        raise RuntimeError("compiler rejected tile")

    with pytest.raises(RuntimeError, match="no hinge_stats tile candidate"):
        autotune.resolve_tiles("hinge_stats", "tpu", 300, 80,
                               measure=exploding)
    assert autotune.resolve_tiles("hinge_stats", "tpu_interpret", 300,
                                  80)[1] == "default"
    autotune.clear_autotune_cache()


# -- calibration disk cache -------------------------------------------------

def test_calibration_disk_roundtrip(tmp_path, monkeypatch):
    from repro.core import routing
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    routing.clear_calibration()
    cal = routing.calibrate(None, force=True)
    assert cal.kernel_backend in registry.RESOLVED_BACKENDS
    assert cal.gram_flops_per_s >= 0.0

    from repro import utils
    disk = utils.disk_cache_load("calibration")
    key = routing._disk_key(jax.default_backend(), 1)
    assert key in disk and set(disk[key]) == set(routing.Calibration._fields)

    # tamper the stored entry; a fresh in-process calibrate must read it
    # back from disk rather than re-measuring
    disk[key]["fanout_speedup"] = 123.5
    utils.disk_cache_update("calibration", {key: disk[key]})
    routing.clear_calibration()
    cal2 = routing.calibrate(None)
    assert cal2.fanout_speedup == 123.5
    routing.clear_calibration()


def test_solve_costs_price_gram_rate():
    from repro.core import routing
    cal = routing.Calibration(
        devices=8, backend="cpu", flops_per_s=1e9, psum_latency_s=1e-5,
        psum_per_byte_s=1e-10, fanout_speedup=4.0, replicated_slowdown=1.1,
        kernel_backend="tpu", gram_flops_per_s=4e9)
    costs = routing._solve_costs(10_000, 100, "dual", cal)
    # the data pass is priced at the measured gram kernel rate, not the
    # generic GEMM rate: a 4x slower kernel -> costlier single-device solve
    cal_slow = cal._replace(gram_flops_per_s=1e9)
    costs_slow = routing._solve_costs(10_000, 100, "dual", cal_slow)
    assert costs["single"] < costs_slow["single"]

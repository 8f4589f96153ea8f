"""GPU (Triton) kernel bodies, the per-backend registry, the tile autotuner,
and the bf16 + iterative-refinement precision path.

Everything here runs on CPU: the GPU bodies execute in Pallas interpret mode
(``backend="gpu_interpret"``), which is the CPU-side parity gate the ISSUE
specifies — the compiled path reuses the identical kernel body, so interpret
parity plus the compile-only plumbing covers the contract a CPU runner can
check. The optional real-GPU job (``-m gpu``) re-runs the compiled variants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.data.synthetic import make_regression
from repro.kernels import autotune, ops, ref, registry

pytestmark = []  # module runs everywhere; see test_gpu_compiled for the marker


def _problem(n, p, dtype=jnp.float32, seed=0):
    X, y, _ = make_regression(n, p, k_true=min(5, p), seed=seed,
                              dtype=jnp.float32)
    return X.astype(dtype), y.astype(dtype)


# -- registry ---------------------------------------------------------------

def test_registry_tables():
    assert set(registry.registered_ops()) >= {
        "shifted_gram", "hinge_stats", "hinge_xtv", "hinge_xd"}
    assert set(registry.kernel_backends("shifted_gram")) == {
        "tpu", "gpu", "ref"}
    assert set(registry.kernel_backends("hinge_stats")) == {
        "tpu", "gpu", "ref"}
    # the two-pass hinge matvec has no GPU body — GEMV-shaped and
    # memory-bound, cuBLAS via the ref oracle is the honest choice
    assert "gpu" not in registry.kernel_backends("hinge_xtv")


def test_registry_lookup_falls_back_to_ref():
    impl, body, interp = registry.lookup("hinge_xtv", "gpu")
    assert body == "ref" and not interp
    impl_i, body_i, interp_i = registry.lookup("hinge_xtv", "gpu_interpret")
    assert body_i == "ref" and not interp_i
    impl_g, body_g, interp_g = registry.lookup("shifted_gram", "gpu_interpret")
    assert body_g == "gpu" and interp_g


def test_resolve_kernel_backend_cpu_default():
    X = jnp.ones((8, 4))
    assert registry.resolve_kernel_backend(None, X) == "tpu_interpret"
    assert registry.resolve_kernel_backend("auto", X) == "tpu_interpret"
    # explicit resolved values pass through untouched
    for be in registry.RESOLVED_BACKENDS:
        assert registry.resolve_kernel_backend(be, X) == be


def test_split_backend():
    assert registry.split_backend("gpu_interpret") == ("gpu", True)
    assert registry.split_backend("tpu") == ("tpu", False)
    assert registry.split_backend("ref") == ("ref", False)


# -- GPU gram body (interpret-mode parity) ----------------------------------

GPU_GRAM_SHAPES = [(64, 64), (96, 48), (33, 57), (130, 96), (256, 64)]


@pytest.mark.parametrize("n,p", GPU_GRAM_SHAPES)
def test_gpu_gram_parity(n, p):
    X, y = _problem(n, p)
    t = 1.3
    K = ops.shifted_gram(X, y, t, backend="gpu_interpret")
    K_ref = ref.flatten_gram(ref.gram_blocks_ref(X, y, t))
    np.testing.assert_allclose(
        np.asarray(K), np.asarray(K_ref),
        atol=3e-6 * max(1.0, float(jnp.abs(K_ref).max())))


def test_gpu_gram_f64_operands_are_cast():
    # preferred_element_type=f32 must not silently widen/narrow: the body
    # casts f64 operands to its f32 compute dtype, so parity holds at f32.
    X, y = _problem(64, 48, jnp.float64)
    K = ops.shifted_gram(X, y, 0.9, backend="gpu_interpret")
    K_ref = ref.flatten_gram(ref.gram_blocks_ref(X, y, 0.9))
    np.testing.assert_allclose(
        np.asarray(K, np.float64), np.asarray(K_ref),
        atol=3e-6 * max(1.0, float(jnp.abs(K_ref).max())))


@pytest.mark.parametrize("backend", ["tpu_interpret", "gpu_interpret"])
@pytest.mark.parametrize("precision,tol", [("bf16", 3e-2), ("tf32", 3e-6)])
def test_gram_low_precision_storage(backend, precision, tol):
    # bf16 = reduced-precision storage with f32 accumulation; tf32 only
    # relaxes matmul precision on hardware that has the mode (on CPU and in
    # interpret mode it matches f32).
    X, y = _problem(96, 64)
    K = ops.shifted_gram(X, y, 1.1, backend=backend, precision=precision)
    K_ref = ref.flatten_gram(ref.gram_blocks_ref(X, y, 1.1))
    np.testing.assert_allclose(
        np.asarray(K), np.asarray(K_ref),
        atol=tol * max(1.0, float(jnp.abs(K_ref).max())))


@settings(max_examples=10, deadline=None)
@given(st.integers(10, 140), st.integers(9, 140), st.floats(0.3, 4.0),
       st.integers(0, 99))
def test_gpu_gram_property(n, p, t, seed):
    X, y = _problem(n, p, seed=seed)
    K = ops.shifted_gram(X, y, t, backend="gpu_interpret")
    K_ref = ref.flatten_gram(ref.gram_blocks_ref(X, y, t))
    np.testing.assert_allclose(
        np.asarray(K), np.asarray(K_ref),
        atol=1e-5 * max(1.0, float(jnp.abs(K_ref).max())))


# -- GPU hinge-stats body ---------------------------------------------------

@pytest.mark.parametrize("n,p", [(64, 64), (130, 96), (57, 33), (200, 40)])
def test_gpu_hinge_stats_parity(n, p):
    X, y = _problem(n, p)
    w = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32) * 0.1
    t, C = 1.3, 2.0
    margin, act, loss, galpha = ops.hinge_stats(
        X, y, t, w, C, backend="gpu_interpret")
    m_ref, a_ref, l_ref, g_ref = ref.hinge_stats_ref(X, y, t, w, C)
    scale = max(1.0, float(jnp.abs(m_ref).max()))
    np.testing.assert_allclose(np.asarray(margin), np.asarray(m_ref),
                               atol=3e-6 * scale)
    np.testing.assert_array_equal(np.asarray(act), np.asarray(a_ref))
    np.testing.assert_allclose(float(loss), float(l_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(galpha), np.asarray(g_ref),
                               atol=3e-6 * scale)


def test_gpu_vs_tpu_bodies_agree():
    X, y = _problem(128, 96)
    K_gpu = ops.shifted_gram(X, y, 1.7, backend="gpu_interpret")
    K_tpu = ops.shifted_gram(X, y, 1.7, backend="tpu_interpret")
    np.testing.assert_allclose(
        np.asarray(K_gpu), np.asarray(K_tpu),
        atol=3e-6 * max(1.0, float(jnp.abs(K_tpu).max())))


# -- bf16 + iterative refinement --------------------------------------------

def _check_bf16_refined(n, p, seed):
    """bf16-storage dual solve + one full-precision refinement re-solve
    lands within 1e-10 of the full-precision solve (the ISSUE gate)."""
    from repro.core.sven import SvenConfig, sven
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.standard_normal((n, p)) / np.sqrt(n))
    y = jnp.asarray(rng.standard_normal((n,)))
    t = 1.0 + 0.01 * seed
    beta_ref = sven(X, y, t, 0.5,
                    SvenConfig(mode="dual", backend="xla", tol=1e-12)).beta
    for backend in ("tpu_interpret", "gpu_interpret"):
        beta = sven(X, y, t, 0.5,
                    SvenConfig(mode="dual", backend=backend,
                               precision="bf16", tol=1e-12)).beta
        np.testing.assert_allclose(np.asarray(beta), np.asarray(beta_ref),
                                   atol=1e-10)


@pytest.mark.parametrize("n,p,seed", [(120, 16, 0), (200, 24, 7)])
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
    X, y = _problem(128, 32)
    K16 = ops.shifted_gram(X, y, 1.0, backend="tpu_interpret",
                           precision="bf16")
    K32 = ops.shifted_gram(X, y, 1.0, backend="tpu_interpret")
    assert float(jnp.max(jnp.abs(K16 - K32))) > 1e-6


# -- deprecated two-flag shim -----------------------------------------------

def test_use_pallas_interpret_shim_warns_and_matches():
    X, y = _problem(64, 48)
    with pytest.warns(DeprecationWarning):
        K_old = ops.shifted_gram(X, y, 1.5, interpret=True)
    K_new = ops.shifted_gram(X, y, 1.5, backend="tpu_interpret")
    np.testing.assert_array_equal(np.asarray(K_old), np.asarray(K_new))
    with pytest.warns(DeprecationWarning):
        K_ref = ops.shifted_gram(X, y, 1.5, use_pallas=False)
    np.testing.assert_array_equal(
        np.asarray(K_ref), np.asarray(ops.shifted_gram(X, y, 1.5,
                                                       backend="ref")))


def test_sven_config_interpret_folds_into_enum():
    from repro.core.sven import SvenConfig, resolve_backend
    X, y = _problem(32, 16)
    a = resolve_backend(SvenConfig(backend="auto", interpret=True), X, y)
    b = resolve_backend(SvenConfig(backend="tpu_interpret"), X, y)
    assert a == b and a.interpret is None  # same jit key — no retrace


# -- autotune ---------------------------------------------------------------

def test_shape_bucket_pow2_and_caps():
    assert autotune.shape_bucket(100, 60) == (128, 64)
    assert autotune.shape_bucket(8, 8) == (8, 8)
    assert autotune.shape_bucket(10**6, 10**5) == (8192, 1024)


def test_resolve_tiles_interpret_gets_static_default():
    tiles, source = autotune.resolve_tiles("shifted_gram", "gpu_interpret",
                                           512, 256)
    assert source == "default"
    assert tiles == {"bm": 64, "bn": 64, "bk": 32}
    tiles_ref, source_ref = autotune.resolve_tiles("hinge_stats", "ref",
                                                   512, 256)
    assert source_ref == "default"


def test_resolve_tiles_clamps_to_tiny_problems():
    tiles, _ = autotune.resolve_tiles("shifted_gram", "gpu_interpret", 20, 10)
    assert tiles["bm"] >= 16 and tiles["bk"] >= 16  # Triton tl.dot floor
    tiles_t, _ = autotune.resolve_tiles("shifted_gram", "tpu_interpret",
                                        20, 10)
    assert tiles_t["bm"] <= 16 and tiles_t["bk"] >= 8


def test_resolve_tiles_measure_memory_disk(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_autotune_cache()
    calls = []

    def fake_measure(op, body, tiles, nb, pb, dtype):
        calls.append(tiles)
        return 1.0 if tiles != (32, 32, 32) else 0.1  # rig a winner

    tiles, source = autotune.resolve_tiles(
        "shifted_gram", "gpu", 200, 100, measure=fake_measure)
    assert source == "measured" and tiles == {"bm": 32, "bn": 32, "bk": 32}
    assert len(calls) == len(autotune.GRAM_CANDIDATES["gpu"])

    tiles2, source2 = autotune.resolve_tiles(
        "shifted_gram", "gpu", 200, 100, measure=fake_measure)
    assert source2 == "memory" and tiles2 == tiles
    assert len(calls) == len(autotune.GRAM_CANDIDATES["gpu"])  # no re-sweep

    autotune.clear_autotune_cache()
    tiles3, source3 = autotune.resolve_tiles(
        "shifted_gram", "gpu", 200, 100, measure=fake_measure)
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
        autotune.resolve_tiles("hinge_stats", "gpu", 300, 80,
                               measure=exploding)
    assert autotune.resolve_tiles("hinge_stats", "gpu_interpret", 300,
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
        kernel_backend="gpu", gram_flops_per_s=4e9)
    costs = routing._solve_costs(10_000, 100, "dual", cal)
    # the data pass is priced at the measured gram kernel rate, not the
    # generic GEMM rate: a 4x slower kernel -> costlier single-device solve
    cal_slow = cal._replace(gram_flops_per_s=1e9)
    costs_slow = routing._solve_costs(10_000, 100, "dual", cal_slow)
    assert costs["single"] < costs_slow["single"]


# -- optional real-GPU job --------------------------------------------------

@pytest.mark.gpu
def test_gpu_compiled_parity():
    """Compiled Triton parity — runs only under the optional GPU CI job
    (`-m gpu`); auto-skips anywhere without a CUDA/ROCm device."""
    if jax.default_backend() not in ("gpu", "cuda", "rocm"):
        pytest.skip("no GPU present")
    X, y = _problem(512, 128)
    K = ops.shifted_gram(X, y, 1.3, backend="gpu")
    K_ref = ref.flatten_gram(ref.gram_blocks_ref(X, y, 1.3))
    np.testing.assert_allclose(
        np.asarray(K), np.asarray(K_ref),
        atol=1e-4 * max(1.0, float(jnp.abs(K_ref).max())))

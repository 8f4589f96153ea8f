"""The Pallas kernels of the main path compile for a TPU v5e at the widths
users run, under the x64 setting every entry point turns on (conftest.py).

The chip is described, not attached: `get_topology_desc` hands the TPU
compiler a v5e:2x2 topology and each kernel is lowered and compiled for one
of its chips, so what the chip's compiler would refuse fails here without
one. Widths: YearPredictionMSD's training shape (463,715 x 90) for the
shifted Gram of the dual path; GLI-85's (85 x 22,283) for the two hinge
mat-vec passes of the primal path and the fused stats kernel.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.hinge import hinge_xd_raw, hinge_xtv_raw
from repro.kernels.hinge_stats import hinge_stats_raw

GLI85 = (85, 22_283)
MSD = (463_715, 90)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_x64_is_on():
    # the i64 index-map failure only shows under the entry points' setting
    assert jax.config.jax_enable_x64


def test_shifted_gram_compiles_at_msd_width(one_chip):
    n, p = MSD
    _compile(lambda X, y, t: ops.shifted_gram(X, y, t, backend="tpu",
                                              bm=128, bn=128, bk=256),
             (n, p), (n,), (), sharding=one_chip)


def test_hinge_xtv_compiles_at_gli85_width(one_chip):
    n, p = 128, 22_400                   # GLI-85 padded to (bk, bp) tiles
    _compile(lambda X, v, y, a, b, i: hinge_xtv_raw(X, v, y, a, b, i,
                                                    bp=128, bk=64),
             (n, p), (n, 1), (n, 1), (p, 1), (p, 1), (1, 1),
             sharding=one_chip)


def test_hinge_xd_compiles_at_gli85_width(one_chip):
    n, p = 128, 22_400
    _compile(lambda X, d, y, v, s: hinge_xd_raw(X, d, y, v, s,
                                                bn=64, bk=128),
             (n, p), (p, 1), (n, 1), (n, 1), (3, 1), sharding=one_chip)


def test_hinge_hessian_matvec_compiles_at_gli85_width(one_chip):
    n, p = GLI85
    _compile(lambda X, y, t, C, a, b, v: ops.hinge_hessian_matvec(
                 X, y, t, C, a, b, v, backend="tpu"),
             (n, p), (n,), (), (), (p,), (p,), (n,), sharding=one_chip)


def test_hinge_stats_compiles_at_gli85_width(one_chip):
    n, p = 128, 22_400
    _compile(lambda X, w, y, s: hinge_stats_raw(X, w, y, s, bp=128, bk=64),
             (n, p), (n, 1), (n, 1), (2, 1), sharding=one_chip)

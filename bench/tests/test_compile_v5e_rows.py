"""Compile `enet_path`'s row layout for a described four-chip TPU v5e host
(v5e:2x2), without a chip: `_enet_path_scan` at YearPredictionMSD's
published 463,715 x 90 in float64, padded with one zero row to 463,716,
with X's rows over the four chips, as `enet_path` runs it under
`dist.mesh_context`. Each chip's plan must fit its memory, and XLA's
partitioner must not gather X onto a chip. The one-chip plan of the same
path, the reason for the layout, must not fit, and `core.routing`'s
estimate of it must match the compiler's.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_compile_v5e_rows.py
"""
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
N, P, POINTS = 463_716, 90, 10


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _compile(X_sharding, vec_sharding, rep_sharding):
    import jax
    import jax.numpy as jnp

    from repro.core import api

    dt = jnp.float64
    args = (jax.ShapeDtypeStruct((N, P), dt, sharding=X_sharding),
            jax.ShapeDtypeStruct((N,), dt, sharding=vec_sharding),
            jax.ShapeDtypeStruct((POINTS,), dt, sharding=rep_sharding),
            jax.ShapeDtypeStruct((), dt, sharding=rep_sharding))
    return api._enet_path_scan.lower(*args, api.PathConfig()).compile()


def _planned(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_rows_fit_each_chip_and_x_is_never_gathered(topo):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as Ps

    from bench import harness

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    compiled = _compile(NamedSharding(mesh, Ps("data", None)),
                        NamedSharding(mesh, Ps("data")),
                        NamedSharding(mesh, Ps()))
    planned = _planned(compiled)
    print(f"per chip: {planned} bytes")
    assert planned < harness.peak_table("TPU v5 lite")["hbm_bytes"]
    gathers = [line for line in compiled.as_text().splitlines()
               if re.search(r"\ball-gather(-start)?\(", line)]
    wide = [g for g in gathers if re.search(rf"\[[0-9,]*\b{N}\b", g)]
    assert not wide, wide[:3]
    assert "all-reduce" in compiled.as_text()


def test_one_chip_plan_does_not_fit_and_matches_the_estimate(topo):
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro.core import routing

    one = SingleDeviceSharding(topo.devices[0])
    planned = _planned(_compile(one, one, one))
    estimate = routing.PATH_PLAN_X_BYTES * N * P * 8
    print(f"one chip: {planned} bytes, estimate {estimate}")
    assert planned > harness.peak_table("TPU v5 lite")["hbm_bytes"]
    assert abs(estimate - planned) <= 0.02 * planned

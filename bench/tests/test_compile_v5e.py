"""Compile each cell's programs for a described TPU v5e at the cell's real
widths, without a chip: the data generator and the path's `_enet_path_scan`
(the one executable a job runs). What the chip's compiler would refuse, or
a program that would not fit the chip's memory, fails here.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_compile_v5e.py
"""
import json
import os
from pathlib import Path

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _fits(compiled, hbm_bytes):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    print(f"argument {m.argument_size_in_bytes} output "
          f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes}")
    assert total < hbm_bytes, total


@pytest.mark.parametrize("cell", CELLS)
def test_cell_compiles_for_v5e(cell, one_chip):
    import jax
    import jax.numpy as jnp

    from bench import data, harness
    from repro.core import api

    _, w, config, traffic = harness.load_cell(cell)
    if traffic["job"] != "enet_path":
        pytest.skip(f"no described-chip compile for job {traffic['job']}")
    hbm = harness.peak_table("TPU v5 lite")["hbm_bytes"]
    n, p = config["n"], config["p"]
    dt = jnp.dtype(config["dtype"])
    k = jax.eval_shape(lambda: data.key_from_seed(0))
    key = jax.ShapeDtypeStruct(k.shape, k.dtype, sharding=one_chip)
    gen_args = {k: v for k, v in config["generator"].items()
                if k != "data_seed"}
    gen = jax.jit(lambda k: data._generate(k, n=n, p=p, dtype=dt,
                                           **gen_args))
    _fits(gen.lower(key).compile(), hbm)

    X = jax.ShapeDtypeStruct((n, p), dt, sharding=one_chip)
    y = jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    grid = jax.ShapeDtypeStruct((traffic["n_lambdas"],), dt,
                                sharding=one_chip)
    lam2 = jax.ShapeDtypeStruct((), dt, sharding=one_chip)
    compiled = api._enet_path_scan.lower(X, y, grid, lam2,
                                         api.PathConfig()).compile()
    _fits(compiled, hbm)

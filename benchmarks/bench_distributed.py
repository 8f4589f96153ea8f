"""Distributed SVEN scaling check (§Discussion's 'distributed systems' row):
runs the shard_map gram + primal solve on a simulated 8-device host mesh in
a subprocess (the bench process itself keeps the real single device) and
reports correctness + timing vs the single-device path. On a TPU it refuses
to run: the child would need the chip this process holds."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit
from repro import utils

_CODE = textwrap.dedent("""
    import os, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, numpy as np, jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    from repro.core.distributed import (distributed_gram, distributed_gram_rs,
                                        sven_primal_distributed)
    from repro.core.reduction import gram_blocks
    from repro.data.synthetic import make_regression

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    X, y, _ = make_regression(4096, 256, seed=0)

    f_local = jax.jit(lambda X, y: gram_blocks(X, y, 1.5))
    f_dist = jax.jit(lambda X, y: distributed_gram(mesh, X, y, 1.5, row_shard_out=False))
    f_rs = jax.jit(lambda X, y: distributed_gram_rs(mesh, X, y, 1.5))
    for name, f in [("local", f_local), ("dist_psum", f_dist), ("dist_rs", f_rs)]:
        out = f(X, y).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            out = f(X, y).block_until_ready()
        print(f"GRAM {name} {(time.perf_counter()-t0)/3*1e6:.1f}")
    err = float(jnp.abs(f_dist(X, y) - f_local(X, y)).max())
    print(f"GRAMERR {err:.3e}")
""")


def run():
    utils.refuse_on_tpu("bench_distributed")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _CODE], env=env, cwd=os.getcwd(),
                       capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-1000:])
    times, err = {}, None
    for line in r.stdout.splitlines():
        if line.startswith("GRAM "):
            _, name, us = line.split()
            times[name] = float(us)
        elif line.startswith("GRAMERR"):
            err = line.split()[1]
    for name, us in times.items():
        emit(f"dist_gram_{name}", us / 1e6,
             f"8dev_host_mesh n=4096 p=256 max_err_vs_local={err}")


if __name__ == "__main__":
    run()

"""The Pallas kernels of the main path compile for a TPU v5e at the widths
users run, under the x64 setting every entry point turns on (conftest.py).

The chip is described, not attached: `get_topology_desc` hands the TPU
compiler a v5e:2x2 topology and each kernel is lowered and compiled for one
of its chips, so what the chip's compiler would refuse fails here without
one. Widths: YearPredictionMSD's training shape (463,715 x 90) for the
shifted Gram of the dual path; GLI-85's (85 x 22,283) for the two hinge
mat-vec passes of the primal path and the fused stats kernel. One test
compiles the whole XLA path at GLI-85's shape and reads the compiled HLO:
the primal CG loop there runs on the explicit 85 x 85 Hessian, so no op in
its body is shaped like X.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import api
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


def _computations(hlo: str) -> dict:
    """{name: instruction lines} of an HLO module's text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _reachable(comps: dict, root: str) -> set:
    """`root` and every computation it calls (fusions, loops, branches)."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            for ref in re.findall(r"(?:calls|body|condition|to_apply|"
                                  r"true_computation|false_computation|"
                                  r"branch_computations)=(\{[^}]*\}|%\S+)",
                                  line):
                todo += re.findall(r"%([\w.\-]+)", ref)
    return seen


def test_cg_loop_holds_no_x_shaped_op_at_gli85(one_chip):
    n, p = GLI85
    f64 = lambda s: jax.ShapeDtypeStruct(s, jnp.float64, sharding=one_chip)
    hlo = api._enet_path_scan.lower(f64((n, p)), f64((n,)), f64((3,)),
                                    f64(()), config=api.PathConfig()
                                    ).compile().as_text()
    comps = _computations(hlo)
    bodies = [re.search(r"body=%([\w.\-]+)", line).group(1)
              for lines in comps.values() for line in lines
              if " while(" in line
              and re.search(r'op_name="[^"]*sven\.cg/while"', line)]
    assert bodies, "no CG while loop found by its sven.cg scope"
    x_shaped = re.compile(rf"\[(?:\d+,)*(?:{p},{n}|{n},{p})\]")
    for body in bodies:
        ops_ = [line.strip() for c in _reachable(comps, body)
                for line in comps[c] if x_shaped.search(line)]
        assert not ops_, ops_[:3]

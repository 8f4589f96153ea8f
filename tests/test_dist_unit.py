"""Unit tests of the repro.dist layer itself: context/rule-table semantics,
spec resolution edge cases, ZeRO widening, compression edge cases, and the
sharding resolvers on model pytrees (single-device mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro import dist
from repro.dist.zero import _widen_spec


class _MeshStub:
    """Only mesh.shape is consulted by _widen_spec/resolve_spec divisibility;
    a stub lets us test non-trivial axis sizes on a 1-device host."""

    def __init__(self, **shape):
        self.shape = shape


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def test_constrain_noop_outside_context():
    x = jnp.arange(12.0).reshape(3, 4)
    out = dist.constrain(x, "batch", "embed")
    assert out is x  # identity, not even a copy
    # and inside a context it still computes the same values
    with dist.mesh_context(_mesh11()):
        np.testing.assert_array_equal(
            np.asarray(dist.constrain(x, "batch", "embed")), np.asarray(x))


def test_constrain_rank_mismatch_raises():
    # arity bugs must surface even on the no-context (single-CPU test) path
    with pytest.raises(ValueError, match="rank"):
        dist.constrain(jnp.ones((2, 3)), "batch")
    with dist.mesh_context(_mesh11()):
        with pytest.raises(ValueError, match="rank"):
            dist.constrain(jnp.ones((2, 3)), "batch")


def test_mesh_context_rule_precedence():
    mesh = _mesh11()
    # partial override: passed entries win, untouched defaults survive
    with dist.mesh_context(mesh, rules={"mlp": "data", "my_axis": "model"}):
        _, rules = dist.current_context()
        assert rules["mlp"] == "data"
        assert rules["my_axis"] == "model"
        assert rules["heads"] == dist.DEFAULT_RULES["heads"]
        # nested contexts: innermost wins, outer restored on exit
        with dist.mesh_context(mesh, rules={"mlp": None}):
            assert dist.current_context()[1]["mlp"] is None
        assert dist.current_context()[1]["mlp"] == "data"
    assert dist.current_context() is None


def test_resolve_spec_skips_nondividing_and_reused_axes():
    mesh = _MeshStub(data=2, model=4)
    rules = {"batch": "data", "heads": "model", "kv_heads": "model"}
    # 7 % 4 != 0 -> heads dim falls back to None
    assert dist.resolve_spec(("batch", "heads"), (6, 7), mesh, rules) == P("data", None)
    # "model" already consumed by heads -> kv_heads resolves None
    assert dist.resolve_spec(("heads", "kv_heads"), (8, 8), mesh, rules) == \
        P("model", None)


def test_widen_spec_basic_and_nondivisible():
    mesh = _MeshStub(data=2, model=1)
    # widens the FIRST unsharded divisible dim only
    assert _widen_spec(P(None, None), (63, 8), "data", mesh) == P(None, "data")
    # nothing divides -> untouched
    assert _widen_spec(P(None, None), (63, 9), "data", mesh) == P(None, None)
    # spec already using the axis -> untouched
    assert _widen_spec(P("data", None), (64, 8), "data", mesh) == P("data", None)
    # sharded dims are never re-widened, even when divisible
    assert _widen_spec(P("model", None), (64, 9), "data", mesh) == P("model", None)


def test_topk_frac_one_roundtrips_exactly():
    from repro.dist.compress import (topk_compress, topk_decompress, topk_init)
    g = {"w": jnp.asarray(np.random.default_rng(1).standard_normal((7, 5)),
                          jnp.float32),
         "b": jnp.linspace(-1, 1, 11).astype(jnp.float32)}
    state = topk_init(g)
    vals, idx, state = topk_compress(g, state, frac=1.0)
    out = topk_decompress(vals, idx, g)
    for k in g:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(g[k]))
        assert float(jnp.abs(state[k]).max()) == 0.0  # residual fully drained


def test_topk_residual_drains_after_full_emission():
    from repro.dist.compress import topk_compress, topk_init
    g = {"w": jnp.asarray([3.0, -2.0, 1.0, 0.5], jnp.float32)}
    state = topk_init(g)
    # frac=0.5 emits 2 entries/step; after one partial step the residual holds
    # exactly the un-emitted mass...
    _, _, state = topk_compress(g, state, frac=0.5)
    np.testing.assert_allclose(np.asarray(state["w"]), [0, 0, 1.0, 0.5])
    # ...and a follow-up full emission flushes it to zero
    _, _, state = topk_compress(jax.tree.map(jnp.zeros_like, g), state, frac=1.0)
    assert float(jnp.abs(state["w"]).max()) == 0.0


def test_params_shardings_requires_context():
    from repro.dist.shardings import params_shardings
    with pytest.raises(RuntimeError, match="mesh_context"):
        params_shardings({"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)})


def test_params_shardings_unknown_leaf_falls_back_replicated():
    from repro.dist.shardings import params_shardings
    with dist.mesh_context(_mesh11()):
        tree = params_shardings({"mystery": jax.ShapeDtypeStruct((3, 5), jnp.float32)})
    assert tree["mystery"].spec == P(None, None)


def test_batch_and_cache_shardings_resolve_model_trees():
    """Every leaf of a real smoke model's inputs + decode caches resolves."""
    from repro.configs import get_config, input_specs
    from repro.dist.shardings import batch_shardings, cache_shardings
    from repro.models import model as M
    cfg = get_config("jamba-v0.1-52b", smoke=True)   # attn + ssm + moe mix
    mesh = _mesh11()
    with dist.mesh_context(mesh, rules={**dist.DEFAULT_RULES, **cfg.rules_override}):
        b_sh = batch_shardings(input_specs(cfg, "train_4k"))
        cache = jax.eval_shape(lambda: M.init_cache(None, cfg, 2, 64))
        c_sh = cache_shardings(cache)
    for leaf in jax.tree.leaves(b_sh) + jax.tree.leaves(c_sh):
        assert isinstance(leaf, NamedSharding)
    assert b_sh["tokens"].spec[0] == "data"


def test_zero1_widens_over_data():
    from repro.dist.shardings import params_shardings
    from repro.dist.zero import zero1_shardings
    mesh = _mesh11()
    shapes = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32)}
    with dist.mesh_context(mesh):
        p_sh = params_shardings(shapes)
    m_sh = zero1_shardings(p_sh, shapes)
    assert m_sh["w"].spec == P("data", None)

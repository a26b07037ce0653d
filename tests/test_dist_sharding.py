"""Unit tests for the repro.dist substrate beyond the seed suite:
rule-table → PartitionSpec resolution for all three rule sets, and the
error-feedback compression identity (compress + residual round-trip)."""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.compress import (compress_with_error_feedback,
                                 zero_residual)
from repro.dist.sharding import (CP_SERVE_RULES, MULTI_POD_RULES,
                                 SINGLE_POD_RULES, active_rules,
                                 resolve_spec, shard, use_rules)
from repro.launch.mesh import make_test_mesh

SINGLE_AXES = {"data": 2, "model": 4}
MULTI_AXES = {"pod": 2, "data": 2, "model": 4}


# ------------------------------------------------------------ rule tables

def test_single_pod_rules_selection():
    # activations (B, S, H, Dh): batch→data, heads→model, seq replicated
    assert resolve_spec((8, 64, 8, 32), ("batch", "seq", "heads", None),
                        SINGLE_POD_RULES, SINGLE_AXES) \
        == P("data", None, "model", None)
    # logits (B, chunk, V): vocab→model
    assert resolve_spec((8, 64, 512), ("batch", None, "vocab"),
                        SINGLE_POD_RULES, SINGLE_AXES) \
        == P("data", None, "model")
    # decode cache (B, Smax, Hkv, Dh): sequence-parallel on model
    assert resolve_spec((8, 64, 2, 32), ("batch", "sp_seq", None, None),
                        SINGLE_POD_RULES, SINGLE_AXES) \
        == P("data", "model", None, None)


def test_multi_pod_rules_selection():
    # batch dim spreads over (pod, data); pod axis must exist in the mesh
    assert resolve_spec((8, 64, 8, 32), ("batch", "seq", "heads", None),
                        MULTI_POD_RULES, MULTI_AXES) \
        == P(("pod", "data"), None, "model", None)
    # on a single-pod mesh the pod axis is dropped, not an error
    assert resolve_spec((8, 64, 8, 32), ("batch", "seq", "heads", None),
                        MULTI_POD_RULES, SINGLE_AXES) \
        == P("data", None, "model", None)


def test_cp_serve_rules_selection():
    # context parallelism: sequence→model, heads replicated
    assert resolve_spec((8, 64, 8, 32), ("batch", "seq", "heads", None),
                        CP_SERVE_RULES, SINGLE_AXES) \
        == P("data", "model", None, None)
    # head-sharded KV is disabled under CP (heads replicated, mp=1)
    assert resolve_spec((8, 64, 2, 32), ("batch", None,
                                         "kv_heads_sharded", None),
                        CP_SERVE_RULES, SINGLE_AXES) \
        == P("data", None, None, None)


def test_resolve_spec_sanitizes_non_dividing_dims():
    # 63 % 4 != 0 → sequence replicated instead of a compile failure
    assert resolve_spec((8, 63, 8, 32), ("batch", "sp_seq", "heads", None),
                        SINGLE_POD_RULES, SINGLE_AXES) \
        == P("data", None, "model", None)
    # heads=2 over model=4 → replicated
    assert resolve_spec((8, 64, 2, 32), ("batch", None, "heads", None),
                        SINGLE_POD_RULES, SINGLE_AXES) \
        == P("data", None, None, None)


def test_resolve_spec_never_reuses_a_mesh_axis():
    # both tags map to "model": first dim wins, second replicates
    assert resolve_spec((64, 512), ("heads", "vocab"),
                        SINGLE_POD_RULES, SINGLE_AXES) == P("model", None)


def test_shard_identity_without_context_and_applies_with_context():
    x = jnp.ones((4, 8))
    assert active_rules() is None
    assert shard(x, "batch", None) is x          # no context → no-op
    mesh = make_test_mesh(1, 1)
    with use_rules(SINGLE_POD_RULES, mesh):
        assert active_rules() == (SINGLE_POD_RULES, mesh)
        y = shard(x, "batch", "vocab")
        # constraint applied (spec resolution is covered above; a 1-device
        # mesh collapses to SingleDeviceSharding) and values unchanged
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert active_rules() is None                # context restored


def test_use_rules_nesting_innermost_wins():
    mesh = make_test_mesh(1, 1)
    with use_rules(SINGLE_POD_RULES, mesh):
        with use_rules(CP_SERVE_RULES, mesh):
            assert active_rules()[0] is CP_SERVE_RULES
        assert active_rules()[0] is SINGLE_POD_RULES


# ------------------------------------------------------------ compression

def test_compress_round_trip_identity_each_step():
    """compress→decompress + residual equals the identity at every step:
    compressed + new_residual == grads + old_residual exactly."""
    rng = np.random.default_rng(0)
    grads = {"w": jnp.asarray(rng.normal(size=(128,)), jnp.float32),
             "b": {"c": jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)}}
    res = zero_residual(grads)
    for _ in range(10):
        comp, res_new = compress_with_error_feedback(grads, res)
        total_in = jax.tree_util.tree_map(jnp.add, grads, res)
        total_out = jax.tree_util.tree_map(jnp.add, comp, res_new)
        for a, b in zip(jax.tree_util.tree_leaves(total_in),
                        jax.tree_util.tree_leaves(total_out)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
        res = res_new


def test_compress_telescopes_over_steps():
    """Σ_t compressed_t + residual_T == T·grads + residual_0 (telescoping
    error feedback) — the property that makes the mean update unbiased."""
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    grads = {"w": g}
    res = zero_residual(grads)
    acc = jnp.zeros_like(g)
    T = 25
    for _ in range(T):
        comp, res = compress_with_error_feedback(grads, res)
        acc = acc + comp["w"]
    np.testing.assert_allclose(np.asarray(acc + res["w"]),
                               np.asarray(T * g), rtol=1e-4, atol=1e-4)


def test_zero_residual_structure_and_dtype():
    grads = {"a": jnp.ones((3,), jnp.bfloat16), "b": jnp.ones((2, 2))}
    res = zero_residual(grads)
    assert jax.tree_util.tree_structure(res) == \
        jax.tree_util.tree_structure(grads)
    for leaf in jax.tree_util.tree_leaves(res):
        assert leaf.dtype == jnp.float32
        assert float(jnp.abs(leaf).sum()) == 0.0


def test_compressed_values_are_int8_representable():
    rng = np.random.default_rng(2)
    grads = {"w": jnp.asarray(rng.normal(size=(512,)), jnp.float32)}
    comp, _ = compress_with_error_feedback(grads, zero_residual(grads))
    w = np.asarray(comp["w"])
    scale = np.abs(np.asarray(grads["w"])).max() / 127.0
    codes = w / scale
    np.testing.assert_allclose(codes, np.round(codes), atol=1e-4)
    assert np.abs(codes).max() <= 127 + 1e-4

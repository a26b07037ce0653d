"""Unit tests of the partitioner's sharding rules: tag → PartitionSpec
resolution (``repro.dist.sharding.resolve_spec``)."""
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import PARTITIONER_RULES, resolve_spec

# a two-axis rule table of the tests' own: each tag names a mesh axis,
# a tuple spreads one dim over two axes
RULES = {"rows": "data", "cols": "model", "both": ("data", "model"),
         "stream": "stream", "vertex": None}
AXES = {"data": 2, "model": 4}


def test_resolve_spec_sanitizes_non_dividing_dims():
    # 63 % 4 != 0 → the dim is replicated instead of a compile failure
    assert resolve_spec((8, 63), ("rows", "cols"), RULES, AXES) \
        == P("data", None)
    # 2 over model = 4 → replicated; a tuple needs the product to divide
    assert resolve_spec((8, 2), ("rows", "cols"), RULES, AXES) \
        == P("data", None)
    assert resolve_spec((8, 4), ("both", None), RULES, AXES) \
        == P(("data", "model"), None)
    assert resolve_spec((12, 4), ("both", None), RULES, AXES) \
        == P(None, None)
    # an axis the mesh lacks is dropped, not an error
    assert resolve_spec((16,), ("stream",), RULES, AXES) == P(None)
    assert resolve_spec((16,), ("stream",), PARTITIONER_RULES,
                        {"stream": 4}) == P("stream")


def test_resolve_spec_never_reuses_a_mesh_axis():
    # both dims ask for "model": the first dim wins, the second replicates
    assert resolve_spec((64, 512), ("cols", "cols"), RULES, AXES) \
        == P("model", None)
    # a tuple entry uses up both of its axes
    assert resolve_spec((8, 8, 8), ("both", "rows", "cols"), RULES,
                        AXES) == P(("data", "model"), None, None)

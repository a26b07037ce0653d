"""repro.analysis: lint rules (planted violations), allowlist burn-down,
IR analyzers, and the dryrun parser-extraction shims."""
import textwrap
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import Allow, run_lint
from repro.analysis import ir
from repro.analysis.rules import (DeprecatedApi, JitPurity, RawCollective,
                                  SessionBypass, StagePlumb)


def plant(tmp_path, relpath, source):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return p


# ---------------------------------------------------------------- lint rules

def test_raw_collective_planted(tmp_path):
    plant(tmp_path, "src/repro/core/bad.py", """\
        import jax
        from jax import lax
        from jax.lax import ppermute

        def f(x):
            y = jax.lax.psum(x, "parts")
            return lax.all_gather(y, "parts")
        """)
    rep = run_lint(root=tmp_path, rules=[RawCollective()], allowlist=[])
    assert sorted(f.key for f in rep.violations) == \
        ["all_gather", "ppermute", "psum"]


def test_raw_collective_skips_dist_layer(tmp_path):
    plant(tmp_path, "src/repro/dist/collectives.py", """\
        import jax

        def psum(x, axis):
            return jax.lax.psum(x, axis)
        """)
    rep = run_lint(root=tmp_path, rules=[RawCollective()], allowlist=[])
    assert rep.ok, rep.format()


def test_stage_plumb_planted(tmp_path):
    plant(tmp_path, "src/repro/core/partitioner.py", """\
        from .clustering import streaming_clustering
        from . import transform

        def strategy(src, dst):
            clu = streaming_clustering(src, dst)
            return transform.transform_np(src, dst, clu)
        """)
    rep = run_lint(root=tmp_path, rules=[StagePlumb()], allowlist=[])
    keys = sorted(f.key for f in rep.violations)
    assert "streaming_clustering" in keys and "transform_np" in keys


def test_session_bypass_planted(tmp_path):
    plant(tmp_path, "examples/demo.py", """\
        from repro.graph import build_layout, simulate_pagerank

        lay = build_layout(src, dst, V, assign, k)
        pr = simulate_pagerank(lay, iters=30)
        """)
    rep = run_lint(root=tmp_path, rules=[SessionBypass()], allowlist=[])
    assert sorted(f.key for f in rep.violations) == \
        ["build_layout", "simulate_pagerank"]


def test_deprecated_api_planted_and_docstrings_exempt(tmp_path):
    plant(tmp_path, "src/repro/user.py", '''\
        """Docstring mentions clugp_partition and comm_bytes_halo —
        strings never trip the AST rule."""

        def f(lay):
            assert not hasattr(lay, "clugp_partition")   # string: fine
            return lay.comm_bytes_halo() + clugp_partition(lay)
        ''')
    rep = run_lint(root=tmp_path, rules=[DeprecatedApi()], allowlist=[])
    assert sorted(f.key for f in rep.violations) == \
        ["clugp_partition", "comm_bytes_halo"]


def test_jit_purity_planted_direct_and_transitive(tmp_path):
    plant(tmp_path, "src/repro/hot.py", """\
        import time
        import numpy as np
        import jax

        def helper(x):
            return x * np.random.rand()      # impure, called from traced

        @jax.jit
        def step(x):
            return helper(x) + time.time()   # impure, directly traced

        def host_only():
            return time.time()               # untraced host code: fine

        def body(c, _):
            return c + np.random.randn(), None

        def driver(x):
            return jax.lax.scan(body, x, None, length=3)
        """)
    rep = run_lint(root=tmp_path, rules=[JitPurity()], allowlist=[])
    keys = sorted(f.key for f in rep.violations)
    assert keys == ["numpy.random.rand", "numpy.random.randn",
                    "time.time"], keys


def test_jit_purity_allows_static_host_numpy(tmp_path):
    plant(tmp_path, "src/repro/shapes.py", """\
        import numpy as np
        import jax

        @jax.jit
        def f(x):
            pad = int(np.ceil(x.shape[0] / 8)) * 8   # static shape math
            return jax.numpy.pad(x, (0, pad - x.shape[0]))
        """)
    rep = run_lint(root=tmp_path, rules=[JitPurity()], allowlist=[])
    assert rep.ok, rep.format()


# ---------------------------------------------------------- allowlist rules

@pytest.fixture()
def one_violation_tree(tmp_path):
    plant(tmp_path, "examples/demo.py", "lay = build_layout(1, 2)\n")
    return tmp_path


def test_allowlist_demotes_exact_count(one_violation_tree):
    allow = [Allow("SESSION-BYPASS", "examples/demo.py", "build_layout",
                   1, "test")]
    rep = run_lint(root=one_violation_tree, rules=[SessionBypass()],
                   allowlist=allow)
    assert rep.ok and len(rep.findings) == 1 and rep.findings[0].allowlisted


def test_allowlist_errors_on_count_drift_both_ways(one_violation_tree):
    for n in (0, 2):
        allow = [Allow("SESSION-BYPASS", "examples/demo.py",
                       "build_layout", n, "test")]
        rep = run_lint(root=one_violation_tree, rules=[SessionBypass()],
                       allowlist=allow)
        assert not rep.ok and rep.errors, n


def test_allowlist_ignores_entries_for_inactive_rules(one_violation_tree):
    # a partial-rule run (the pytest wrappers) must not reconcile other
    # rules' entries against a tree those rules never scanned
    allow = [Allow("SESSION-BYPASS", "examples/demo.py", "build_layout",
                   1, "test"),
             Allow("DEPRECATED-API", "tests/test_session.py",
                   "comm_bytes_halo", 1, "not scanned here")]
    rep = run_lint(root=one_violation_tree, rules=[SessionBypass()],
                   allowlist=allow)
    assert rep.ok, rep.format()


def test_real_tree_is_clean():
    """The CI gate, as a test: the shipped tree has zero violations and
    an exactly-reconciled allowlist."""
    rep = run_lint()
    assert rep.ok, rep.format()


# ------------------------------------------------------------- IR analyzers

def test_dtype_drift_catches_f16_repromotion():
    def f(x):
        q = x.astype(jnp.float16)        # quantized payload …
        return q.astype(jnp.float32) * 2  # … silently re-promoted

    sites = ir.dtype_drift(f, jnp.ones(8))
    assert [(s["old"], s["new"]) for s in sites] == \
        [("float16", "float32")]


def test_dtype_drift_ignores_dequantize_and_allow():
    def dequant(codes, scale):
        return codes.astype(jnp.float32) * scale   # kind change: fine

    assert ir.dtype_drift(dequant, jnp.zeros(8, jnp.uint8),
                          jnp.float32(0.5)) == []

    def f(x):
        return x.astype(jnp.float16).astype(jnp.float32)

    assert ir.dtype_drift(f, jnp.ones(4),
                          allow=[("float16", "float32")]) == []


def test_retrace_count_stable_vs_leaky():
    def f(x, k):
        return x * k

    stable = ir.retrace_count(
        f, [(jnp.ones(4), jnp.float32(i)) for i in range(4)])
    assert stable == 1, stable

    leaky = ir.retrace_count(
        f, [(jnp.ones(4), float(i)) for i in range(4)],
        jit_kwargs=dict(static_argnums=1))
    assert leaky == 4, leaky


def test_scatter_copy_detected_in_scan_but_not_transform():
    def scat(x, idx):
        def body(c, i):
            return c.at[i].add(1.0), None
        out, _ = jax.lax.scan(body, x, idx)
        return out

    sites = ir.scatter_copy_sites(scat, jnp.zeros(8), jnp.arange(4) % 3)
    assert len(sites) == 1 and sites[0]["path"] == "scan", sites

    # the production transform scan is the arithmetic one-hot rewrite —
    # it must stay scatter-free (EXPERIMENTS.md §Perf-partitioner)
    from functools import partial
    from repro.core.transform import transform_jax
    z = jnp.zeros(16, jnp.int32)
    jx = jax.make_jaxpr(partial(transform_jax, k=4))(
        jnp.arange(10, dtype=jnp.int32), jnp.arange(10, dtype=jnp.int32),
        z, jnp.ones(16, jnp.int32), z)
    assert ir.scatter_copy_sites(jx) == []


def test_static_offset_scatter_not_flagged():
    def f(x):
        def body(c, _):
            return c.at[0].set(1.0), None    # constant index: harmless
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    assert ir.scatter_copy_sites(f, jnp.zeros(8)) == []


def test_unreduced_divergence_planted_and_reduced():
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("parts",))

    def bad(x):
        return x.sum()                       # per-shard partial sum

    sm_bad = jax.shard_map(bad, mesh=mesh, in_specs=P("parts"),
                           out_specs=P(), check_vma=False)
    div = ir.unreduced_divergence(sm_bad, jnp.ones(8))
    assert [d["output"] for d in div] == [0], div

    def good(x):
        return jax.lax.psum(x.sum(), "parts")

    sm_good = jax.shard_map(good, mesh=mesh, in_specs=P("parts"),
                            out_specs=P(), check_vma=False)
    assert ir.unreduced_divergence(sm_good, jnp.ones(8)) == []

    def sharded_out(x):
        return x * 2                         # varying but declared so

    sm_ok = jax.shard_map(sharded_out, mesh=mesh, in_specs=P("parts"),
                          out_specs=P("parts"), check_vma=False)
    assert ir.unreduced_divergence(sm_ok, jnp.ones(8)) == []


# -------------------------------------------------- dryrun extraction shims

SAMPLE_HLO = """\
  %x = f32[8,4]{1,0} parameter(0)
  %all-reduce.1 = f32[8,4]{1,0} all-reduce(f32[8,4]{1,0} %x)
  %all-to-all.2 = (f32[1,4]{1,0}, f32[1,4]{1,0}) all-to-all(%a, %b)
  %collective-permute-start.3 = (f32[8]{0}, f32[8]{0}) collective-permute-start(%x)
  %collective-permute-done.3 = f32[8]{0} collective-permute-done(%collective-permute-start.3)
  ROOT %r = f32[8,4]{1,0} add(%x, %x)
"""


def test_dryrun_parser_shims_are_identity_and_warn():
    # import late: dryrun rewrites XLA_FLAGS at import, which only
    # matters before jax initializes (it already has, above)
    from repro.launch import dryrun

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        shim_bytes = dryrun.collective_bytes(SAMPLE_HLO)
        shim_count = dryrun.collective_permute_count(SAMPLE_HLO)
    assert [str(x.message) for x in w
            if issubclass(x.category, DeprecationWarning)], \
        "shims must warn"
    assert shim_bytes == ir.collective_bytes(SAMPLE_HLO)
    assert shim_count == ir.collective_permute_count(SAMPLE_HLO)
    # and the parse itself is sane: 128B all-reduce, 2×16B all-to-all
    # tuple, one async permute pair counted once (32B, done half skipped)
    assert shim_bytes["all-reduce"] == 128
    assert shim_bytes["all-to-all"] == 32
    assert shim_bytes["collective-permute"] == 32
    assert shim_count == 1


def test_dryrun_reexports_parser_constants():
    from repro.launch import dryrun

    assert dryrun.COLLECTIVE_KINDS is ir.COLLECTIVE_KINDS
    assert dryrun.DTYPE_BYTES is ir.DTYPE_BYTES
    assert dryrun.SHAPE_RE is ir.SHAPE_RE

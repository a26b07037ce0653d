"""The GAS local phase: the segmented reduction over destination-sorted
edge views (``engine._edge_reduce``) against ``segment_sum`` /
``segment_min`` over the unsorted edges, and the compiled iteration's
scatters (only the halo exchange's own remain)."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.graph import PROGRAM_NAMES, build_layout, engine, get_program
from repro.dist.halo import _pad_value, get_exchange

from conftest import random_graph_and_assign

K, L_MAX, E_MAX = 6, 24, 64


def _edge_tables(seed: int, k: int = K):
    """(k, E_MAX) edge tables as the layout pads them (pad lanes target
    L_MAX), with partition 1 empty, partition 2 all on two slots, and
    slots no edge targets."""
    rng = np.random.default_rng(seed)
    src = np.full((k, E_MAX), L_MAX, np.int32)
    dst = np.full((k, E_MAX), L_MAX, np.int32)
    mask = np.zeros((k, E_MAX), bool)
    for p in range(k):
        m = {1: 0, 2: 9}.get(p, int(rng.integers(1, E_MAX + 1)))
        hi = 2 if p == 2 else L_MAX - 3          # top slots stay empty
        src[p, :m] = rng.integers(0, L_MAX, m)
        dst[p, :m] = rng.integers(0, hi, m)
        mask[p, :m] = True
    return {"edge_src": jnp.asarray(src), "edge_dst": jnp.asarray(dst),
            "edge_mask": jnp.asarray(mask),
            "vert_gid": jnp.zeros((k, L_MAX), jnp.int32)}


def _slot_values(seed: int, dtype, k: int = K):
    """(k, L_MAX + 1) per-slot values; the pad slot holds a non-identity
    value, so an unmasked pad lane would show; some slots hold the CC
    sentinel."""
    rng = np.random.default_rng(seed + 100)
    if dtype == np.float32:
        vals = rng.random((k, L_MAX + 1)).astype(np.float32)
    else:
        vals = rng.integers(0, 1 << 20, (k, L_MAX + 1)).astype(np.int32)
        vals[:, ::5] = engine.CC_SENTINEL
    vals[:, L_MAX] = 7
    return jnp.asarray(vals)


def _unsorted(per_edge, tgt, mask, combine):
    ident = _pad_value(combine, per_edge.dtype)
    seg = (jax.ops.segment_sum if combine == "sum"
           else jax.ops.segment_min)
    return seg(jnp.where(mask, per_edge, ident), tgt,
               num_segments=L_MAX + 1)[:L_MAX]


CASES = [("sum", np.float32), ("sum", np.int32), ("min", np.int32)]


@pytest.mark.parametrize("kind", ["in", "both"])
@pytest.mark.parametrize("parts", [K, 2], ids=["stacked", "local_stack"])
@pytest.mark.parametrize("combine,dtype", CASES,
                         ids=["sum_f32", "sum_i32", "min_i32"])
def test_edge_reduce_matches_unsorted_segment_ops(combine, dtype, parts,
                                                  kind):
    """Over the whole (k, …) stack, and over one device's (m, …) local
    stack of the mesh engine: min and integer sums exact, float sums to
    their order of addition."""
    part = slice(0, K) if parts == K else slice(1, 1 + parts)  # 1 is empty
    dev = {f: a[part] for f, a in _edge_tables(0).items()}
    vals = _slot_values(0, dtype)[part]
    prog = engine.GASProgram(name="t", combine=combine, dtype=dtype,
                             init=None, local=None, apply=None, edges=kind)
    views = engine._with_edge_views((prog,), dev)["view_" + kind]
    got = jax.vmap(lambda v, w: engine._edge_reduce(
        v[w["src"]], w, combine))(vals, views)
    tgt, src, mask = jax.vmap(
        lambda d: engine._view_edges(kind, d))(dev)
    want = jax.vmap(lambda v, t, s, m: _unsorted(v[s], t, m, combine))(
        vals, tgt, src, mask)
    assert got.shape == (parts, L_MAX) and got.dtype == want.dtype
    if dtype == np.float32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("combine,dtype", CASES,
                         ids=["sum_f32", "sum_i32", "min_i32"])
def test_edge_reduce_gives_identity_where_no_edge_lands(combine, dtype):
    """A partition with no edges (all pad lanes), slots no edge targets:
    the combine identity, as the unsorted scatter leaves it."""
    dev = _edge_tables(1)
    vals = _slot_values(1, dtype)
    prog = engine.GASProgram(name="t", combine=combine, dtype=dtype,
                             init=None, local=None, apply=None)
    views = engine._with_edge_views((prog,), dev)["view_in"]
    got = np.asarray(jax.vmap(lambda v, w: engine._edge_reduce(
        v[w["src"]], w, combine))(vals, views))
    ident = np.asarray(_pad_value(combine, dtype))
    assert (got[1] == ident).all()                       # empty partition
    assert (got[:, L_MAX - 3:] == ident).all()           # untargeted slots
    assert (got[2, 2:] == ident).all()


def test_sorted_views_hold_every_edge_lane_once():
    """A view holds each valid edge lane once, sorted by target, and every
    masked or pad lane in the pad run behind them; each slot's last lane
    ends its run."""
    dev = _edge_tables(2)
    for kind in ("in", "both"):
        prog = engine.GASProgram(name="t", combine="sum", dtype=jnp.int32,
                                 init=None, local=None, apply=None,
                                 edges=kind)
        view = engine._with_edge_views((prog,), dev)["view_" + kind]
        tgt, src, mask = jax.vmap(lambda d: engine._view_edges(kind, d))(dev)
        for p in range(K):
            vt, vs = np.asarray(view["tgt"][p]), np.asarray(view["src"][p])
            assert (np.diff(vt) >= 0).all()
            real = vt < L_MAX
            m = np.asarray(mask[p])
            assert sorted(zip(vt[real], vs[real])) == sorted(
                zip(np.asarray(tgt[p])[m], np.asarray(src[p])[m]))
            assert (~real).sum() == (~m).sum()
            last, has = np.asarray(view["last"][p]), np.asarray(view["has"][p])
            for slot in range(L_MAX):
                run = np.flatnonzero(vt == slot)
                assert has[slot] == (run.size > 0)
                if run.size:
                    assert last[slot] == run[-1]


# ------------------------------------------------------------ compiled HLO

_REF = re.compile(r"(?:body|condition|to_apply|calls)=%([\w.\-]+)")


def _loop_body_scatters(text: str) -> int:
    """Scatter ops in the computations every ``while`` body of the
    optimized HLO module reaches."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    todo = [b for c in comps.values() for line in c if " while(" in line
            for b in re.findall(r"body=%([\w.\-]+)", line)]
    assert todo, "no loop in the compiled GAS step"
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo.extend(_REF.findall(line))
    return sum(1 for c in seen for line in comps[c]
               if re.search(r"\sscatter\(", line))


@pytest.mark.parametrize("name", PROGRAM_NAMES)
def test_no_scatter_in_the_local_phase(name):
    """Stacked iteration on the halo wire: the only scatters in the
    compiled step are the exchange's own (the segment combine of
    ``_route_reduce`` and the scatter of ``_route_broadcast``), and the
    loop holds them — except degree's, whose
    iteration reads no loop state, so XLA hoists all of it (exchange
    included) out of the loop."""
    src, dst, n, assign = random_graph_and_assign(0, 4)
    lay = build_layout(src, dst, assign, n, 4)
    dev = engine._stack_dev(lay, "halo")
    ex = get_exchange("halo", lay)
    text = engine._sim_gas.lower(get_program(name, n), dev, 2, ex, None,
                                 False, None).compile().as_text()
    assert len(re.findall(r"\sscatter\(", text)) == 2
    assert _loop_body_scatters(text) == (0 if name == "degree" else 2)


# ------------------------------------------------------------ gas.run counter

@pytest.mark.parametrize("names,views", [
    (("pagerank",), 1), (("cc",), 2), (("degree",), 2),
    (("pagerank", "centrality"), 2), (("sssp", "cc"), 3)])
def test_gas_run_counts_its_sorted_lanes(names, views):
    """``gas.run``'s ``sorted_lanes``: the edge lanes one iteration's
    local phases scan on the device, over all k stacked partitions — a
    view per program, twice the edge lanes where it is undirected."""
    import time
    from repro import obs
    from repro.graph import simulate_gas, simulate_gas_many
    src, dst, n, assign = random_graph_and_assign(1, 4)
    lay = build_layout(src, dst, assign, n, 4)
    t0 = time.perf_counter()
    progs = [get_program(p, n) for p in names]
    if len(progs) == 1:
        simulate_gas(progs[0], lay, iters=2, exchange="halo")
    else:
        simulate_gas_many(progs, lay, iters=2, exchange="halo")
    runs = [r[4] for r in obs.spans(t0) if r[0] == "gas.run"]
    assert [r["sorted_lanes"] for r in runs] == [
        views * lay.k * lay.edge_src.shape[1]]


@pytest.mark.multidevice
def test_mesh_gas_run_counts_the_lanes_of_its_device(multidevice):
    """On a mesh, one device scans the lanes of its k/D partitions."""
    out = multidevice("""
        import time
        from repro import obs
        from repro.core import CLUGPConfig, web_graph
        from repro.launch.mesh import make_graph_mesh
        from repro.session import GraphSession, SessionConfig
        g = web_graph(scale=9, seed=1)
        sess = GraphSession(SessionConfig(clugp=CLUGPConfig.optimized(4),
                                          exchange="halo"))
        sess.partition(g.src, g.dst, g.num_vertices).layout()
        t0 = time.perf_counter()
        sess.run("pagerank", iters=2, mesh=make_graph_mesh(4))
        sess.run("cc", iters=2, mesh=make_graph_mesh(4))
        e_max = sess.partition_layout.edge_src.shape[1]
        for r in obs.spans(t0):
            if r[0] == "gas.run":
                print(r[4]["parts_per_device"], r[4]["sorted_lanes"], e_max)
    """, n_devices=2)
    rows = [[int(x) for x in line.split()] for line in out.splitlines()
            if line]
    e_max = rows[0][2]
    assert [row[:2] for row in rows] == [[2, 2 * e_max], [2, 4 * e_max]]

"""The halo wire's routes (``PartitionLayout.halo_routes``): every real
mirror lane routed once, padded only per device pair; the lanes the
stacked halves gather and scatter; ``gas.run``'s lane counters; and the
halo wire's values against the dense wire's, stacked, fused and on a
mesh of m = k/D partitions a device."""
import re
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.dist.halo import get_exchange
from repro.graph import (build_layout, engine, get_program, simulate_gas,
                         simulate_gas_many)

from conftest import random_graph_and_assign

# (k, m): one partition a device, several, one device; (16, 16) and
# (12, 3) need H_dev above the pad multiple so that a device's D·H_dev
# lanes split over its m rows
CUTS = [(8, 1), (8, 2), (8, 4), (8, 8), (16, 16), (12, 3)]


def _layout(k: int, seed: int = 0):
    src, dst, n, assign = random_graph_and_assign(seed, k)
    return build_layout(src, dst, assign, n, k)


def _expected_h_dev(lay, m: int) -> int:
    """The fullest device pair's real lanes, rounded up to the pad
    multiple, and on by the pad multiple until D·H_dev splits over m."""
    d = lay.k // m
    pairs = lay.halo_cnt.reshape(d, m, d, m).sum(axis=(1, 3))
    step = lay.pad_multiple
    h = max(-(-int(pairs.max()) // step), 1) * step
    while (d * h) % m:
        h += step
    return h


@pytest.mark.parametrize("k,m", CUTS)
def test_routes_carry_every_mirror_once(k, m):
    """Decoded from the flat-stack indices, each real send lane is a
    mirror slot of its partition, paired lane for lane with a master slot
    of the same vertex on its owner; every mirror slot is sent exactly
    once; pad lanes read and land only in partition 0's pad column; and
    H_dev is the fullest device pair, rounded up."""
    lay = _layout(k)
    r = lay.halo_routes(m)
    d, h, col = k // m, r.h_dev, lay.l_max + 1
    assert r.send.shape == r.recv.shape == (k, d * h // m)
    send = r.send.reshape(d, d, h)                     # [source, owner dev]
    recv = r.recv.reshape(d, d, h).transpose(1, 0, 2)  # [source, owner dev]
    real = send % col != lay.l_max
    np.testing.assert_array_equal(real, recv % col != lay.l_max)
    assert (send[~real] == lay.l_max).all() and (recv[~real] == lay.l_max
                                                 ).all()
    src_dev, own_dev, _ = np.nonzero(real)
    p = src_dev * m + send[real] // col
    q = own_dev * m + recv[real] // col
    s, t = send[real] % col, recv[real] % col
    assert lay.vert_mask[p, s].all() and not lay.is_master[p, s].any()
    assert lay.is_master[q, t].all()
    np.testing.assert_array_equal(lay.vert_gid[p, s], lay.vert_gid[q, t])
    np.testing.assert_array_equal(lay.owner[p, s], q)
    routed = np.zeros(lay.vert_mask.shape, np.int64)
    np.add.at(routed, (p, s), 1)
    np.testing.assert_array_equal(routed,
                                  lay.vert_mask & ~lay.is_master)
    assert int(real.sum()) == lay.mirrors_total
    assert r.h_dev == _expected_h_dev(lay, m)
    assert r.lanes == d * r.h_dev
    assert r.mirror_lanes == int(real.sum(axis=(1, 2)).max())


@pytest.mark.parametrize("seed,k", [(0, 4), (5, 8)])
def test_routes_at_one_partition_a_device_are_the_per_pair_tables(seed, k):
    """At m = 1 the device pair is the partition pair: the routes hold
    the per-pair tables lane for lane, and the wire's bytes are the
    per-pair formula's."""
    lay = _layout(k, seed)
    r = lay.halo_routes(1)
    assert r.h_dev == lay.h_max
    np.testing.assert_array_equal(r.send.reshape(k, k, -1), lay.halo_send)
    np.testing.assert_array_equal(r.recv.reshape(k, k, -1), lay.halo_recv)
    per_chip = lay.comm_bytes("halo", parts_per_device=1)
    assert per_chip == 2 * (k - 1) * lay.h_max * 4
    assert k * per_chip == lay.comm_bytes("halo")


def test_routes_are_built_once_and_refuse_a_non_divisor():
    lay = _layout(8)
    assert lay.halo_routes(2) is lay.halo_routes(2)
    assert lay.halo_routes() is lay.halo_routes(8)
    with pytest.raises(ValueError, match="do not divide"):
        lay.halo_routes(3)


# ------------------------------------------------------ lanes on the wire

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INST = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\]"
                   r"[^ ]*\s+([\w\-]+)\(([^)]*)\)")


def _gathered_and_scattered_lanes(hlo: str) -> tuple:
    """(lanes of each gather's output, lanes of each scatter's updates):
    the leading dimension, in the optimized HLO."""
    gathers, scatters, shapes = [], [], {}
    for line in hlo.splitlines():
        if _COMP.match(line):
            shapes = {}
            continue
        m = _INST.match(line)
        if not m:
            continue
        name, _, dims, op, args = m.groups()
        shapes[name] = [int(x) for x in dims.split(",") if x]
        if op == "gather":
            gathers.append(shapes[name][0])
        elif op == "scatter":
            updates = args.split(",")[2].strip().lstrip("%")
            scatters.append(shapes[updates][0])
    return gathers, scatters


@pytest.mark.parametrize("programs", [0, 3], ids=["one", "fused3"])
@pytest.mark.parametrize("combine", ["sum", "min"])
@pytest.mark.parametrize("half", ["reduce", "broadcast"])
def test_stacked_halo_moves_only_the_mirror_lanes(half, combine, programs):
    """One gather and one scatter a phase, each of mirrors_total lanes
    rounded up to the pad multiple (the per-pair tables moved k·k·H_max),
    for one program and for a fused bundle whose lanes carry three."""
    lay = _layout(4, seed=3)
    dev = engine._stack_dev(lay, "halo")
    ex = get_exchange("halo", lay)
    dtype = jnp.float32 if combine == "sum" else jnp.int32
    shape = ((lay.k, programs, lay.l_max) if programs
             else (lay.k, lay.l_max))
    values = jnp.ones(shape, dtype)
    op = {("reduce", 0): ex.reduce_stacked,
          ("broadcast", 0): ex.broadcast_stacked,
          ("reduce", 3): ex.reduce_stacked_multi,
          ("broadcast", 3): ex.broadcast_stacked_multi}[half, programs]
    text = jax.jit(lambda v, d: op(v, d, combine)[0]).lower(
        values, dev).compile().as_text()
    gathers, scatters = _gathered_and_scattered_lanes(text)
    step = lay.pad_multiple
    lanes = -(-lay.mirrors_total // step) * step
    assert lanes < lay.k * lay.k * lay.h_max
    assert gathers == [lanes] and scatters == [lanes], (gathers, scatters)


# --------------------------------------------------------- gas.run counter

@pytest.mark.parametrize("names", [("pagerank",), ("cc",),
                                   ("pagerank", "centrality")])
def test_gas_run_counts_its_halo_lanes(names):
    """``gas.run``'s ``halo_lanes`` (lanes one device packs a phase, pads
    included) and ``mirror_lanes`` (the real ones): on one device, every
    mirror, rounded up; the dense wire records neither."""
    lay = _layout(4, seed=1)
    progs = [get_program(p, lay.num_vertices) for p in names]
    for exchange in ("halo", "dense"):
        t0 = time.perf_counter()
        if len(progs) == 1:
            simulate_gas(progs[0], lay, iters=2, exchange=exchange)
        else:
            simulate_gas_many(progs, lay, iters=2, exchange=exchange)
        (run,) = [r[4] for r in obs.spans(t0) if r[0] == "gas.run"]
        if exchange == "dense":
            assert "halo_lanes" not in run and "mirror_lanes" not in run
            continue
        step = lay.pad_multiple
        assert run["mirror_lanes"] == lay.mirrors_total
        assert run["halo_lanes"] == -(-lay.mirrors_total // step) * step


# ------------------------------------------------- halo against dense wire

@pytest.mark.parametrize("names", [("pagerank", "centrality"),
                                   ("cc", "sssp")])
@pytest.mark.parametrize("fused", [False, True], ids=["one", "fused"])
def test_stacked_halo_matches_dense(names, fused):
    """Stacked, one program at a time and fused (``run_many``'s path):
    integer programs exactly, float ones to their summation order."""
    src, dst, n, assign = random_graph_and_assign(2, 8, n=400)
    lay = build_layout(src, dst, assign, n, 8)
    progs = [get_program(p, n) for p in names]

    def run(exchange):
        if fused:
            return simulate_gas_many(progs, lay, iters=25,
                                     exchange=exchange)
        return [simulate_gas(p, lay, iters=25, exchange=exchange)
                for p in progs]

    for name, halo, dense in zip(names, run("halo"), run("dense")):
        if np.issubdtype(halo.dtype, np.integer):
            np.testing.assert_array_equal(halo, dense, err_msg=name)
        else:
            np.testing.assert_allclose(halo, dense, rtol=1e-6, atol=1e-9,
                                       err_msg=name)


@pytest.mark.multidevice
@pytest.mark.parametrize("k", [8, 16])
def test_mesh_halo_matches_dense(multidevice, k):
    """On 8 devices, one (k = 8) and two (k = 16) partitions a device:
    PageRank to float tolerance and CC exactly against the dense wire, the
    fused bundle too where the fused driver runs (k = 8), and ``gas.run``
    counts the device's lanes and the wire's bytes from its routes."""
    multidevice(f"""
    import time
    import numpy as np
    from repro import obs
    from repro.graph import (build_layout, get_program, shard_map_cc,
                             shard_map_gas_many, shard_map_pagerank)
    from repro.launch.mesh import make_graph_mesh
    import sys
    sys.path.insert(0, {str(Path(__file__).parent)!r})
    from conftest import random_graph_and_assign

    k = {k}
    src, dst, n, assign = random_graph_and_assign(4, k, n=400)
    lay = build_layout(src, dst, assign, n, k)
    mesh = make_graph_mesh(k)
    m = k // mesh.shape["parts"]
    t0 = time.perf_counter()
    pr = {{ex: shard_map_pagerank(lay, mesh, 25, exchange=ex)
           for ex in ("halo", "dense")}}
    np.testing.assert_allclose(pr["halo"], pr["dense"], rtol=1e-6,
                               atol=1e-9)
    cc = {{ex: shard_map_cc(lay, mesh, 30, exchange=ex)
           for ex in ("halo", "dense")}}
    np.testing.assert_array_equal(cc["halo"], cc["dense"])
    routes = lay.halo_routes(m)
    runs = [r[4] for r in obs.spans(t0) if r[0] == "gas.run"]
    halo = [r for r in runs if "halo_lanes" in r]
    assert len(halo) == 2 and len(runs) == 4, runs
    for r in halo:
        assert r["parts_per_device"] == m
        assert r["halo_lanes"] == routes.lanes == 8 * routes.h_dev
        assert r["mirror_lanes"] == routes.mirror_lanes
        assert r["ici_bytes"] == 2 * 7 * routes.h_dev * 4
    if m == 1:
        for names in (("pagerank", "centrality"), ("cc", "sssp")):
            progs = [get_program(p, n) for p in names]
            outs = {{ex: shard_map_gas_many(progs, lay, mesh, 25,
                                            exchange=ex)
                     for ex in ("halo", "dense")}}
            for a, b in zip(outs["halo"], outs["dense"]):
                if np.issubdtype(a.dtype, np.integer):
                    np.testing.assert_array_equal(a, b)
                else:
                    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)
    print("ok")
    """)

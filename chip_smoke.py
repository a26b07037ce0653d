"""Smoke run of the CLUGP batch job and the graph server on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the mesh paths only

One chip: a crawl-shaped ``web_graph`` at scale 20 (V=1,048,576,
E=6,427,515) is partitioned 16 ways by ``GraphSession`` on the ``jit``
backend with the kernel options at ``auto`` (Pallas, compiled by Mosaic,
on a TPU); PageRank and CC run on its layout and are held to the NumPy
oracles; the Pallas kernels are held to their XLA twins at scale 16; a
``GraphServer`` over the batch session answers a mixed query batch
before and after ingesting one 4,096-edge window, every reply checked.

Four chips: the §III-C ``sharded`` partitioner over a ``("stream",)``
mesh of the four chips, held to the one-chip ``jit`` result, and the
mesh GAS (``GraphSession.run(..., mesh=<("parts",) mesh>)``) on every
wire the multidevice tests cover, held to the one-device run and to the
oracles.

Every phase prints what it checked; wall times are this run's set-up and
smoke time, not metrics.  The last line of standard output is one JSON
object naming the device.  With no TPU the script exits non-zero and
prints no result: there is no CPU fallback.  ``tests/test_chip_smoke.py``
runs the phase functions at small scale on the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SCORE_PROGRAMS = ("pagerank", "cc", "degree", "labelprop")
# (pagerank iterations, max |mesh − reference|) per wire, the bounds
# tests/test_dist_multidevice.py holds the shard_map engine to.  The
# lossy wires' error feedback converges to the exact fixed point, but at
# scale 20 it lags at 30 iterations (max error 8.5e-5 quantized, 6.2e-4
# ragged_quantized on 4 CPU devices, the same on one device); at 60 it
# is 1.3e-6 and 1.2e-5
MESH_PAGERANK = {"halo": (30, 1e-6), "ragged": (30, 1e-6),
                 "quantized": (60, 1e-5), "ragged_quantized": (60, 5e-4)}


def expect(ok, *detail) -> None:
    """A smoke check that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(" ".join(map(str, detail)))


def expect_equal(what: str, a, b) -> None:
    a, b = np.asarray(a), np.asarray(b)
    expect(a.shape == b.shape, what, "shapes differ:", a.shape, b.shape)
    expect(np.array_equal(a, b), what, "differ at", int((a != b).sum()),
           "of", a.size, "entries")


def say(tag: str, msg: str) -> None:
    print(f"[{tag}] {msg}", flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_graph(scale: int, seed: int):
    from repro.core import web_graph
    t0 = time.perf_counter()
    g = web_graph(scale=scale, seed=seed)
    say("graph", f"web_graph scale={scale} seed={seed}: V={g.num_vertices} "
        f"E={g.num_edges} ({time.perf_counter() - t0:.1f} s host set-up)")
    return g


def _session(k: int, backend: str = "jit", nodes: int = 1, **clugp):
    from repro.core import CLUGPConfig
    from repro.session import GraphSession, SessionConfig
    cfg = CLUGPConfig.optimized(k, **clugp)
    return GraphSession(SessionConfig(clugp=cfg, backend=backend,
                                      nodes=nodes))


def check_partition(sess, g, tag: str) -> dict:
    """Every edge has a partition in [0, k), and balance ≤ τ.  The
    transform admits an edge while a partition's load is below τ·E/k —
    per stream slice, τ·E_i/k, on the n-node sharded backend — so the
    heaviest partition may pass τ·E/k by one edge per slice: the bound
    is τ + n·k/E."""
    k, tau, n = sess.k, sess.cfg.clugp.tau, sess.cfg.nodes
    a = np.asarray(sess.assign)
    expect(a.shape == (g.num_edges,), (tag, a.shape))
    expect(a.min() >= 0 and a.max() < k, (tag, a.min(), a.max()))
    st = sess.stats
    expect(st["balance"] <= tau + n * k / g.num_edges, (tag, st["balance"]))
    say(tag, f"k={k} RF={st['rf']!r} balance={st['balance']!r} "
        f"(tau={tau}) clusters={st.get('num_clusters')} "
        f"game_rounds={st.get('game_rounds')}")
    return st


def batch_job(g, k: int = 16, iters: int = 30) -> dict:
    """Partition → layout → PageRank and CC, held to the oracles."""
    from repro.core import baselines, metrics
    from repro.core.stages import resolve_cluster_kernel, resolve_game_mode
    from repro.graph import reference_cc, reference_pagerank

    sess = _session(k)
    cfg = sess.cfg.clugp
    say("batch", "kernels resolved: cluster="
        f"{resolve_cluster_kernel(cfg.cluster_kernel)} "
        f"game={resolve_game_mode(cfg.kernel, 1 << 12)}")
    t0 = time.perf_counter()
    sess.partition(g.src, g.dst, g.num_vertices).layout()
    t_part = time.perf_counter() - t0
    st = check_partition(sess, g, "batch")
    hashed = baselines.hashing(g.src, g.dst, g.num_vertices, k)
    rf_hash = metrics.replication_factor(g.src, g.dst, hashed,
                                         g.num_vertices, k)
    expect(st["rf"] < rf_hash, (st["rf"], rf_hash))
    say("batch", f"RF {st['rf']!r} < hashing RF {rf_hash!r} "
        f"(partition + layout {t_part:.1f} s, compile included)")

    t0 = time.perf_counter()
    pr = sess.run("pagerank", iters=iters)
    ref = reference_pagerank(g.src, g.dst, g.num_vertices, iters=iters)
    l1 = float(np.abs(pr.astype(np.float64) - ref).sum())
    expect(pr.shape == ref.shape and np.isfinite(pr).all())
    expect(l1 <= 1e-4, l1)
    say("batch", f"pagerank {iters} iters: L1 error vs reference_pagerank "
        f"{l1!r} (<= 1e-4) ({time.perf_counter() - t0:.1f} s)")

    t0 = time.perf_counter()
    cc, cc_iters = sess.run("cc", iters=4 * iters, tol=0.0,
                            return_iters=True)
    ref_cc = reference_cc(g.src, g.dst, g.num_vertices)
    expect_equal("cc vs reference_cc:", cc, ref_cc)
    say("batch", f"cc: equals reference_cc exactly after {cc_iters} "
        f"iters ({time.perf_counter() - t0:.1f} s)")
    return {"session": sess, "rf": st["rf"], "rf_hashing": rf_hash,
            "balance": st["balance"], "pagerank_l1": l1}


def kernel_twins(g, k: int = 16) -> dict:
    """The Pallas clustering, game and transform kernels against their
    XLA twins: equal labels and assignments, bit for bit."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from repro.core.transform import transform_jax
    from repro.kernels import ops

    on_tpu = jax.devices()[0].platform == "tpu"
    # what the device compiles for each kernel: a Mosaic custom call on TPU
    B, M, kpad = 128, 4096, 128
    i32, f32 = jnp.int32, jnp.float32
    cs = ops.cluster_scatter.lower(
        jnp.zeros((B, 3), i32), jnp.zeros((10 * B,), i32),
        jnp.zeros((4,), i32), f32(1.0)).compile().as_text()
    gb = ops.game_best_response.lower(
        jnp.zeros((M, kpad), f32), jnp.zeros((M,), f32),
        jnp.zeros((M,), f32), jnp.zeros((M,), i32), jnp.zeros((kpad,), f32),
        f32(1.0), k=k).compile().as_text()
    # the transform picks its kernel by platform, its scan for traced k
    lmax = 1.1 * g.num_edges / k
    transform = jax.jit(partial(transform_jax, k=k, lmax=lmax))
    z = jnp.zeros((g.num_vertices,), i32)
    tr = transform.lower(g.src, g.dst, z, z, z).compile().as_text()
    mosaic = {"cluster_scatter": "tpu_custom_call" in cs,
              "game_bestresponse": "tpu_custom_call" in gb,
              "greedy_transform": "tpu_custom_call" in tr}
    say("kernels", f"compiled as Mosaic custom calls: {mosaic}")
    if on_tpu:
        expect(all(mosaic.values()), mosaic)

    def run(cluster_kernel, game_kernel):
        t0 = time.perf_counter()
        s = _session(k, cluster_kernel=cluster_kernel, kernel=game_kernel)
        s.partition(g.src, g.dst, g.num_vertices)
        r = s.result
        say("kernels", f"cluster={cluster_kernel} game={game_kernel}: "
            f"RF={r.stats['rf']!r} clusters={r.stats['num_clusters']} "
            f"({time.perf_counter() - t0:.1f} s)")
        return r

    # labels come from the clustering alone, the cluster assignment from
    # the game on those labels: comparing in that order names the kernel
    # at fault
    pallas, xla = run("pallas", "pallas"), run("xla", "xla")
    expect_equal("cluster kernel: labels", pallas.clustering.clu,
                 xla.clustering.clu)
    expect_equal("game kernel: cluster assignment", pallas.cluster_assign,
                 xla.cluster_assign)
    expect_equal("edge assignment", pallas.assign, xla.assign)

    rng = np.random.default_rng(0)
    vp = rng.integers(0, k, g.num_vertices).astype(np.int32)
    deg = np.bincount(np.concatenate([g.src, g.dst]),
                      minlength=g.num_vertices).astype(np.int32)
    divided = (rng.random(g.num_vertices) < 0.1).astype(np.int32)
    expect_equal("transform kernel: assignment",
                 transform(g.src, g.dst, vp, deg, divided),
                 transform(g.src, g.dst, vp, deg, divided,
                           k_real=jnp.int32(k)))
    say("kernels", "pallas == xla: clustering labels (cluster kernel), "
        "cluster assignment (game kernel), edge assignment (transform "
        "kernel, and the whole pipeline)")
    return {"mosaic": mosaic}


def _submit_mix(srv, n: int, rng) -> list:
    tickets = []
    for i in range(24):
        prog = SCORE_PROGRAMS[i % len(SCORE_PROGRAMS)]
        verts = rng.integers(0, n, 4)
        tickets.append((srv.submit("score", program=prog, vertices=verts),
                        "score", prog, verts))
    for v in rng.integers(0, n, 4):
        tickets.append((srv.submit("owner", vertices=[v]), "owner", None,
                        [v]))
        tickets.append((srv.submit("neighbors", vertices=[v]), "neighbors",
                        None, [v]))
    return tickets


def _verify_replies(srv, tickets) -> int:
    """No reply carries an error; score replies bit-match ``run_many``
    on the same layout with the server's own (combine, dtype) grouping;
    owners are partitions; neighbors match the edge list."""
    from repro.session import resolve_program
    sess = srv.sess
    n = sess.num_vertices
    replies = {t: srv.result(t) for t, *_ in tickets}
    errors = [(t, r.error) for t, r in replies.items()
              if r is None or r.error is not None]
    expect(not errors, errors)
    cells: dict = {}
    for p in SCORE_PROGRAMS:
        prog = resolve_program(p, n)
        cells.setdefault((prog.combine, np.dtype(prog.dtype).name),
                         []).append(p)
    direct = {}
    for progs in cells.values():
        direct.update(zip(progs, sess.run_many(progs)))
    src, dst = sess.edges
    for t, kind, prog, verts in tickets:
        got = replies[t].value
        if kind == "score":
            expect(np.array_equal(got, direct[prog][np.asarray(verts)]),
                   (prog, got))
        elif kind == "owner":
            expect(0 <= int(got[0]) < sess.k, got)
        else:
            v = int(verts[0])
            want = np.unique(np.concatenate([dst[src == v], src[dst == v]]))
            expect(np.array_equal(got[0], want), (v, got[0], want))
    return len(tickets)


def service(sess, window: int = 4096, seed: int = 0) -> dict:
    """A GraphServer over the batch session: a mixed query batch, one
    ingested window, the batch again."""
    from repro.serve import GraphServer

    srv = GraphServer(sess, max_batch=64, window=window)
    rng = np.random.default_rng(seed + 1)
    n = sess.num_vertices
    out = {}
    for phase in ("before ingest", "after ingest"):
        if phase == "after ingest":
            t0 = time.perf_counter()
            flushed = srv.ingest(rng.integers(0, n, window),
                                 rng.integers(0, n, window))
            expect(flushed and srv.stats["windows"] == 1, srv.stats)
            say("serve", f"ingested {window} edges: E={sess.edges[0].shape[0]} "
                f"RF={srv.rf_trace[-1][1]!r} restreams="
                f"{srv.stats['restreams']} ({time.perf_counter() - t0:.1f} s)")
        t0 = time.perf_counter()
        tickets = _submit_mix(srv, n, rng)
        served = srv.serve_pending()
        expect(served == len(tickets), (served, len(tickets)))
        checked = _verify_replies(srv, tickets)
        say("serve", f"{phase}: {checked} replies, no errors, score "
            f"replies bit-match run_many ({time.perf_counter() - t0:.1f} s)")
        out[phase] = checked
    return out


def _peak_bytes(devices) -> list:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def sharded_partition(g, k: int = 4, nodes: int = 4,
                      rf_within: float = 0.10, **clugp):
    """§III-C: the stream split over a ("stream",) mesh of ``nodes``
    devices, held to the one-device jit result of the same run: RF
    within ``rf_within``.  Runs first in its process, so the devices'
    peak memory shows where the sharded run put its arrays.  ``clugp``
    overrides reach both sessions.  Returns the jit session."""
    import jax
    from repro.launch.mesh import make_stream_mesh

    mesh = make_stream_mesh(nodes)
    t0 = time.perf_counter()
    sh = _session(k, backend="sharded", nodes=nodes, **clugp)
    sh.partition(g.src, g.dst, g.num_vertices, mesh=mesh)
    peaks = _peak_bytes(mesh.devices.flat)
    st = check_partition(sh, g, "sharded")
    say("sharded", f"nodes={nodes}: per-node clusters "
        f"{[p['clusters'] for p in st['per_node']]}; device peak bytes "
        f"{peaks} ({time.perf_counter() - t0:.1f} s)")
    if jax.devices()[0].platform == "tpu":
        expect(min(peaks) > 0.1 * max(peaks), peaks)
    t0 = time.perf_counter()
    ref = _session(k, **clugp).partition(g.src, g.dst, g.num_vertices)
    st_jit = check_partition(ref, g, "sharded: jit reference")
    ratio = st["rf"] / st_jit["rf"]
    expect(abs(ratio - 1.0) <= rf_within, (st["rf"], st_jit["rf"]))
    say("sharded", f"RF {st['rf']!r} vs one-device jit {st_jit['rf']!r}: "
        f"ratio {ratio!r}, within {rf_within:.0%} "
        f"({time.perf_counter() - t0:.1f} s)")
    return ref


def mesh_gas(sess, g) -> dict:
    """The mesh GAS, one partition per device, on every wire: held to
    the same session's one-device run and to the oracles."""
    from repro.graph import reference_cc, reference_pagerank
    from repro.launch.mesh import make_graph_mesh

    mesh = make_graph_mesh(sess.k)
    step, args = sess.dryrun_step("pagerank", mesh=mesh)
    shards = {s.device for s in step(*args).addressable_shards}
    expect(shards == set(mesh.devices.flat), (shards, mesh.devices))
    say("mesh", f"one GAS step's output lives on {len(shards)} devices: "
        f"{sorted(d.id for d in shards)}")
    refs = {n: reference_pagerank(g.src, g.dst, g.num_vertices, iters=n)
            for n in sorted({n for n, _ in MESH_PAGERANK.values()})}
    out = {}
    for ex, (iters, tol) in MESH_PAGERANK.items():
        t0 = time.perf_counter()
        on_mesh = sess.run("pagerank", iters=iters, exchange=ex, mesh=mesh)
        one = sess.run("pagerank", iters=iters, exchange=ex)
        d_one = float(np.abs(on_mesh - one).max())
        d_ref = float(np.abs(on_mesh - refs[iters]).max())
        expect(d_one <= tol and d_ref <= tol, (ex, d_one, d_ref, tol))
        say("mesh", f"pagerank {ex}, {iters} iters: max|mesh-one device| "
            f"{d_one!r}, max|mesh-reference| {d_ref!r} (<= {tol}) "
            f"({time.perf_counter() - t0:.1f} s)")
        out[ex] = d_ref
    t0 = time.perf_counter()
    cc = sess.run("cc", iters=120, tol=0.0, exchange="halo", mesh=mesh)
    one = sess.run("cc", iters=120, tol=0.0, exchange="halo")
    ref_cc = reference_cc(g.src, g.dst, g.num_vertices)
    expect_equal("cc mesh vs one device:", cc, one)
    expect_equal("cc mesh vs reference_cc:", cc, ref_cc)
    say("mesh", f"cc halo: equals the one-device run and reference_cc "
        f"({time.perf_counter() - t0:.1f} s)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths: the sharded "
                         "partitioner and the mesh GAS")
    args = ap.parse_args(argv)
    dev = device_info()
    print(f"[device] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{dev['platform']!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if dev["count"] < want:
        print(f"chip_smoke: needs {want} chips, JAX sees {dev['count']}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    say("device", f"compile cache: {enable_compile_cache()}")
    t_all = time.perf_counter()
    g = make_graph(20, seed=0)
    phases = []
    if args.four_chips:
        t0 = time.perf_counter()
        sess = sharded_partition(g)
        phases.append(("sharded", time.perf_counter() - t0))
        t0 = time.perf_counter()
        mesh_gas(sess.layout(), g)
        phases.append(("mesh GAS", time.perf_counter() - t0))
    else:
        t0 = time.perf_counter()
        batch = batch_job(g)
        phases.append(("batch", time.perf_counter() - t0))
        t0 = time.perf_counter()
        kernel_twins(make_graph(16, seed=0))
        phases.append(("kernels", time.perf_counter() - t0))
        t0 = time.perf_counter()
        service(batch["session"])
        phases.append(("service", time.perf_counter() - t0))
    for name, dt in phases:
        say("time", f"{name}: {dt:.1f} s wall (set-up + smoke, compile "
            f"included; not a metric)")
    say("time", f"total {time.perf_counter() - t_all:.1f} s wall")
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

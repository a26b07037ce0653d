"""Graph-serving launcher: drive a resident GraphServer end to end.

``python -m repro.launch.serve_graph --scale 13 --k 8 --smoke`` builds a
web graph, partitions it, and stands up ``repro.serve.GraphServer``
in-process (no sockets — the driver IS the event loop), then:

1. **queries** — submits a batched mix of score/label/owner/neighbors
   requests, serves them microbatch by microbatch, and (``--smoke``)
   asserts every score reply bit-matches a direct
   ``GraphSession.run``/``run_many`` on the same layout;
2. **ingestion** — streams random edge arrivals through the window
   buffer, recording the RF trace as windows flush and the drift
   watermark triggers prioritized restreams (``--smoke`` asserts at
   least one restream fired and left RF ≤ the drifted RF);
   With ``--tol`` the server runs the convergence early-exit loop
   (``--iters`` becomes a cap) and, after ingestion, replays the same
   query mix **cold** (program inits) and **warm** (pre-swap fixed
   points as seeds) — ``--smoke`` gates warm ``iters_run`` and
   ``query_ms`` strictly below cold;
3. **preemption** — (``--smoke`` + ``--ckpt-dir``) spawns a child copy
   of itself (``--child-snapshot``) that builds the same deterministic
   server, checkpoints through ``dist.ft.ServiceFT``, and SIGKILLs its
   own process mid-serving; the parent resumes from the snapshot and
   asserts the identical config blob, assignment, and query replies.
   The child runs to its death before the parent touches a backend: an
   accelerator belongs to one process at a time.

Writes ``results/BENCH_serve.json`` (query latency, RF trace summary)
for ``benchmarks/trend.py`` to diff across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from repro.core import CLUGPConfig, web_graph
from repro.dist.ft import ServiceFT
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import GraphServer
from repro.session import GraphSession, SessionConfig

SCORE_PROGRAMS = ("pagerank", "degree", "cc", "labelprop")


def build_server(args, ft=None) -> GraphServer:
    """Deterministic graph → session → server from the CLI args — the
    parent, the ``--child-snapshot`` child, and the resumed server all
    reconstruct bit-identical state from the same flags."""
    g = web_graph(scale=args.scale, seed=args.seed)
    cfg = SessionConfig(clugp=CLUGPConfig(k=args.k), backend=args.backend,
                        exchange=args.exchange, iters=args.iters)
    sess = GraphSession(cfg).partition(g.src, g.dst, g.num_vertices)
    sess.layout()
    return GraphServer(sess, max_batch=args.max_batch, window=args.window,
                       rf_watermark=args.watermark,
                       restream_passes=args.restream_passes,
                       tol=args.tol, ft=ft)


def drive_queries(srv: GraphServer, args, check: bool) -> dict:
    """Submit a batched query mix, serve it, optionally verify replies
    against the session run directly on the same layout."""
    rng = np.random.default_rng(args.seed + 1)
    n = srv.sess.num_vertices
    tickets = []
    for i in range(args.queries):
        prog = SCORE_PROGRAMS[i % len(SCORE_PROGRAMS)]
        verts = rng.integers(0, n, 4)
        tickets.append((srv.submit("score", program=prog, vertices=verts),
                        "score", prog, verts))
    for v in rng.integers(0, n, 4):
        tickets.append((srv.submit("owner", vertices=[v]), "owner", None,
                        [v]))
        tickets.append((srv.submit("neighbors", vertices=[v]),
                        "neighbors", None, [v]))
    t0 = time.perf_counter()
    served = srv.serve_pending()
    dt = time.perf_counter() - t0
    replies = {t: srv.result(t) for t, *_ in tickets}
    assert all(r is not None and r.error is None
               for r in replies.values()), "serve loop dropped a request"
    if check:
        # every score reply must bit-match a direct run_many with the
        # SAME (combine, dtype) wire-cell grouping the server fuses —
        # the server only batches/caches, it never changes the compute
        from repro.session import resolve_program
        cells: dict = {}
        for p in SCORE_PROGRAMS:
            prog = resolve_program(p, n)
            cells.setdefault((prog.combine, np.dtype(prog.dtype).name),
                             []).append(p)
        direct = {}
        for progs in cells.values():
            if args.tol is None:
                outs = srv.sess.run_many(progs, iters=args.iters,
                                         exchange=args.exchange)
            else:
                # same tol semantics as the server's step: cold seeds,
                # iters as a cap — bit-match still holds exactly
                outs, _ = srv.sess.run_many(
                    progs, iters=args.iters, exchange=args.exchange,
                    tol=args.tol,
                    init_values=[np.zeros(0)] * len(progs),
                    return_iters=True)
            direct.update(zip(progs, outs))
        for t, kind, prog, verts in tickets:
            if kind == "score":
                want = direct[prog][np.asarray(verts)]
                got = replies[t].value
                assert np.array_equal(got, want), (prog, got, want)
        print(f"[serve] {args.queries} score replies bit-match direct "
              f"run_many ({args.exchange} wire)")
    return {"served": served, "query_ms": dt * 1e3 / max(served, 1),
            "microbatches": srv.stats["microbatches"]}


def drive_ingest(srv: GraphServer, args) -> dict:
    """Stream random edge arrivals until ``--ingest-windows`` windows
    have flushed; return the RF drift/repair summary."""
    rng = np.random.default_rng(args.seed + 2)
    n = srv.sess.num_vertices
    target = srv.stats["windows"] + args.ingest_windows
    while srv.stats["windows"] < target:
        chunk = max(1, args.window // 4)
        srv.ingest(rng.integers(0, n, chunk), rng.integers(0, n, chunk))
    drifted = [v for e, v in srv.rf_trace if e == "window"]
    repaired = [v for e, v in srv.rf_trace if e == "restream"]
    return {"rf_base": srv.rf_trace[0][1],
            "rf_drifted": max(drifted) if drifted else srv.rf_base,
            "rf_post_restream": repaired[-1] if repaired else None,
            "restreams": srv.stats["restreams"],
            "ingested_edges": srv.stats["ingested_edges"]}


def drive_warm_cold(srv: GraphServer, args, check: bool) -> list[dict]:
    """Post-ingest warm-vs-cold comparison (``--tol`` mode only).

    The restream swap flushed the value caches and seeded ``_warm`` with
    the pre-swap fixed points.  This runs the SAME query mix twice over
    the grown graph: once **cold** (warm seeds stashed away — the
    all-False warm mask takes every program back to its init) and once
    **warm** (seeds restored).  Both rounds reuse the while_loop compiled
    during the pre-ingest queries, so ``query_ms`` compares fairly; the
    smoke gate requires the warm round to run strictly fewer iterations
    AND strictly less wall-clock per query than cold."""
    n = srv.sess.num_vertices

    def round_(warm: bool) -> tuple[dict, dict]:
        rng = np.random.default_rng(args.seed + 3)   # same mix both ways
        srv.last_iters_run.clear()
        tickets = []
        for i in range(args.queries):
            prog = SCORE_PROGRAMS[i % len(SCORE_PROGRAMS)]
            verts = rng.integers(0, n, 4)
            tickets.append(
                (srv.submit("score", program=prog, vertices=verts),
                 prog, verts))
        t0 = time.perf_counter()
        served = srv.serve_pending()
        dt = time.perf_counter() - t0
        replies = {t: srv.result(t) for t, *_ in tickets}
        assert all(r is not None and r.error is None
                   for r in replies.values()), "serve loop dropped a request"
        row = {"warm": warm,
               "query_ms": round(dt * 1e3 / max(served, 1), 3),
               "iters_run": max(srv.last_iters_run.values())}
        return row, [(replies[t], p, v) for t, p, v in tickets]

    stash = dict(srv._warm)
    srv._warm.clear()
    srv._values.clear()
    cold, _ = round_(warm=False)
    srv._warm.update(stash)
    srv._values.clear()          # force the warm round to recompute
    warm, warm_replies = round_(warm=True)
    print(f"[serve] post-ingest cold: {cold['iters_run']} iters "
          f"{cold['query_ms']}ms/q — warm: {warm['iters_run']} iters "
          f"{warm['query_ms']}ms/q")
    if check:
        # warm replies must still bit-match a direct run_many with the
        # same tol and the same warm seeds — warm start changes where
        # the loop starts, never what the server computes
        from repro.session import resolve_program
        cells: dict = {}
        for p in SCORE_PROGRAMS:
            prog = resolve_program(p, n)
            cells.setdefault((prog.combine, np.dtype(prog.dtype).name),
                             []).append(p)
        direct = {}
        for progs in cells.values():
            seeds = [stash.get((p, args.exchange), np.zeros(0))
                     for p in progs]
            outs, _ = srv.sess.run_many(
                progs, iters=args.iters, exchange=args.exchange,
                tol=args.tol, init_values=seeds, return_iters=True)
            direct.update(zip(progs, outs))
        for reply, prog, verts in warm_replies:
            want = direct[prog][np.asarray(verts)]
            assert np.array_equal(reply.value, want), (prog, reply.value,
                                                       want)
        assert warm["iters_run"] < cold["iters_run"], (
            f"warm start ran {warm['iters_run']} iters, cold "
            f"{cold['iters_run']} — no repair win")
        assert warm["query_ms"] < cold["query_ms"], (
            f"warm query_ms {warm['query_ms']} not below cold "
            f"{cold['query_ms']}")
        print(f"[serve] warm replies bit-match direct run_many; "
              f"warm {warm['iters_run']} < cold {cold['iters_run']} "
              f"iters and faster per query")
    return [cold, warm]


def child_snapshot(args) -> None:
    """The preemption victim: build the deterministic server, serve one
    microbatch, checkpoint, then SIGKILL this very process — nothing
    after the kill runs, so only the atomic snapshot survives."""
    ft = ServiceFT(args.ckpt_dir)
    srv = build_server(args, ft=ft)
    srv.submit("score", program="pagerank", vertices=[0, 1])
    srv.step()
    srv.checkpoint()
    ft.wait()
    print("[serve-child] snapshot written, dying", flush=True)
    os.kill(os.getpid(), signal.SIGKILL)


def run_snapshot_child(args) -> None:
    """Run the preemption victim to its death and verify it died by
    SIGKILL.  Called before this process initialises a backend, so the
    child can have the accelerator to itself."""
    cmd = [sys.executable, "-m", "repro.launch.serve_graph",
           "--child-snapshot", "--ckpt-dir", args.ckpt_dir,
           "--scale", str(args.scale), "--k", str(args.k),
           "--exchange", args.exchange, "--backend", args.backend,
           "--iters", str(args.iters), "--seed", str(args.seed),
           "--window", str(args.window)]
    if args.tol is not None:
        cmd += ["--tol", str(args.tol)]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    assert proc.returncode == -signal.SIGKILL, (
        f"child expected to die by SIGKILL, got {proc.returncode}:\n"
        f"{proc.stdout}{proc.stderr}")


def resume_check(args) -> None:
    """Resume from the dead child's snapshot and assert the partition
    state is identical to the deterministic reference."""
    ref = build_server(args)
    srv = GraphServer.resume(ServiceFT(args.ckpt_dir), tol=args.tol)
    assert srv.sess.to_json() == ref.sess.to_json(), "config blob drifted"
    assert np.array_equal(srv.sess.assign, ref.sess.assign), \
        "resumed assignment differs from the pre-kill partition"
    ta = srv.submit("score", program="pagerank", vertices=[0, 1])
    srv.step()
    tb = ref.submit("score", program="pagerank", vertices=[0, 1])
    ref.step()
    assert np.array_equal(srv.result(ta).value, ref.result(tb).value)
    print("[serve] SIGKILL'd child resumed from snapshot: identical "
          "config, assignment, and replies")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=int, default=13)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--exchange", default="halo")
    ap.add_argument("--backend", default="np")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--tol", type=float, default=None,
                    help="convergence early-exit tolerance: --iters "
                         "becomes a cap, the server's value caches turn "
                         "into warm-start seeds across ingest swaps, and "
                         "BENCH_serve.json gains post-ingest cold/warm "
                         "rows (query_ms, iters_run)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--window", type=int, default=2048)
    ap.add_argument("--ingest-windows", type=int, default=3)
    ap.add_argument("--watermark", type=float, default=1.02)
    ap.add_argument("--restream-passes", type=int, default=2)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="assert correctness gates (CI mode)")
    ap.add_argument("--child-snapshot", action="store_true",
                    help=argparse.SUPPRESS)   # internal: preemption victim
    ap.add_argument("--out", default=None,
                    help="override results/BENCH_serve.json")
    args = ap.parse_args()
    enable_compile_cache()

    if args.child_snapshot:
        child_snapshot(args)
        return 0                    # unreachable — SIGKILL above

    preempt = bool(args.ckpt_dir and args.smoke)
    if preempt:
        run_snapshot_child(args)
    srv = build_server(args)
    q = drive_queries(srv, args, check=args.smoke)
    ing = drive_ingest(srv, args)
    wc = (drive_warm_cold(srv, args, check=args.smoke)
          if args.tol is not None else [])
    if args.smoke:
        assert ing["restreams"] >= 1, (
            f"RF watermark never tripped: trace {srv.rf_trace}")
        assert ing["rf_post_restream"] <= ing["rf_drifted"] + 1e-9, ing
        # the grown graph still serves
        t = srv.submit("score", program="pagerank", vertices=[0])
        srv.step()
        assert srv.result(t).error is None
        print(f"[serve] drift {ing['rf_drifted']:.3f} repaired to "
              f"{ing['rf_post_restream']:.3f} over {ing['restreams']} "
              f"restream(s)")
    if preempt:
        resume_check(args)

    row = {"bench": "serve", "scale": args.scale, "k": args.k,
           "exchange": args.exchange, "window": args.window,
           "queries": q["served"], "microbatches": q["microbatches"],
           "query_ms": round(q["query_ms"], 3),
           "rf_base": round(ing["rf_base"], 4),
           "rf_drifted": round(ing["rf_drifted"], 4),
           "rf_post_restream": round(ing["rf_post_restream"], 4)
           if ing["rf_post_restream"] is not None else None,
           "restreams": ing["restreams"],
           "ingested_edges": ing["ingested_edges"],
           # compilations under the server's steps and flushes
           "compiles": srv.stats["compiles"]}
    rows = [row]
    if args.tol is not None:
        # pre-ingest row + one post-ingest row per temperature; the
        # warm/tol identity columns keep trend.py from diffing a warm
        # row against a cold one
        row.update({"tol": args.tol, "warm": False})
        for r in wc:
            rows.append({"bench": "serve_post_ingest", "scale": args.scale,
                         "k": args.k, "exchange": args.exchange,
                         "window": args.window, "tol": args.tol,
                         "warm": r["warm"], "iters_cap": args.iters,
                         "iters_run": r["iters_run"],
                         "query_ms": r["query_ms"]})
    out = (Path(args.out) if args.out else
           Path(__file__).resolve().parents[3] / "results"
           / "BENCH_serve.json")
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(rows, indent=1))
    for r in rows:
        print(",".join(f"{k}={v}" for k, v in r.items()))
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

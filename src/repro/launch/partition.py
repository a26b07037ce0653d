"""Graph-partitioning launcher — a thin client of the GraphSession façade.

``python -m repro.launch.partition --scale 13 --k 16 --algo clugp-opt``
partitions a synthetic web crawl and reports RF / balance / runtime, then
(optionally) runs distributed PageRank on the result via the session's
GAS engine (--pagerank).

``--backend {np,jit,sharded}`` picks the partitioner strategy
(repro.core.partitioner): the host oracle, the single-device fused jit
pipeline, or the §III-C stream-sharded shard_map pipeline over ``--nodes``
devices.  ``--restream N`` adds N prioritized-restream passes.  jax must
see enough devices for the sharded backend, so the arg parse happens
BEFORE any jax import and sets XLA_FLAGS itself; after jax initializes,
the requested ``--nodes`` is validated against the realizable device
count so a mismatch fails with a clear message instead of a shard_map
shape error deep inside jax.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=13)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--algo", default="clugp-opt",
                    choices=["clugp", "clugp-opt", "clugp-parallel",
                             "hashing", "dbh", "greedy", "hdrf", "mint"])
    ap.add_argument("--backend", default="np",
                    choices=["np", "jit", "sharded"],
                    help="partitioner implementation for clugp algos")
    ap.add_argument("--nodes", type=int, default=4,
                    help="stream-split width: sharded mesh size / "
                         "clugp-parallel node count")
    ap.add_argument("--restream", type=int, default=0,
                    help="extra prioritized-restream passes")
    ap.add_argument("--unroll", type=int, default=1,
                    help="clustering inner-scan unroll (device backends)")
    ap.add_argument("--graph", default="web", choices=["web", "social"])
    ap.add_argument("--pagerank", action="store_true")
    ap.add_argument("--exchange", default="halo",
                    choices=["dense", "halo", "quantized"],
                    help="mirror-sync wire format for --pagerank")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def validate_nodes(args) -> None:
    """Fail fast (and clearly) when the requested stream-split width is
    not realizable as XLA devices — without this, the mismatch surfaces
    as a shard_map sharding/shape error deep inside jax.  Must run after
    the XLA_FLAGS setup and the first jax import."""
    import jax

    if args.nodes < 1:
        sys.exit(f"error: --nodes must be >= 1, got {args.nodes}")
    if args.backend != "sharded":
        return
    have = jax.device_count()
    if have < args.nodes:
        plat = jax.default_backend()
        hint = (
            "XLA_FLAGS=--xla_force_host_platform_device_count=N only "
            "creates virtual CPU devices; on "
            f"'{plat}' the device count is fixed by the hardware"
            if plat != "cpu" else
            "the device count locked at the first jax import — make sure "
            "nothing imported jax before this launcher set XLA_FLAGS")
        sys.exit(
            f"error: --backend sharded --nodes {args.nodes} needs "
            f"{args.nodes} XLA devices but only {have} "
            f"{'is' if have == 1 else 'are'} realizable on platform "
            f"'{plat}' ({hint})")


def session_for(args, g):
    """Build the (serializable) session this invocation describes and run
    the partition strategy on the graph.  Baseline algos adopt their
    assignment into the same session type, so the downstream layout /
    engine / comm accounting is identical for every algo."""
    import numpy as np

    from repro.core import CLUGPConfig, baselines, random_stream
    from repro.session import GraphSession, SessionConfig

    algo, k, seed = args.algo, args.k, args.seed
    if algo.startswith("clugp"):
        cfg = (CLUGPConfig.optimized(k) if algo == "clugp-opt"
               else CLUGPConfig.paper(k))
        cfg = dataclasses.replace(cfg, restream=args.restream,
                                  unroll=args.unroll)
        # --nodes drives the stream split for the sharded backend and for
        # the legacy clugp-parallel alias (np multi-node combine)
        nodes = (1 if args.backend == "np" and algo != "clugp-parallel"
                 else args.nodes)
        sess = GraphSession(SessionConfig(
            clugp=cfg, backend=args.backend, nodes=nodes,
            exchange=args.exchange))
        return sess.partition(g.src, g.dst, g.num_vertices)
    gr = random_stream(g, seed=seed)
    a = baselines.ALL_BASELINES[algo](gr.src, gr.dst, g.num_vertices, k)
    # map back to the original stream order for downstream use
    out = np.zeros_like(a)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(g.num_edges)
    out[perm] = a
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig(k=k),
                                      exchange=args.exchange))
    return sess.with_partition(g.src, g.dst, g.num_vertices, out)


def main():
    args = build_parser().parse_args()
    if args.backend == "sharded":
        # must land before the first jax import — the device count locks
        # then.  An existing flag with a smaller count is raised to
        # --nodes (jax hasn't initialized yet, so overriding is safe).
        import re

        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                      flags)
        if m is None or int(m.group(1)) < args.nodes:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", "", flags)
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={args.nodes}")

    import numpy as np

    from repro.core import web_graph
    from repro.core.graphgen import social_graph
    from repro.launch.compile_cache import enable_compile_cache

    validate_nodes(args)
    enable_compile_cache()

    g = (web_graph(scale=args.scale, seed=args.seed) if args.graph == "web"
         else social_graph(n=1 << args.scale, seed=args.seed))
    print(f"graph: V={g.num_vertices} E={g.num_edges}")
    t0 = time.time()
    sess = session_for(args, g)
    dt = time.time() - t0
    label = args.algo if not args.algo.startswith("clugp") \
        else f"{args.algo}[{args.backend}, restream={args.restream}]"
    print(f"{label}: rf={sess.stats['rf']:.3f} "
          f"balance={sess.stats['balance']:.3f} "
          f"time={dt:.2f}s ({1e6*dt/g.num_edges:.2f} µs/edge)")

    if args.pagerank:
        from repro.graph import reference_pagerank
        sess.layout()
        st = sess.partition_layout.interior_frontier_stats()
        print(f"interior/frontier: frac={st['interior_frac']:.3f} "
              f"min={st['interior_frac_min']:.3f} "
              f"(overlap headroom — interior rows compute during the "
              f"ring hops)")
        t0 = time.time()
        pr = sess.run("pagerank", iters=30)
        dt = time.time() - t0
        ref = reference_pagerank(g.src, g.dst, g.num_vertices, iters=30)
        cb = sess.comm_bytes()
        print(f"pagerank[{args.exchange}]: {dt:.2f}s  "
              f"max|err|={np.abs(pr-ref).max():.2e}  "
              f"comm/iter: ideal={cb['ideal']/1e6:.2f}MB "
              f"quantized={cb['quantized']/1e6:.2f}MB "
              f"halo={cb['halo']/1e6:.2f}MB "
              f"dense-gather={cb['dense_gather']/1e6:.2f}MB "
              f"allreduce={cb['allreduce']/1e6:.2f}MB")


if __name__ == "__main__":
    main()

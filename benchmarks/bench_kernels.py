"""Partitioner kernel micro-benchmarks (interpret-mode correctness +
host-CPU μs; device times come from the chip benchmark in bench/)."""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def _time(fn, *args, reps=3):
    fn(*args)                          # compile/warm
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps


def kernels_microbench():
    rows = []
    rng = np.random.default_rng(0)
    aff = jnp.asarray(rng.random((1024, 256)), jnp.float32)
    sizes = jnp.asarray(rng.integers(1, 50, 1024), jnp.float32)
    rt = jnp.asarray(np.asarray(aff).sum(1), jnp.float32)
    cur = jnp.asarray(rng.integers(0, 256, 1024), jnp.int32)
    loads = jnp.asarray(rng.random(256) * 100, jnp.float32)
    t_kern = _time(lambda *a: ops.game_best_response(*a, lam=2.0,
                                                     interpret=True),
                   aff, sizes, rt, cur, loads)
    t_ref = _time(lambda *a: ref.game_bestresponse_ref(*a, lam=2.0),
                  aff, sizes, rt, cur, loads)
    rows.append({"bench": "kernel_game_br", "us_kernel_interp":
                 round(1e6 * t_kern, 1), "us_ref": round(1e6 * t_ref, 1)})

    # cluster-scatter: the clustering inner loop on the Pallas fused
    # table-update kernel (interpret mode off-TPU) vs the XLA
    # fused-scatter scan — bit-identical outputs by construction (both
    # compose edge_decisions), so the cells differ only in µs/edge.
    # "kernel" is the trend identity field keying the two cells.
    from functools import partial

    from repro.core.clustering import streaming_clustering_jax

    E, V = 4096, 1024
    src = jnp.asarray(rng.integers(0, V, E), jnp.int32)
    dst = jnp.asarray(rng.integers(0, V, E), jnp.int32)
    outs = {}
    for kernel in ("xla", "pallas"):
        fn = jax.jit(partial(streaming_clustering_jax, num_vertices=V,
                             vmax=64.0, id_cap=2 * V, kernel=kernel))
        t = _time(fn, src, dst)
        outs[kernel] = [np.asarray(o) for o in fn(src, dst)]
        rows.append({"bench": "kernel_cluster_scatter", "kernel": kernel,
                     "us_per_edge": round(1e6 * t / E, 3)})
    assert all(np.array_equal(a, b) for a, b in
               zip(outs["xla"], outs["pallas"])), \
        "cluster_scatter kernels diverged"
    return rows

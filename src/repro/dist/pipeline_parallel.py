"""GPipe-style pipeline parallelism over a named "stage" mesh axis.

``pipeline_apply`` shards stacked per-stage parameters (leading dim = S
stages) across the axis and streams M microbatches through the ring with
``ppermute``: tick t has stage s working on microbatch t−s, so the
pipeline fills in S−1 ticks and drains in S−1 — M+S−1 ticks total versus
M·S sequential.  ``reference_apply`` is the single-device oracle.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def reference_apply(stacked_params, xs, fn):
    """Sequentially run every microbatch through all stages.

    stacked_params: pytree with leading stage dim S; xs: (M, mb, ...);
    fn(x, stage_params) → x.  Returns (M, mb, ...).
    """
    def one(x):
        def step(carry, p):
            return fn(carry, p), None
        y, _ = jax.lax.scan(step, x, stacked_params)
        return y

    return jax.vmap(one)(xs)


def pipeline_apply(mesh, axis: str, stacked_params, xs, fn):
    """Run ``fn`` as an S-stage pipeline on ``mesh[axis]``.

    stacked_params leaves have leading dim S == mesh.shape[axis] and are
    sharded one stage per device; xs (M, mb, ...) microbatches are
    replicated (stage 0 consumes them in order).  Returns the (M, mb, ...)
    outputs of the last stage, replicated.
    """
    S = mesh.shape[axis]
    M = xs.shape[0]
    ticks = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    def local(p_local, xs_all):
        p_local = jax.tree_util.tree_map(lambda a: a[0], p_local)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs_all.shape[1:]
        state0 = jnp.zeros(mb_shape, xs_all.dtype)
        out0 = jnp.zeros((M,) + mb_shape, xs_all.dtype)

        def tick(carry, t):
            state, outputs = carry
            # stage s receives stage s−1's previous output; stage 0 feeds
            # the next microbatch (clipped reads are never committed)
            prev = jax.lax.ppermute(state, axis, perm)
            fresh = jax.lax.dynamic_index_in_dim(
                xs_all, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            x_in = jnp.where(stage == 0, fresh, prev)
            out = fn(x_in, p_local)
            mb = t - (S - 1)
            write = (stage == S - 1) & (mb >= 0)
            mb_c = jnp.clip(mb, 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, mb_c, 0,
                                               keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, out, cur), mb_c, 0)
            return (out, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (state0, out0), jnp.arange(ticks))
        # only the last stage wrote; psum replicates its buffer
        return jax.lax.psum(outputs, axis)

    fn_sharded = partial(
        jax.shard_map, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False)(local)
    return fn_sharded(stacked_params, xs)

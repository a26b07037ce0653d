"""Ahead-of-time compiles for a described TPU v5e — no chip needed.

The TPU compiler ships with jaxlib's TPU library and compiles for a chip
that is described, not attached.  These tests hold the main path's Pallas
kernels and the jitted partitioner body to what the chip's compiler
accepts (Mosaic rejects layouts and memory spaces that interpret mode
runs happily).  The topology is described inside a module fixture, never
at import: only one process at a time may load the TPU library, and the
worker that runs this file keeps it until it exits.
"""
import dataclasses
import os

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        # the library logs under /tmp unless told otherwise
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "can't"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_cluster_scatter_compiles_to_mosaic(one_chip):
    from repro.kernels.cluster_scatter import cluster_scatter
    B = 128

    def f(ints, buf, scal, vmax):
        return cluster_scatter(ints, buf, scal, vmax, interpret=False)

    text = jax.jit(f).lower(
        _spec((B, 3), jnp.int32, one_chip),
        _spec((10 * B,), jnp.int32, one_chip),
        _spec((4,), jnp.int32, one_chip),
        _spec((), jnp.float32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


def test_game_bestresponse_compiles_to_mosaic(one_chip):
    from repro.kernels.game_bestresponse import game_bestresponse
    M, kpad, k = 4096, 128, 16

    def f(aff, sizes, row_tot, cur, loads, lam):
        return game_bestresponse(aff, sizes, row_tot, cur, loads, lam=lam,
                                 k=k, interpret=False)

    text = jax.jit(f).lower(
        _spec((M, kpad), jnp.float32, one_chip),
        _spec((M,), jnp.float32, one_chip),
        _spec((M,), jnp.float32, one_chip),
        _spec((M,), jnp.int32, one_chip),
        _spec((kpad,), jnp.float32, one_chip),
        _spec((), jnp.float32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


def test_greedy_transform_compiles_to_mosaic(one_chip):
    from repro.kernels.greedy_transform import greedy_transform
    E, k = 6 * 1024 + 5, 16

    def f(*cols):
        return greedy_transform(*cols, k=k, interpret=False)

    edges = [_spec((E,), jnp.int32, one_chip)] * 7
    text = jax.jit(f).lower(
        *edges, _spec((), jnp.float32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


def test_jit_partitioner_body_compiles_with_pallas_kernels(one_chip):
    """The whole jit strategy lowered for the TPU from this CPU process:
    the kernels' default mode picks Mosaic by the platform the call is
    lowered for, so the clustering, game and transform kernels all
    appear as custom calls."""
    from repro.core import CLUGPConfig
    from repro.core.partitioner import _init_caps, _jit_body
    V, E, k = 2048, 8192, 16
    cfg = dataclasses.replace(CLUGPConfig.optimized(k), kernel="pallas",
                              cluster_kernel="pallas")
    caps = _init_caps(V, E)
    edges = _spec((E,), jnp.int32, one_chip)
    compiled = _jit_body.lower(
        edges, edges, num_vertices=V, cfg=cfg, vmax=float(E / k),
        game_mode="pallas", id_cap=caps.id_cap, m_cap=caps.m_cap,
        nnz_cap=caps.nnz_cap).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2 ** 30

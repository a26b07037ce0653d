"""Pallas TPU kernels of the partitioner (Mosaic on TPU, the Pallas
interpreter elsewhere) + jnp oracles."""
from . import ops, ref  # noqa: F401
from .ops import game_best_response, cluster_scatter  # noqa: F401

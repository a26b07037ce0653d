"""Pallas TPU kernels (Mosaic on TPU, the Pallas interpreter elsewhere) +
jnp oracles."""
from . import ops, ref  # noqa: F401
from .ops import (flash_attention, game_best_response, ell_spmv,  # noqa: F401
                  cluster_scatter)

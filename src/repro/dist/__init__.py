"""Distribution substrate: named-axis sharding rules, sequence-parallel
decode, error-feedback gradient compression, fault-tolerant training loop,
and pipeline parallelism.

This package is the single place device meshes touch model code: models
tag arrays with logical axis names (``shard(x, "batch", "seq", ...)``)
and the active rule table (``use_rules``) maps tags onto mesh axes.
Everything runs on CPU under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the same code
path the production pod meshes lower through.
"""
from . import collectives  # noqa: F401  (axis-wide reduction helpers)
from .halo import (DenseExchange, HaloExchange,  # noqa: F401
                   QuantizedHaloExchange, get_exchange)
from .sharding import (CP_SERVE_RULES, MULTI_POD_RULES,  # noqa: F401
                       SINGLE_POD_RULES, shard, use_rules)

"""Distribution layer of the graph system: the mirror exchange's wire
formats (``halo``: dense / halo / int8-quantized / ragged-ring / top-Δ
ragged-quantized), the axis-wide reduction helpers (``collectives``),
the row quantizer the lossy wires use (``compress``), the partitioner's
sharding rules (``sharding``) and the graph service's checkpoint and
straggler watch (``ft``).

Collectives go through this package: the partitioner, the GAS engine
and the exchange never call ``jax.lax`` collectives directly.
Everything runs on CPU under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the same code
path a TPU mesh lowers through.
"""
from . import collectives  # noqa: F401  (axis-wide reduction helpers)
from .halo import (DenseExchange, HaloExchange,  # noqa: F401
                   QuantizedHaloExchange, get_exchange)

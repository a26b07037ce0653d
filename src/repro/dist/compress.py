"""Row quantizer for the lossy exchange wires.

``quantize_rows(x)`` / ``dequantize_rows(codes, scales)``: int8 codes
plus one max-abs scale per trailing row — the lane-group quantizer the
halo exchange's quantized wire formats (``repro.dist.halo``) use to cut
the per-mirror payload, the bytes the partitioner's replication factor
does not already remove.
"""
from __future__ import annotations

import jax.numpy as jnp

_QMAX = 127.0


def quantize_rows(x: jnp.ndarray, qmax: float = _QMAX):
    """Max-abs int8 quantization per trailing row: ``x`` (..., n) →
    (codes int8 (..., n), scales f32 (...)).  Each leading index gets its
    own scale — for the halo exchange these rows are per-destination lane
    groups, so one hot lane can't wash out another destination's
    precision.  All-zero rows take scale 1 so dequantization is exact."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scales = jnp.where(amax > 0, amax / qmax, 1.0)
    codes = jnp.clip(jnp.round(xf / scales[..., None]),
                     -qmax, qmax).astype(jnp.int8)
    return codes, scales


def dequantize_rows(codes: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``quantize_rows``: (..., n) int8 codes × (...) scales →
    (..., n) f32.  Exact for the codes produced by ``quantize_rows`` (the
    round-trip error lives in the encoder's residual, not here)."""
    return codes.astype(jnp.float32) * scales[..., None]

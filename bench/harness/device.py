"""The chip a run is given: the gate, what the result line says of it,
and JAX's persistent compilation cache."""
from __future__ import annotations

import json
import os
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]      # the checkout
PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def gate(chips: int) -> list:
    """The first ``chips`` TPU devices, or ``NoChip``.  There is no CPU
    fallback: a number measured on the CPU is not a chip's."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def peaks(kind: str) -> dict:
    """Published peaks of the device kind; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}")
    return table[kind]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    if set, else at ``<checkout>/.jax_cache``: a fixed path, so the next
    run in this checkout finds every program the first one compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


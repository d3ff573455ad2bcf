"""The chip: refusal of anything else, the peak table, the compile cache,
and a meter of compilations."""
from __future__ import annotations

import collections
import json
import os
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
#: the persistent compilation cache when JAX_COMPILATION_CACHE_DIR is unset
CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


class NoChip(RuntimeError):
    """The run cannot be measured here."""


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise NoChip(f"device kind {device_kind!r} is not in {PEAKS_FILE.name}"
                     f" ({sorted(table)}); add its published peaks first")
    return table[device_kind]


def require_chip(jax, chips: int) -> dict:
    """Platform, kind and count of the TPU chips JAX sees; raises on any
    other backend, an unknown kind, or fewer chips than ``chips``."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {dev.platform!r} "
                     f"({dev.device_kind})")
    peaks(dev.device_kind)
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def use_compile_cache(jax) -> str:
    """Keep every compiled program in the persistent cache: the directory
    JAX_COMPILATION_CACHE_DIR names, else the fixed one in the checkout."""
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


class CompileMeter:
    """Seconds JAX spends tracing, lowering and compiling or loading
    programs, and persistent-cache hits and misses (a miss is an XLA
    compilation), from ``jax.monitoring`` events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.counts = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, name, **_):
        self.counts[name] += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds,
                "cache_hits": self.counts["/jax/compilation_cache/cache_hits"],
                "cache_misses":
                    self.counts["/jax/compilation_cache/cache_misses"]}

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}

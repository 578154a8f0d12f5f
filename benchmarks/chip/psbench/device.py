"""The device side of a run: the chip check, the compile cache, the table
of peaks, compile counting and the memory peak.

Nothing here falls back to the CPU: a run without a TPU stops before it
builds anything.
"""
from __future__ import annotations

import os
from importlib import metadata
from pathlib import Path

#: Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB
#: of HBM at 819 GB/s per chip).
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9},
}


class DeviceError(RuntimeError):
    """The run found no TPU, too few chips, or a chip of unknown peaks."""


def peaks_for(device_kind: str) -> dict:
    """Peaks of ``device_kind``; an unknown kind is an error, not a
    default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise DeviceError(f"no peaks for device kind {device_kind!r}; "
                          f"known: {sorted(PEAKS)}") from None


def configure_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache``. Every program is
    cached, however short its compile, so a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_tpu(chips: int, devices=None) -> list:
    """The first ``chips`` TPU devices, or ``DeviceError``."""
    import jax

    devices = jax.devices() if devices is None else devices
    platform = devices[0].platform
    if platform != "tpu":
        raise DeviceError(f"needs a TPU, JAX found {platform!r}")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")
    peaks_for(devices[0].device_kind)
    return list(devices[:chips])


def versions() -> str:
    """Installed versions of the packages a chip run depends on."""
    out = []
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            out.append(f"{pkg}={metadata.version(pkg)}")
        except metadata.PackageNotFoundError:
            out.append(f"{pkg}=absent")
    return " ".join(out)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no statistics)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and how many
    programs it traced, read from ``jax.monitoring`` while registered.
    (Copied from the repository's chip smoke run, with a count added.)"""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    TRACE = EVENTS[0]
    HIT, MISS = ("/jax/compilation_cache/cache_hits",
                 "/jax/compilation_cache/cache_misses")

    def __init__(self):
        self.seconds = 0.0
        self.traces = 0
        self.cache_hits = self.cache_misses = 0

    def _listen(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            self.traces += event == self.TRACE

    def _count(self, event, **_):
        self.cache_hits += event == self.HIT
        self.cache_misses += event == self.MISS

    def __str__(self):
        return (f"compile_s={self.seconds:.3f} traces={self.traces} "
                f"cache_hits={self.cache_hits} "
                f"cache_misses={self.cache_misses}")

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        jax.monitoring.register_event_listener(self._count)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)
        jax.monitoring.unregister_event_listener(self._count)

"""Process set-up shared by every cell: the compilation cache, the device
check, and the count of compiles."""
from __future__ import annotations

import os
import pathlib

import jax

ROOT = pathlib.Path(__file__).resolve().parents[2]
# one fixed directory inside the checkout: the cache's path is part of its
# key, so a directory that moved would never hit
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: JAX_COMPILATION_CACHE_DIR when
    the environment sets it, else `<checkout>/.jax_cache`. Every program is
    cached, however fast it compiled, so a second run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_devices(chips: int) -> list:
    """The devices a cell runs on. Raises NoChip without a TPU (never falls
    back to the CPU) or with fewer chips than asked for."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def describe(devs, peak_bytes=None) -> dict:
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if peak_bytes is not None:
        out["memory_peak_bytes"] = peak_bytes
    return out


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Counts compilations (persistent-cache hits included) while active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.active = False
        self.seconds = 0.0    # compiling or loading from the cache, always
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == self.EVENT:
            self.seconds += duration
            if self.active:
                self.count += 1


_COUNTER = None


def compile_counter() -> CompileCounter:
    """The process's one counter, reset to 0 and inactive."""
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    _COUNTER.count, _COUNTER.active, _COUNTER.seconds = 0, False, 0.0
    return _COUNTER


def profile_options():
    """Profiler options of a traced run: device and runtime events and the
    benchmark's annotations, without the Python function tracer (which
    multiplies the host's events and slows the host it measures)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts

"""The process around a run: the chip check, the compile cache, the count of
programs compiled or loaded, the process clock and the device record."""
from __future__ import annotations

import os
import time


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def process_start_monotonic() -> float:
    """``time.monotonic()`` reading of this process's start (Linux), so that
    ``setup_s`` counts interpreter start-up and imports too."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.monotonic() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return time.monotonic()


def require_tpu(chips: int):
    """Return the first ``chips`` TPU devices, or raise :class:`NoChip`."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:  # no backend could be initialised
        raise NoChip(str(e)) from e
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default backend is "
                     f"{devs[0].platform if devs else 'empty'}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: str) -> str:
    """Persistent compilation cache for every program, however quick its
    compile: ``$JAX_COMPILATION_CACHE_DIR`` where set, else the fixed
    ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


_SETUP_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileMonitor:
    """From ``jax.monitoring``: programs compiled or loaded (JAX's
    ``backend_compile`` event covers both), how many of those came from the
    persistent cache, jaxprs traced, and the seconds spent tracing, lowering
    and compiling or loading."""

    def __init__(self):
        self.compiles = 0
        self.loads = 0
        self.traces = 0
        self.seconds = 0.0

    def install(self) -> "CompileMonitor":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == _CACHE_READ:
            self.loads += 1
            return
        if event not in _SETUP_EVENTS:
            return
        self.seconds += duration
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
        elif event.endswith("jaxpr_trace_duration"):
            self.traces += 1

    def snapshot(self) -> tuple[int, int, int, float]:
        return self.compiles, self.loads, self.traces, self.seconds


def device_record(devs) -> dict:
    """The devices as JAX reports them, with the peak memory of the
    fullest one where the backend reports it."""
    d0 = devs[0]
    rec = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devs)}
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
             for d in devs]
    if max(peaks) >= 0:
        rec["memory_peak_bytes"] = max(peaks)
    return rec

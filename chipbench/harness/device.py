"""The chip: which one runs the cell, its peaks, and what JAX compiles."""
from __future__ import annotations

import jax

# device_kind -> published peaks of one chip. Source: Google Cloud
# documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s
# bf16 and 819 GB/s of HBM bandwidth per chip, 16 GB of HBM.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(kind: str) -> dict:
    """Peaks of ``kind``; a kind that is not in the table is an error."""
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}: add a row with "
                       f"its published source to chipbench/harness/device.py")
    return PEAKS[kind]


class NoChip(SystemExit):
    pass


def require_tpu(chips: int) -> dict:
    """The JAX device this cell runs on; exits non-zero unless JAX's first
    device is a TPU and at least ``chips`` of them are present."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"chipbench: no TPU (JAX's first device is "
                     f"{devs[0].platform!r}); nothing was measured")
    if len(devs) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} chips, JAX sees "
                     f"{len(devs)}; nothing was measured")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    out = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out


class CompileClock:
    """Seconds and events of tracing, lowering, compiling and persistent
    cache reads, from ``jax.monitoring``; ``window`` counts the compile
    and cache-read events while it is set."""

    PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read"}
    COUNTED = ("compile", "cache_read")

    def __init__(self):
        self.secs = dict.fromkeys(self.PHASES.values(), 0.0)
        self.window = False
        self.window_events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        phase = self.PHASES.get(event)
        if phase is None:
            return
        self.secs[phase] += float(duration)
        if self.window and phase in self.COUNTED:
            self.window_events += 1

"""The device a run measures: the chip check, the peaks table, the peak
HBM reading, and a clock of the compiles JAX makes."""

from __future__ import annotations

import os
from typing import Any, Dict

from bench.harness.spec import BENCH_DIR, load_json

PEAKS_FILE = os.path.join(BENCH_DIR, "peaks.json")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The devices of a run: ``chips`` TPUs, or :class:`NoChip`."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is on platform "
                     f"{devs[0].platform!r} ({devs[0].device_kind!r})")
    if len(devs) < chips:
        raise NoChip(f"cell asks for {chips} chips; JAX sees {len(devs)}")
    return devs[:chips]


def describe(devs) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(PEAKS_FILE)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"{PEAKS_FILE}; known: {sorted(table)}")
    return table[device_kind]


def peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs`` since process start."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


class CompileClock:
    """Counts JAX's traces and backend compiles (or persistent-cache
    loads) through ``jax.monitoring`` duration events (after
    ``chip_smoke.CompileClock``)."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        self.compiles = 0
        self.traces = 0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == self.COMPILE:
            self.compiles += 1
        elif event == self.TRACE:
            self.traces += 1

    def snapshot(self):
        return (self.compiles, self.traces)

    def install(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

"""What the harness hands a driver for one run, and the spans a traced
run records around the calls into the system's layers."""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import time
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Ctx:
    cell: Dict[str, Any]              # bench/cells/<cell>.json + name, chips
    config: Dict[str, Any]            # bench/configs/<config>.json + name
    seed: int
    seconds: float
    trace: bool
    t_start: float                    # perf_counter at process start
    work_dir: str                     # scratch inside the checkout
    devs: List[Any] = dataclasses.field(default_factory=list)
    clock: Any = None                 # device.CompileClock
    rate: Optional[float] = None      # serve: offered load override

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    def span(self, name: str):
        """A host span on the profiler's clock in traced runs; nothing
        otherwise, so untraced runs measure the system alone."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def elapsed(self) -> float:
        return time.perf_counter() - self.t_start

    def work(self, *parts: str) -> str:
        path = os.path.join(self.work_dir, *parts)
        os.makedirs(path, exist_ok=True)
        return path


def profile_options():
    """Profiler options: device ops and benchmark spans, no Python
    function tracing (it would swamp the host plane)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts

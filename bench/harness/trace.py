"""From a profiler trace to the numbers the per-layer metrics read.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler.trace`` wrote
and keeps three things, in a plain JSON-able form (``Events``):

  device  (name, start_ns, dur_ns) of every operation the chip ran
          ("XLA Ops" lines of each ``/device:TPU:n`` plane), named by
          :func:`op_name`
  modules (name, start_ns, dur_ns) of every program the chip ran
          ("XLA Modules" lines), named by :func:`module_name`
  host    (name, start_ns, dur_ns) of the benchmark's own host spans
          (``TraceAnnotation`` names starting ``bench.``)

The functions below turn that into busy and idle time over the traced
window, time by operation and by program name, and the idle gaps
labelled by the host span they fell in. Tests check it on a small trace
recorded on a TPU v5e and kept in ``bench/testdata``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Sequence, Tuple

Span = Tuple[str, int, int]          # name, start_ns, dur_ns
HOST_PREFIX = "bench."
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# control flow that holds other ops: left out of the ops' time
CONTAINERS = re.compile(r"^(while|conditional|call)$")


def op_name(text: str) -> str:
    """An op's name from the HLO text a TPU trace gives it: the
    instruction name without ``%`` and its numeric suffix
    (``%zo_matmul.1 = bf16[...] custom-call(...)`` -> ``zo_matmul``)."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def module_name(text: str) -> str:
    """A program's name without its fingerprint:
    ``jit_draft_spec(6306...)`` -> ``jit_draft_spec``."""
    return re.sub(r"\(\d+\)$", "", text)


@dataclasses.dataclass
class Events:
    window: Tuple[int, int]                      # traced window, ns
    n_devices: int
    device: List[List[Span]]                     # per device: ops
    modules: List[List[Span]]                    # per device: programs
    host: List[Span]

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        tup = lambda xs: [tuple(x) for x in xs]      # noqa: E731
        return cls(window=tuple(d["window"]), n_devices=d["n_devices"],
                   device=[tup(x) for x in d["device"]],
                   modules=[tup(x) for x in d["modules"]],
                   host=tup(d["host"]))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def read(cls, path: str) -> "Events":
        with open(path) as f:
            return cls.from_json(json.load(f))


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(xplane: str, window: Tuple[int, int]) -> Events:
    """Read the trace; keep events that overlap ``window`` (ns, on the
    trace's clock)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane)
    w0, w1 = window
    device, modules, host = [], [], []

    def keep(ev) -> bool:
        return ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0

    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            for name, dst, rename in ((OPS_LINE, device, op_name),
                                      (MODULES_LINE, modules, module_name)):
                evs = lines[name].events if name in lines else []
                dst.append(sorted(((rename(ev.name), int(ev.start_ns),
                                    int(ev.duration_ns))
                                   for ev in evs if keep(ev)),
                                  key=lambda e: e[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events
                            if ev.name.startswith(HOST_PREFIX) and keep(ev))
    return Events(window=(int(w0), int(w1)), n_devices=len(device),
                  device=device, modules=modules,
                  host=sorted(host, key=lambda e: e[1]))


# ---------------------------------------------------------------------------
# reduction


def union(spans: Sequence[Span], window: Tuple[int, int]
          ) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals of ``spans`` clipped to ``window``."""
    w0, w1 = window
    iv = sorted((max(s, w0), min(s + d, w1)) for _, s, d in spans
                if s < w1 and s + d > w0)
    out: List[List[int]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: Events) -> float:
    """Busy time of the traced window, averaged over the devices."""
    if not events.device:
        return 0.0
    tot = sum(sum(b - a for a, b in union(ops, events.window))
              for ops in events.device)
    return tot / len(events.device)


def window_ns(events: Events) -> int:
    return events.window[1] - events.window[0]


def time_by_name(spans_per_device: Sequence[Sequence[Span]],
                 pattern: str) -> Tuple[float, int]:
    """(summed ns, number of events) of spans whose name matches
    ``pattern`` (``re.search``), averaged over devices; control-flow
    containers are left out."""
    rx = re.compile(pattern)
    tot, n = 0, 0
    for spans in spans_per_device:
        for name, _, d in spans:
            if rx.search(name) and not CONTAINERS.match(name):
                tot += d
                n += 1
    k = max(len(spans_per_device), 1)
    return tot / k, n // k


def top_ops(events: Events, k: int = 10) -> List[List]:
    """The ``k`` operation names that took most device time, seconds
    (control-flow containers left out: their ops are counted)."""
    acc: Dict[str, int] = collections.Counter()
    for ops in events.device:
        for name, _, d in ops:
            if not CONTAINERS.match(name):
                acc[name] += d
    nd = max(len(events.device), 1)
    return [[name, ns / nd / 1e9] for name, ns in acc.most_common(k)]


def _host_label(host: Sequence[Span], starts: Sequence[int], t: int) -> str:
    """Innermost benchmark span that holds instant ``t``: of nested
    spans sorted by start, the last one begun by ``t`` that holds it."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        name, s, d = host[i]
        if t < s + d:
            return name
    return "outside-bench-spans"


def idle_gaps(events: Events) -> List[Tuple[str, int]]:
    """Every idle gap of device 0 in the window: (host label, ns)."""
    if not events.device:
        return []
    busy = union(events.device[0], events.window)
    edges = [events.window[0]] + [x for iv in busy for x in iv] \
        + [events.window[1]]
    starts = [s for _, s, _ in events.host]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((_host_label(events.host, starts, (a + b) // 2),
                         b - a))
    return gaps


def top_gaps(events: Events, k: int = 10) -> List[List]:
    """Idle time summed by the host span it fell in, the ``k`` largest:
    ``["<span> x<gaps>", seconds]``."""
    acc: Dict[str, List[int]] = {}
    for label, ns in idle_gaps(events):
        a = acc.setdefault(label, [0, 0])
        a[0] += ns
        a[1] += 1
    ranked = sorted(acc.items(), key=lambda kv: -kv[1][0])[:k]
    return [[f"{label} x{n}", ns / 1e9] for label, (ns, n) in ranked]


def summary(events: Events) -> dict:
    """What every traced run reports: busy and window seconds, and the
    breakdown the ledger keeps."""
    return {"busy_s": busy_ns(events) / 1e9,
            "window_s": window_ns(events) / 1e9,
            "breakdown": {"device_ops": top_ops(events),
                          "idle_gaps": top_gaps(events)}}

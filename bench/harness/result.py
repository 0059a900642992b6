"""The result line of a run, from a driver's record."""

from __future__ import annotations

import collections
import dataclasses
import os
from typing import Any, Dict, Optional

from bench.harness import trace as TR
from bench.harness.device import describe


@dataclasses.dataclass
class View:
    """What a per-layer metric's ``read(view)`` sees."""
    record: Dict[str, Any]            # the driver's record of the run
    events: Optional[TR.Events]       # the traced window
    peaks: Dict[str, float]           # bench/peaks.json for this device
    counts: Any                       # bench/counts/<config>.py
    cell: Dict[str, Any]
    config: Dict[str, Any]

    def device_s(self, pattern: str, modules: bool = False):
        """(seconds, events) of device ops (or programs) whose name
        matches ``pattern``, averaged over the chips."""
        spans = self.events.modules if modules else self.events.device
        ns, n = TR.time_by_name(spans, pattern)
        return ns / 1e9, n


def traced_events(ctx, record) -> TR.Events:
    """Load the trace of the window, bounded by the driver's window
    span, and keep its reduced events beside it."""
    from jax.profiler import ProfileData

    path = TR.find_xplane(record["trace_dir"])
    name = f"bench.{ctx.cell['driver']}.window"
    win = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == name:
                        win = (int(ev.start_ns),
                               int(ev.start_ns + ev.duration_ns))
    if win is None:
        raise RuntimeError(f"no {name} span in the trace {path}")
    events = TR.load(path, win)
    events.save(os.path.join(record["trace_dir"], "events.json"))
    mods = collections.Counter(n for m in events.modules for n, _, _ in m)
    ctx.log(f"trace: {events.n_devices} device(s), programs "
            f"{dict(mods.most_common(12))}")
    return events


def build(bench, ctx, record: Dict[str, Any],
          peaks: Dict[str, float]) -> Dict[str, Any]:
    cell = ctx.cell["name"]
    device = {**describe(ctx.devs), "memory_peak_bytes": record["peak_bytes"]}
    line: Dict[str, Any] = {"correct": record["check"].correct,
                            "attempted": int(record["attempted"]),
                            "failed": int(record["failed"])}
    if not ctx.trace:
        line["metrics"] = {
            m["name"]: {"value": record["e2e"][m["name"]], "unit": m["unit"]}
            for m in bench.end_to_end(cell)}
    else:
        events = traced_events(ctx, record)
        view = View(record, events, peaks, bench.counts(ctx.config["name"]),
                    ctx.cell, ctx.config)
        metrics = {}
        for m in bench.per_layer(cell):
            value = bench.metric(m["name"]).read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        s = TR.summary(events)
        device["busy_s"] = s["busy_s"]
        device["window_s"] = s["window_s"]
        line["breakdown"] = s["breakdown"]
    line["device"] = device
    line["check"] = record["check"].as_json()
    ctx.log(f"compiles, traces in the window: "
            f"{record.get('compiles_in_window')}")
    return line

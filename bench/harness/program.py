#!/usr/bin/env python3
"""What the program says about itself in a traced run: the scope path of
every device op, and the program's own host spans with their args.

    python3 bench/harness/program.py <trace_dir>

prints, for a traced run whose ``events.json`` (written by
``result.traced_events``) lies in ``trace_dir``, the device time by
innermost program scope and op, the program's spans, and the idle gaps
labelled by the innermost benchmark or program span.

Where the scope path lives: on a TPU v5e an op event's name is its HLO
instruction text without metadata, and its ``tf_op`` stat holds the
instruction's ``op_name`` metadata (for a fusion, its root's) with a
trailing ``:``. That stat sits on the event's metadata in the device
plane, which ``ProfileData`` does not expose, so :func:`scopes` reads
the ``.xplane.pb`` with a small protobuf wire decoder. Spans and their
args come through ``ProfileData``.

The scope and span names are the program's own (``repro.obs``). Where
the program has none, as a checkout from before them, :func:`load`
returns None and so does every reader built on it.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

from bench.harness import counts as C          # noqa: E402
from bench.harness import trace as TR          # noqa: E402

try:
    from repro import obs
except ImportError:                 # a program without its tracing layer
    obs = None

Op = Tuple[str, int, int, str]      # op name, start_ns, dur_ns, scope path
Span = Tuple[str, int, int, Dict[str, Any]]    # name, start, dur, args
KERNEL = r"^zo_matmul$"
STEP = r"_jit_step"


def segment(*names: str) -> str:
    """Pattern of a scope path holding one of ``names`` as a segment."""
    return r"(^|/)(" + "|".join(map(re.escape, names)) + r")(/|$)"


def matmul(mixer: str) -> str:
    """Pattern of the ``zo_matmul.<path>`` scopes of one mixer's
    projections (``attn``: q, k, v, o; ``mlp``: fc1, fc2)."""
    return r"(^|/)" + re.escape(obs.MATMUL) + r"([\w.-]+/)*" + mixer + "/"


# ---------------------------------------------------------------------------
# the protobuf wire format, as far as XSpace's event metadata needs it


def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of a message: an int, or a memoryview of a
    length-delimited field (fixed-width fields are skipped)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, v


def scopes(xplane: str) -> Dict[str, str]:
    """Op event name -> scope path (``tf_op``) over the TPU planes.
    XPlane: name 2, event_metadata 4, stat_metadata 5 (map entries: key
    1, value 2); XEventMetadata: name 2, stats 5; XStat: metadata_id 1,
    str_value 5, ref_value 7; XStatMetadata: id 1, name 2."""
    with open(xplane, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, str] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                metas.append(v)
            elif g == 5:
                md = dict(_fields(dict(_fields(v))[2]))
                stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        if not name.startswith("/device:TPU:"):
            continue
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        for entry in metas:
            ev_name, path = "", ""
            for h, v in _fields(dict(_fields(entry)).get(2, b"")):
                if h == 2:
                    ev_name = bytes(v).decode()
                elif h == 5:
                    st = dict(_fields(v))
                    if st.get(1) in tf_op:
                        path = (bytes(st[5]).decode() if 5 in st
                                else stat_names.get(st.get(7), ""))
            if path:
                out[ev_name] = path.rstrip(":")
    return out


# ---------------------------------------------------------------------------
# the program's view of a traced window


@dataclasses.dataclass
class Program:
    window: Tuple[int, int]
    ops: List[List[Op]]                 # per device
    spans: List[Span]                   # bench.* and repro.*, by start

    def device_s(self, scope: str, op: str = "") -> Tuple[float, int]:
        """(seconds, events) of device ops whose scope path matches
        ``scope`` and name ``op`` (``re.search``), averaged over the
        devices; control-flow containers are left out."""
        sx, ox = re.compile(scope), re.compile(op)
        tot = n = 0
        for ops in self.ops:
            for name, _, d, path in ops:
                if sx.search(path) and ox.search(name) \
                        and not TR.CONTAINERS.match(name):
                    tot += d
                    n += 1
        k = max(len(self.ops), 1)
        return tot / k / 1e9, n // k

    def has_spans(self) -> bool:
        """Whether the program recorded any span of its own."""
        return any(s[0].startswith("repro.") for s in self.spans)

    def count(self, name: str) -> int:
        """Spans called ``name`` that begin inside the window."""
        w0, w1 = self.window
        return sum(1 for s in self.spans if s[0] == name and w0 <= s[1] < w1)

    def by_scope(self, k: int = 20) -> List[List]:
        """Device seconds by (innermost program scope, op), the ``k``
        largest."""
        acc: Dict[str, int] = {}
        for ops in self.ops:
            for name, _, d, path in ops:
                if not TR.CONTAINERS.match(name):
                    key = f"{label(path)} {name}"
                    acc[key] = acc.get(key, 0) + d
        nd = max(len(self.ops), 1)
        return [[key, ns / nd / 1e9] for key, ns in
                sorted(acc.items(), key=lambda kv: -kv[1])[:k]]

    def named_share(self, modules: List[TR.Span],
                    program: str = STEP) -> Optional[float]:
        """Share of the device time of ops inside runs of ``program``
        (``modules``: device 0's programs) that a program scope holds."""
        runs = sorted((s, s + d) for n, s, d in modules
                      if re.search(program, n))
        starts = [a for a, _ in runs]
        tot = named = 0
        for name, s, d, path in (self.ops[0] if self.ops else []):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][1] or TR.CONTAINERS.match(name):
                continue
            tot += d
            named += d if label(path) != "-" else 0
        return named / tot if tot else None

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Idle time of device 0 summed by the innermost benchmark or
        program span it fell in, the ``k`` largest."""
        ev = TR.Events(window=self.window, n_devices=len(self.ops),
                       device=[[o[:3] for o in ops] for ops in self.ops],
                       modules=[], host=[s[:3] for s in self.spans])
        return TR.top_gaps(ev, k)


def label(path: str) -> str:
    """The innermost program scope in a scope path, ``-`` if none; a
    perturbed projection reads as its whole ``zo_matmul.<path>``."""
    out, segs = "-", path.split("/")
    for i, seg in enumerate(segs):
        if seg in (obs.FORWARD, obs.UPDATE, obs.LM_HEAD, obs.LOSS):
            out = seg
        elif seg.startswith(obs.MATMUL):
            tail = []
            for t in segs[i + 1:-1]:          # up to the primitive
                if "(" in t:                  # jit(zo_matmul)/pallas_call
                    break
                tail.append(t)
            out = "/".join([seg] + tail)
    return out


def read(xplane: str, window: Tuple[int, int]) -> Program:
    from jax.profiler import ProfileData

    where = scopes(xplane)
    w0, w1 = window
    ops, spans = [], []

    def keep(ev) -> bool:
        return ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0

    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = [ln for ln in plane.lines if ln.name == TR.OPS_LINE]
            ops.append(sorted(
                ((TR.op_name(ev.name), int(ev.start_ns),
                  int(ev.duration_ns), where.get(ev.name, ""))
                 for ln in lines for ev in ln.events if keep(ev)),
                key=lambda o: o[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (ev.name, int(ev.start_ns), int(ev.duration_ns),
                     dict(ev.stats or ()))
                    for ev in line.events
                    if ev.name.startswith(("repro.", TR.HOST_PREFIX))
                    and keep(ev))
    return Program(window=(int(w0), int(w1)), ops=ops,
                   spans=sorted(spans, key=lambda s: s[1]))


_CACHE: Dict[Tuple[str, Tuple[int, int]], Program] = {}


def load(view) -> Optional[Program]:
    """The traced window of a run as the program describes it, read once
    per run; None without a trace or without the program's tracing
    layer."""
    trace_dir = view.record.get("trace_dir")
    if obs is None or not trace_dir or view.events is None:
        return None
    key = (TR.find_xplane(trace_dir), tuple(view.events.window))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = read(*key)
    return _CACHE[key]


# ---------------------------------------------------------------------------
# what the train cell's readers share


def per_step_ms(view, scope: str) -> Optional[float]:
    """Device ms under ``scope`` per run of the step program, or None
    where no op carries the scope."""
    prog = load(view)
    _, steps = view.device_s(STEP, modules=True)
    if prog is None or not steps:
        return None
    s, n = prog.device_s(scope)
    return s / steps * 1e3 if n else None


def matmul_roofline(view, mixer: str, which: slice) -> Optional[float]:
    """Share of its roofline that ``zo_matmul`` reaches on one mixer's
    projections: the least time of their work (``counts.projections``
    picked by ``which``; two forwards x layers x step programs traced)
    over the kernel's time under their scopes."""
    prog = load(view)
    _, steps = view.device_s(STEP, modules=True)
    if prog is None or not steps:
        return None
    s, n = prog.device_s(matmul(mixer), KERNEL)
    if not n:
        return None
    m, t = view.config["model"], view.record["train"]
    act, w = view.counts.ACT_BYTES, view.counts.W_BYTES
    flops = bytes_ = 0.0
    for k, n_out in C.projections(m["d_model"], m["d_ff"])[which]:
        f, b = C.matmul_work(t["batch"] * t["seq"], k, n_out, act, w, act)
        flops += f
        bytes_ += b
    runs = 2 * m["n_layers"] * steps
    least = runs * max(flops / view.peaks["bf16_flops"],
                       bytes_ / view.peaks["hbm_bytes_per_s"])
    return 100.0 * least / s


def main(argv) -> int:
    trace_dir = argv[0]
    events = TR.Events.read(os.path.join(trace_dir, "events.json"))
    prog = read(TR.find_xplane(trace_dir), events.window)
    counts: Dict[str, List[float]] = {}
    for name, _, d, _ in prog.spans:
        c = counts.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += d / 1e9
    print(json.dumps({"by_scope": prog.by_scope(),
                      "named_share": prog.named_share(events.modules[0]),
                      "spans": counts, "idle_gaps": prog.idle_gaps(),
                      "compiles": [s[3] for s in prog.spans
                                   if s[0] == "repro.jit.compile"]},
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

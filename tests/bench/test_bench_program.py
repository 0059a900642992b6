"""The program's view of a traced run (bench/harness/program.py) and the
readers built on it: scope paths decoded from an XSpace's event
metadata, time by scope, spans, idle gaps by program span; every reader
None where the program has no scope or span, and the existing readers
and the recorded trace's reduction unchanged."""

import os

import pytest

from bench.harness import program as P
from bench.harness import trace as TR
from bench.harness.device import peaks
from bench.harness.result import View
from bench.harness.spec import ROOT, Bench

CELL = "opt-1.3b.zo-train.b8s512"
NEW = ("zo_matmul_roofline.attn", "zo_matmul_roofline.ffn",
       "runtime.lm_head_device_ms", "zo.update_device_ms",
       "jit.compiles_in_window")


# ---------------------------------------------------------------------------
# the wire decoder, on an XSpace encoded here


def _varint(x):
    out = b""
    while True:
        b, x = x & 0x7F, x >> 7
        out += bytes([b | (0x80 if x else 0)])
        if not x:
            return out


def _len(num, payload):
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _int(num, x):
    return _varint(num << 3) + _varint(x)


def _map(num, key, value):
    return _len(num, _int(1, key) + _len(2, value))


def _xspace():
    stat_md = (_map(5, 7, _int(1, 7) + _len(2, b"tf_op"))
               + _map(5, 8, _int(1, 8) + _len(2, b"flops"))
               + _map(5, 9, _int(1, 9) + _len(2, b"jit(f)/zo.update/add:")))
    kernel = (_int(1, 1) + _len(2, b"%zo_matmul.3 = bf16[8] custom-call()")
              + _len(5, _int(1, 8) + _varint(2 << 3 | 1) + b"\0" * 8)
              + _len(5, _int(1, 7) + _len(5, b"jit(f)/zo.forward/zo_matmul."
                                          b"blocks/attn/wq/pallas_call:")))
    interned = (_int(1, 2) + _len(2, b"%fusion.1 = f32[] fusion()")
                + _len(5, _int(1, 7) + _varint(7 << 3) + _varint(9)))
    bare = _int(1, 3) + _len(2, b"%copy.2 = f32[] copy()")
    tpu = (_int(1, 1) + _len(3, b"\x08\x01") + stat_md
           + _len(2, b"/device:TPU:0") + _map(4, 1, kernel)
           + _map(4, 2, interned) + _map(4, 3, bare))
    host = _len(2, b"/host:CPU") + stat_md + _map(4, 1, kernel)
    return _len(1, tpu) + _len(1, host) + _len(4, b"hostname")


def test_scopes_from_event_metadata(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    assert P.scopes(str(path)) == {
        "%zo_matmul.3 = bf16[8] custom-call()":
            "jit(f)/zo.forward/zo_matmul.blocks/attn/wq/pallas_call",
        "%fusion.1 = f32[] fusion()": "jit(f)/zo.update/add"}


# ---------------------------------------------------------------------------
# reductions on a hand-made program view

STEP = "jit(_jit_step_donate)/"
SCOPED = [
    ("zo_matmul", 0, 30, STEP + "zo.forward/while/body/closed_call/"
     "zo_matmul.blocks/attn/wq/jit(zo_matmul)/pallas_call"),
    ("zo_matmul", 30, 50, STEP + "zo.forward/while/body/closed_call/"
     "zo_matmul.blocks/mlp/w_in/jit(zo_matmul)/pallas_call"),
    ("fusion", 80, 6, STEP + "zo.forward/runtime.lm_head/"
     "zo_matmul.lm_head/dot_general"),
    ("fusion", 86, 4, STEP + "zo.forward/runtime.loss/reduce_sum"),
    ("zo_add", 100, 8, STEP + "zo.update/while/body/jit(zo_add)/"
     "pallas_call"),
    ("while", 100, 8, STEP + "zo.update/while"),
    ("copy", 108, 2, STEP + "copy"),
]
SPANS = [("bench.train.window", 0, 200, {}),
         ("repro.zo.step", 0, 1, {"step": 4}),
         ("repro.ckpt.append", 1, 150, {"step": 4}),
         ("repro.jit.compile", 150, 0, {"fun": "jit(f)", "ms": 3.0}),
         ("repro.jit.compile", 250, 0, {"fun": "jit(g)", "ms": 1.0})]


def _program(ops=SCOPED, spans=SPANS):
    return P.Program(window=(0, 200), ops=[list(ops)], spans=list(spans))


def test_time_by_scope_and_label():
    prog = _program()
    assert prog.device_s(P.matmul("attn"), P.KERNEL) == (30e-9, 1)
    assert prog.device_s(P.matmul("mlp"), P.KERNEL) == (50e-9, 1)
    assert prog.device_s(P.segment("runtime.lm_head", "runtime.loss")) \
        == (10e-9, 2)
    assert prog.device_s(P.segment("zo.update")) == (8e-9, 1)  # no while
    assert [P.label(o[3]) for o in SCOPED] == [
        "zo_matmul.blocks/attn/wq", "zo_matmul.blocks/mlp/w_in",
        "zo_matmul.lm_head", "runtime.loss", "zo.update", "zo.update", "-"]
    # the copy carries no scope; an op outside every step run is not counted
    steps = [("jit__jit_step_donate", 0, 110), ("jit_other", 110, 90)]
    assert prog.named_share(steps) == pytest.approx(98 / 100)
    assert prog.named_share(steps[:1], program="other") is None
    assert prog.by_scope(1) == [["zo_matmul.blocks/mlp/w_in zo_matmul",
                                 50e-9]]


def test_spans_counts_and_gaps():
    prog = _program()
    assert prog.has_spans()
    assert prog.count("repro.jit.compile") == 1           # one in window
    # idle 90..100 inside the replay-log append, 110..200 in the window
    assert prog.idle_gaps() == [["bench.train.window x1", 90e-9],
                                ["repro.ckpt.append x1", 10e-9]]
    assert not _program(spans=SPANS[:1]).has_spans()


# ---------------------------------------------------------------------------
# the readers


@pytest.fixture(scope="module")
def bench():
    return Bench(ROOT)


RECORDED = os.path.join(ROOT, "bench", "testdata", "train_trace.json")


def _view(bench, trace_dir=None):
    ev = TR.Events.read(RECORDED)
    rec = {"train": {"steps": 1, "batch": 8, "seq": 512,
                     "tokens_per_step": 4096},
           "window_s": TR.window_ns(ev) / 1e9, "trace_dir": trace_dir}
    return View(rec, ev, peaks("TPU v5 lite"), bench.counts("opt-1.3b"),
                bench.cell(CELL), bench.config("opt-1.3b"))


def test_new_metrics_listed_for_the_train_cell(bench):
    listed = {m["name"]: m for m in bench.per_layer(CELL)}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "train_tok_s"


@pytest.mark.parametrize("name", NEW)
def test_readers_none_without_program_scopes(bench, monkeypatch, name):
    """No trace dir, and a trace whose ops and spans are the benchmark's
    alone (as from a program before its tracing layer): None, never 0."""
    mod = bench.metric(name)
    assert mod.read(_view(bench)) is None
    bare = [(n, s, d, STEP + "while/body/dot_general")
            for n, s, d, _ in SCOPED]
    monkeypatch.setattr(P, "load", lambda view: _program(bare, SPANS[:1]))
    assert mod.read(_view(bench, "x")) is None


def test_readers_on_scoped_ops(bench, monkeypatch):
    """The recorded trace has one step program; the hand-made ops give
    each class its kernel time."""
    monkeypatch.setattr(P, "load", lambda view: _program())
    view = _view(bench, "x")
    got = {name: bench.metric(name).read(view) for name in NEW}
    m = bench.config("opt-1.3b")["model"]
    d, ff, layers, rows = m["d_model"], m["d_ff"], m["n_layers"], 8 * 512
    peak = view.peaks["bf16_flops"]
    attn = 2 * layers * 4 * 2.0 * rows * d * d / peak
    ffn = 2 * layers * 2 * 2.0 * rows * d * ff / peak
    assert got["zo_matmul_roofline.attn"] == pytest.approx(
        100 * attn / 30e-9)
    assert got["zo_matmul_roofline.ffn"] == pytest.approx(100 * ffn / 50e-9)
    assert got["runtime.lm_head_device_ms"] == pytest.approx(10e-6)
    assert got["zo.update_device_ms"] == pytest.approx(8e-6)
    assert got["jit.compiles_in_window"] == 1


def test_recorded_trace_and_existing_readers_unchanged(bench):
    """The ledger's breakdown and the accepted readers read what they
    read before the program's scopes existed."""
    view = _view(bench)
    s = TR.summary(view.events)
    assert s["busy_s"] == 1.594622299 and s["window_s"] == 1.607134003
    assert s["breakdown"]["device_ops"][:3] == [
        ["zo_matmul", 1.485559563], ["fusion", 0.075158805],
        ["copy", 0.010410415]]
    assert s["breakdown"]["idle_gaps"][0] == ["bench.train.batch x2",
                                              0.012509063]
    got = {name: bench.metric(name).read(view) for name in (
        "zo.step_device_ms", "zo_matmul_roofline", "mfu.train",
        "device.idle.train")}
    assert got == pytest.approx({
        "zo.step_device_ms": 1594.628443,
        "zo_matmul_roofline": 6.762636374371745,
        "mfu.train": 6.914341944161116,
        "device.idle.train": 0.7785103156702999})

"""``runtime.attention_device_ms``: device ms under the program's
``runtime.attention`` scope per run of the step program, listed for both
train cells; None where no op carries the scope or the program names no
such scope."""

import os
import types

import pytest

from bench.harness import program as P
from bench.harness import trace as TR
from bench.harness.device import peaks
from bench.harness.result import View
from bench.harness.spec import ROOT, Bench

NAME = "runtime.attention_device_ms"
CELLS = ["opt-1.3b.zo-train.b8s512", "roberta-large.zo-train.b64s128"]
RECORDED = os.path.join(ROOT, "bench", "testdata", "train_trace.json")
STEP = "jit(_jit_step_donate)/zo.forward/while/body/closed_call/"
OPS = [
    ("zo_matmul", 0, 30, STEP + "zo_matmul.blocks/attn/wq/jit(zo_matmul)/"
     "pallas_call"),
    ("fusion", 30, 7, STEP + "runtime.attention/bkgst,btkh->bskgh/"
     "dot_general"),
    ("exponential_reduce_fusion", 37, 5, STEP + "runtime.attention/exp"),
    ("fusion", 42, 4, STEP + "runtime.cls_head/tanh"),
    ("copy", 46, 2, "jit(_jit_step_donate)/copy"),
]


@pytest.fixture(scope="module")
def bench():
    return Bench(ROOT)


def _view(bench, cell, trace_dir="x"):
    """A view of the recorded trace, which holds one step program."""
    b = bench.cell(cell)
    rec = {"train": {"steps": 1, "batch": b["traffic"]["batch"],
                     "seq": b["traffic"]["seq"]},
           "trace_dir": trace_dir}
    return View(rec, TR.Events.read(RECORDED), peaks("TPU v5 lite"),
                bench.counts(b["config"]), b, bench.config(b["config"]))


def _program(ops):
    return P.Program(window=(0, 100), ops=[list(ops)], spans=[])


def test_listed_for_both_train_cells(bench):
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == CELLS
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "model runtime", "train_tok_s", "device_trace")
    for cell in CELLS:
        assert NAME in {m["name"] for m in bench.per_layer(cell)}


@pytest.mark.parametrize("cell", CELLS)
def test_reads_ms_under_the_scope_per_step(bench, monkeypatch, cell):
    monkeypatch.setattr(P, "load", lambda view: _program(OPS))
    got = bench.metric(NAME).read(_view(bench, cell))
    assert got == pytest.approx((7 + 5) * 1e-6)     # ns -> ms, one step


def test_none_where_no_op_carries_the_scope(bench, monkeypatch):
    mod = bench.metric(NAME)
    assert mod.read(_view(bench, CELLS[0], trace_dir=None)) is None
    unscoped = [o for o in OPS if "runtime.attention" not in o[3]]
    monkeypatch.setattr(P, "load", lambda view: _program(unscoped))
    assert mod.read(_view(bench, CELLS[0])) is None


def test_none_where_the_program_names_no_such_scope(bench, monkeypatch):
    """A program from before the scope existed: its tracing layer lacks
    the name, and the reader returns None without raising."""
    monkeypatch.setattr(P, "load", lambda view: _program(OPS))
    monkeypatch.setattr(P, "obs", types.SimpleNamespace(
        **{k: v for k, v in vars(P.obs).items() if k != "ATTENTION"}))
    assert bench.metric(NAME).read(_view(bench, CELLS[1])) is None

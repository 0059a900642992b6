"""The trace reduction: busy union, idle share, time by name, and idle
gaps labelled by the host span they fell in; on a hand-made trace and on
a small trace recorded on a TPU v5e (bench/testdata)."""

import os


from bench.harness import trace as TR
from bench.harness.spec import ROOT


def toy() -> TR.Events:
    # window 0..100 ns; ops overlap at 10..30, gap 30..50 inside the
    # host span "bench.serve.step", gap 80..100 outside any span
    return TR.Events(
        window=(0, 100), n_devices=1,
        device=[[("fusion", 0, 20), ("zo_matmul", 10, 20),
                 ("while", 50, 30), ("zo_matmul", 50, 30), ("late", 95, 50)]],
        modules=[[("jit_step", 0, 30), ("jit_step", 50, 30)]],
        host=[("bench.serve.window", 0, 100), ("bench.serve.step", 25, 40)])


def test_union_and_idle():
    ev = toy()
    assert TR.union(ev.device[0], ev.window) == [(0, 30), (50, 80), (95, 100)]
    assert TR.busy_ns(ev) == 65
    assert TR.window_ns(ev) == 100


def test_names_from_hlo_text():
    assert TR.op_name("%zo_matmul.12 = bf16[8,8]{1,0} custom-call(%a)") \
        == "zo_matmul"
    assert TR.op_name("%while.35 = (s32[], bf16[2]) while(%t)") == "while"
    assert TR.module_name("jit_draft_spec(630645689719840750)") \
        == "jit_draft_spec"


def test_time_by_name():
    ev = toy()
    assert TR.time_by_name(ev.device, r"zo_matmul") == (50, 2)
    assert TR.time_by_name(ev.device, r"while") == (0, 0)    # a container
    assert TR.time_by_name(ev.modules, r"^jit_step$") == (60, 2)
    assert TR.top_ops(ev, 2) == [["zo_matmul", 50 / 1e9], ["late", 50 / 1e9]]


def test_gaps_labelled_by_host_span():
    ev = toy()
    assert TR.idle_gaps(ev) == [("bench.serve.step", 20),
                                ("bench.serve.window", 15)]
    assert TR.top_gaps(ev)[0] == ["bench.serve.step x1", 20 / 1e9]
    s = TR.summary(ev)
    assert s["busy_s"] == 65e-9 and s["window_s"] == 100e-9


def test_events_round_trip(tmp_path):
    ev = toy()
    ev.save(str(tmp_path / "e.json"))
    back = TR.Events.read(str(tmp_path / "e.json"))
    assert back == ev


RECORDED = os.path.join(ROOT, "bench", "testdata", "train_trace.json")


def test_recorded_tpu_trace():
    """One fused ZO step of opt-1.3b.zo-train.b8s512, traced on a TPU
    v5e: the zo_matmul kernel fills most of a busy chip, and every idle
    gap is labelled by a benchmark span."""
    ev = TR.Events.read(RECORDED)
    busy = TR.busy_ns(ev)
    assert ev.n_devices == 1
    assert 0.95 * TR.window_ns(ev) < busy <= TR.window_ns(ev)
    ns, n = TR.time_by_name(ev.device, r"zo_matmul")
    assert n == 288 and 0.9 * busy < ns <= busy     # 24 layers x 6 x 2
    assert TR.top_ops(ev, 1)[0][0] == "zo_matmul"
    step_ns, steps = TR.time_by_name(ev.modules, r"_jit_step")
    assert steps == 1 and 0.95 * busy < step_ns <= TR.window_ns(ev)
    gaps = TR.idle_gaps(ev)
    assert abs(sum(g for _, g in gaps) - (TR.window_ns(ev) - busy)) <= 1
    assert {label for label, _ in gaps} <= {
        "bench.train.batch", "bench.train.replay_log", "bench.train.step",
        "bench.train.sync", "bench.train.window"}

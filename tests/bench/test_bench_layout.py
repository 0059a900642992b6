"""BENCHMARK.json and the files it names: every piece is found by name
from files alone, and a new cell, configuration or metric needs only
new files and entries."""

import json
import os
import re
import shutil

import pytest

from bench.harness.spec import ROOT, Bench, load_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return Bench(ROOT)


def test_top_level_keys(bench):
    assert set(bench.spec) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert bench.spec["command"] == ["python3", "bench/run.py"]
    for p in bench.spec["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= bench.spec["run_seconds"] <= 51


def test_names(bench):
    s = bench.spec
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in s[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in s[k]}) == len(s[k])


ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entry_keys_and_form(bench, section):
    """Each entry has exactly the keys its section allows, in the form
    the benchmark's file format sets."""
    need, may = ENTRY_KEYS[section]
    entries = bench.spec[section]
    assert entries
    for e in entries:
        assert need <= set(e) <= need | may, (section, sorted(e))
        if section in ("end_to_end", "per_layer"):
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        if section == "end_to_end":
            assert e["source"] in ("host_clock", "device_trace")
            assert 0.01 <= e["bound"] <= 0.25
        if section == "per_layer":
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
            assert _line(e["layer"])
        if section in ("configs", "workloads"):
            assert _line(e["why"])
        if section == "configs":
            assert _line(e["source"]) and len(e["reduced"]) <= 16
            assert all(NAME.match(k) for k in e["reduced"])
            assert any(e["file"].startswith(p + "/")
                       for p in bench.spec["paths"])
        if section == "workloads":
            assert NAME.match(e["traffic"]) and e["chips"] in (1, 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_every_cell_found_by_name(bench):
    for w in bench.spec["workloads"]:
        cell = bench.cell(w["name"])
        assert cell["config"] == w["config"]
        cfg = bench.config(cell["config"])
        assert cfg["source"].startswith("https://")
        assert callable(bench.driver(cell["driver"]).run)
        assert bench.counts(cell["config"]) is not None
        e2e = {m["name"] for m in bench.end_to_end(w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert bench.per_layer(w["name"])
        assert w["chips"] == 1


def test_every_config_used_and_reduced_listed(bench):
    used = {w["config"] for w in bench.spec["workloads"]}
    for c in bench.spec["configs"]:
        assert c["name"] in used
        data = bench.config(c["name"])
        assert data["reduced"] == c["reduced"]
        assert c["file"].startswith("bench/configs/")


def test_metric_files_match_the_spec(bench):
    e2e = {m["name"] for m in bench.spec["end_to_end"]}
    for m in bench.spec["per_layer"]:
        mod = bench.metric(m["name"])
        assert (mod.UNIT, mod.BETTER, mod.MOVES) == (
            m["unit"], m["better"], m["moves"]), m["name"]
        assert m["moves"] in e2e
        assert callable(mod.read)
        cells = {w["name"] for w in bench.spec["workloads"]}
        assert set(m.get("workloads", cells)) <= cells


def test_layers_are_named_in_perf_md(bench):
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in {m["layer"] for m in bench.spec["per_layer"]}:
        assert layer in perf, layer


def test_new_cell_config_and_metric_by_files_alone(tmp_path):
    """A later change adds a configuration, a traffic mix and a metric by
    adding files and entries; the harness finds each by name."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(tmp_path / "bench/configs/opt-1.3b.json"))
    cfg["model"] = dict(cfg["model"], name="opt-toy", n_layers=2)
    (tmp_path / "bench/configs/opt-toy.json").write_text(json.dumps(cfg))
    shutil.copy(tmp_path / "bench/counts/opt-1.3b.py",
                tmp_path / "bench/counts/opt-toy.py")
    cell = json.load(open(tmp_path / "bench/cells/"
                          "opt-1.3b.zo-train.b8s512.json"))
    cell = dict(cell, config="opt-toy",
                traffic=dict(cell["traffic"], batch=2, seq=64))
    (tmp_path / "bench/cells/opt-toy.zo-train.b2s64.json").write_text(
        json.dumps(cell))
    (tmp_path / "bench/metrics/toy.steps.py").write_text(
        'UNIT, BETTER, MOVES = "1", "higher", "train_tok_s"\n\n'
        'def read(view):\n    return view.record["train"]["steps"]\n')
    spec["configs"].append({"name": "opt-toy", "source": "https://x.org",
                            "file": "bench/configs/opt-toy.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "opt-toy.zo-train.b2s64",
                              "config": "opt-toy", "traffic": "toy",
                              "chips": 1, "why": "toy"})
    for m in spec["end_to_end"]:
        if "workloads" in m and m["name"] == "train_tok_s":
            m["workloads"].append("opt-toy.zo-train.b2s64")
    spec["per_layer"].append({"name": "toy.steps", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "toy", "moves": "train_tok_s",
                              "workloads": ["opt-toy.zo-train.b2s64"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    b = Bench(str(tmp_path))
    cell = b.cell("opt-toy.zo-train.b2s64")
    assert cell["traffic"]["batch"] == 2
    assert b.config(cell["config"])["model"]["n_layers"] == 2
    assert [m["name"] for m in b.per_layer(cell["name"])] == ["toy.steps"]
    assert {m["name"] for m in b.end_to_end(cell["name"])} >= {
        "setup_s", "train_tok_s"}
    mod = b.metric("toy.steps")
    assert mod.read(type("V", (), {"record": {"train": {"steps": 7}}})) == 7
    assert b.counts("opt-toy").zo_matmul_step(1, 1)[0] > 0
    assert callable(b.driver(cell["driver"]).run)


def test_missing_pieces_raise(bench):
    with pytest.raises(KeyError):
        bench.cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        load_module(os.path.join(ROOT, "bench", "metrics", "nope.py"))


def test_peaks_by_device_kind():
    from bench.harness.device import peaks

    v5e = peaks("TPU v5 lite")
    assert (v5e["bf16_flops"], v5e["int8_ops"], v5e["hbm_bytes_per_s"]) \
        == (197e12, 393e12, 819e9)
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")

"""The output check drives a whole run on the CPU at a reduced size,
skipping only the look for a chip: the program's run reads correct,
the control and each fault planted in the timed path read not correct.
And ``bench/run.py`` refuses a host without a TPU."""

import dataclasses
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench.harness.context import Ctx
from bench.harness.device import CompileClock
from bench.harness.spec import ROOT, Bench, load_json

TRAIN = "opt-1.3b.zo-train.b8s512"
TRAIN_F32 = "roberta-large.zo-train.sst2-b64s128"
SERVE = "opt-1.3b.serve.2user-poisson"
SEED = 2 ** 31 + 4242
SMALL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
             vocab=512, max_seq=64)
# limits at this size, between what the program and the control read
# here on three to six seeds (opt-1.3b program: loss <= 4.9e-5, delta <=
# 0.04, logit_gap <= 1.3e-3; control: loss >= 1.8e-4, logit_gap >= 6e-3;
# roberta-large program: loss <= 4.5e-8, control: loss >= 2.5e-7); a
# state left unchanged reads delta 1
TINY_LIMITS = {"loss": 1.2e-4, "delta": 0.5, "logit_gap": 3e-3}
TINY_LIMITS_OF = {TRAIN_F32: {**TINY_LIMITS, "loss": 1e-7}}


def tiny_ctx(tmp_path, name):
    b = Bench(ROOT)
    cell = load_json(os.path.join(b.dir, "cells", f"{name}.json"))
    config = load_json(os.path.join(b.dir, "configs",
                                    f"{cell['config']}.json"))
    config = {**config, "name": cell["config"],
              "model": {**config["model"], **SMALL}}
    t = dict(cell["traffic"])
    if cell["driver"] == "train":
        t.update(batch=2, seq=16)
    else:
        t.update(rate_rps=4.0,
                 prompt={"median": 12, "sigma": 0.8, "min": 4, "max": 24},
                 output={"median": 12, "sigma": 0.5, "min": 4, "max": 16},
                 engine={"slots": 4, "page_size": 8, "pool_pages": 17,
                         "prefill_chunk": 8, "spec_k": 2, "max_len": 40},
                 adapter={**t["adapter"], "records": 3})
    limits = TINY_LIMITS_OF.get(name, TINY_LIMITS)
    cell = {**cell, "name": name, "chips": 1, "traffic": t, "check": {
        **cell["check"], "limits": {k: limits[k]
                                    for k in cell["check"]["limits"]}}}
    ctx = Ctx(cell=cell, config=config, seed=SEED, seconds=1.0, trace=False,
              t_start=time.perf_counter(), work_dir=str(tmp_path),
              devs=jax.devices(), clock=CompileClock().install())
    return b.driver(cell["driver"]), ctx


@pytest.fixture(scope="module", params=[TRAIN, TRAIN_F32])
def train_run(tmp_path_factory, request):
    driver, ctx = tiny_ctx(tmp_path_factory.mktemp("t"), request.param)
    return driver, ctx, driver.run(ctx)


def test_train_program_reads_correct(train_run):
    _, _, rec = train_run
    assert rec["check"].correct, rec["check"].lines()
    assert set(rec["check"].readings) == {"loss", "delta"}
    assert rec["attempted"] >= 1 and rec["failed"] == 0
    assert rec["e2e"]["train_tok_s"] > 0


def test_train_control_reads_not_correct(train_run):
    driver, ctx, rec = train_run
    ctl = driver.control(ctx)
    limits = ctx.cell["check"]["limits"]
    assert any(ctl[k] > limits[k] for k in limits), ctl


def _planted(monkeypatch, fault):
    from repro.core import engine as E
    orig = E.ZOStrategy.step

    def step(self, loss_fn, state, batch, seed, cfg, mask=None):
        if fault == "unchanged":
            keep = jax.tree.map(jnp.copy, state)
            _, aux = orig(self, loss_fn, state, batch, seed, cfg, mask)
            return keep, aux
        if fault == "half_batch":
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return orig(self, loss_fn, state, half, seed, cfg, mask)
        new, aux = orig(self, loss_fn, state, batch, seed, cfg, mask)
        return new, dataclasses.replace(aux, gs=2 * aux.gs)  # gs over eps, not 2 eps

    monkeypatch.setattr(E.ZOStrategy, "step", step)


@pytest.mark.parametrize("cell", [TRAIN, TRAIN_F32])
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "answer"])
def test_train_fault_reads_not_correct(tmp_path, monkeypatch, fault, cell):
    _planted(monkeypatch, fault)
    driver, ctx = tiny_ctx(tmp_path, cell)
    rec = driver.run(ctx)
    assert not rec["check"].correct, (fault, rec["check"].lines())


@pytest.fixture(scope="module")
def serve_run(tmp_path_factory):
    driver, ctx = tiny_ctx(tmp_path_factory.mktemp("s"), SERVE)
    return driver, ctx, driver.run(ctx)


def test_serve_program_reads_correct(serve_run):
    _, _, rec = serve_run
    assert rec["check"].correct, rec["check"].lines()
    assert rec["serve"]["finished"] >= 1
    assert rec["e2e"]["ttft_p90_ms"] > 0


def test_serve_control_reads_not_correct(serve_run):
    driver, ctx, rec = serve_run
    ctl = driver.control(ctx, rec)
    assert ctl["logit_gap"] > ctx.cell["check"]["limits"]["logit_gap"], ctl


def test_serve_altered_token_reads_not_correct(tmp_path, monkeypatch):
    from repro.serve.engine import ServeEngine
    orig = ServeEngine._finish

    def finish(self, slot):
        self._out[slot][-1] = (self._out[slot][-1] + 1) % self.cfg.vocab
        return orig(self, slot)

    monkeypatch.setattr(ServeEngine, "_finish", finish)
    driver, ctx = tiny_ctx(tmp_path, SERVE)
    rec = driver.run(ctx)
    assert not rec["check"].correct, rec["check"].lines()


def _run_bench(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", TRAIN, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_run_without_tpu_exits_nonzero_naming_the_platform():
    p = _run_bench(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "'cpu'" in p.stderr and "TPU" in p.stderr


def test_run_without_the_system_exits_nonzero(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run_bench(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""

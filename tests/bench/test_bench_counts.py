"""Operation and byte counts of both configurations, against numbers
worked out by hand."""

import os

from bench.harness.spec import ROOT, load_module


def counts(name):
    return load_module(os.path.join(ROOT, "bench", "counts", f"{name}.py"))


def test_opt_zo_matmul_step():
    c = counts("opt-1.3b")
    flops, bytes_ = c.zo_matmul_step(8, 512)
    m = 4096
    per_layer = 2 * m * (4 * 2048 * 2048 + 2 * 2048 * 8192)
    assert flops == 2 * 24 * per_layer               # two forwards
    assert flops == 19_791_209_299_968
    io = 2 * (4 * (m * 2048 + 2048 * 2048 + m * 2048)
              + (m * 2048 + 2048 * 8192 + m * 8192)
              + (m * 8192 + 8192 * 2048 + m * 2048))
    assert bytes_ == 2 * 24 * io


def test_opt_model_flops():
    c = counts("opt-1.3b")
    n_mm = 24 * 12 * 2048 ** 2 + 2048 * 50272
    assert n_mm == 1_310_916_608
    per_tok = 2 * n_mm + 4 * 24 * 2048 * (512 + 1) / 2
    assert c.train_step_flops(8, 512) == 2 * 4096 * per_tok
    assert c.token_flops(100) - c.token_flops(100, head=False) \
        == 2 * 2048 * 50272
    w = c.attention_work(q_keys=10, slot_keys=3)
    assert w["flops"] == 4 * 10 * 2048 * 24
    assert w["bytes"] == 2 * 3 * 2048 * 2 * 24


def test_roberta_counts():
    c = counts("roberta-large")
    flops, bytes_ = c.zo_matmul_step(64, 128)
    m = 8192
    per_layer = 2 * m * (4 * 1024 ** 2 + 2 * 1024 * 4096)
    assert flops == 2 * 24 * per_layer
    io = 4 * (4 * (m * 1024 + 1024 ** 2 + m * 1024)
              + 2 * (m * 1024 + 1024 * 4096 + m * 4096))
    assert bytes_ == 2 * 24 * io
    body = 2 * 24 * 12 * 1024 ** 2 + 4 * 24 * 1024 * 128
    assert c.train_step_flops(64, 128) == 2 * (m * body + 2 * 64 * 1024 * 2)

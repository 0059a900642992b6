"""The generator: everything from the seed, and every seed the same
amount of work."""

import numpy as np

from bench.harness import traffic as T

SERVE = {"rate_rps": 2.0,
         "prompt": {"median": 256, "sigma": 0.8, "min": 32, "max": 1024},
         "output": {"median": 64, "sigma": 0.8, "min": 8, "max": 256},
         "users": [{"name": "a", "share": 0.75}, {"name": "b", "share": 0.25}]}
BIG = 2 ** 31 + 12345


def test_train_batches_deterministic_and_distinct():
    tr = {"task": "lm", "batch": 4, "seq": 16}
    a = T.train_batch(tr, 1000, BIG, 3)
    b = T.train_batch(tr, 1000, BIG, 3)
    c = T.train_batch(tr, 1000, BIG, 4)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["targets"][:, :-1])
    assert len({r.tobytes() for r in a["tokens"]}) == 4
    cls = T.train_batch({"task": "cls", "batch": 8, "seq": 4,
                         "n_classes": 2}, 50, BIG, 0)
    assert set(cls["label"].tolist()) <= {0, 1}


def test_arrivals_deterministic_in_the_seed():
    a = T.arrivals(SERVE, 50272, BIG, 40.0)
    b = T.arrivals(SERVE, 50272, BIG, 40.0)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = T.arrivals(SERVE, 50272, BIG + 1, 40.0)
    assert [x.due_s for x in a] != [x.due_s for x in c]


def test_every_seed_offers_the_same_work():
    a = T.arrivals(SERVE, 50272, 1, 40.0)
    b = T.arrivals(SERVE, 50272, BIG, 40.0)
    assert len(a) == len(b) == 80
    for f in (lambda x: x.prompt.size, lambda x: x.max_new,
              lambda x: x.user):
        assert sorted(map(f, a)) == sorted(map(f, b))
    assert abs(a[-1].due_s - b[-1].due_s) < 40.0 / 2
    gaps = np.diff([x.due_s for x in a])
    assert abs(gaps.mean() - 0.5) < 0.1
    plens = np.array([x.prompt.size for x in a])
    assert plens.min() >= 32 and plens.max() <= 1024
    assert abs(np.median(plens) - 256) < 30
    assert sum(x.user == "a" for x in a) == 60


def test_adapter_records_deterministic():
    ad = {"records": 5, "lr": 1e-6, "eps": 1e-3, "gs_sigma": 10.0}
    r1 = T.adapter_records(ad, BIG, 0)
    assert r1 == T.adapter_records(ad, BIG, 0)
    assert r1 != T.adapter_records(ad, BIG, 1)
    assert [r["step"] for r in r1] == list(range(5))
    assert all(0 <= r["seed"] < 2 ** 32 for r in r1)


def test_seed32_fits_jax():
    for s in (0, 1, 2 ** 31 - 1, BIG, 2 ** 40):
        assert 0 <= T.seed32(s) < 2 ** 31
    assert T.seed32(BIG, 0) != T.seed32(BIG, 1)

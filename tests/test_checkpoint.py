"""Checkpoint store, replay log, and crash-recovery semantics."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, ReplayLog, latest_step,
                              load_params, save_params)
from repro.checkpoint.replay_log import replay_into
from repro.core import MezoConfig


def _params(key=0):
    k = jax.random.PRNGKey(key)
    return {"a": {"w": jax.random.normal(k, (8, 16))},
            "b": jnp.arange(5, dtype=jnp.float32)}


def test_save_load_roundtrip(tmp_path):
    p = _params()
    save_params(str(tmp_path), 3, p)
    assert latest_step(str(tmp_path)) == 3
    q = load_params(str(tmp_path), 3, jax.tree.map(jnp.zeros_like, p))
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_atomic_overwrite(tmp_path):
    p = _params()
    save_params(str(tmp_path), 1, p)
    p2 = jax.tree.map(lambda x: x + 1, p)
    save_params(str(tmp_path), 1, p2)
    q = load_params(str(tmp_path), 1, p)
    np.testing.assert_array_equal(np.asarray(q["b"]),
                                  np.asarray(p["b"] + 1))


def test_replay_log_roundtrip(tmp_path):
    path = str(tmp_path / "log.jsonl")
    log = ReplayLog(path)
    log.append(0, 123, [0.5, -0.25], 1e-3, 1e-2)
    log.append(1, 456, [0.1, 0.2], 1e-3, 1e-2)
    log.close()
    recs = ReplayLog.read(path)
    assert [r["step"] for r in recs] == [0, 1]
    assert recs[0]["gs"] == [0.5, -0.25]


def test_replay_log_torn_tail(tmp_path):
    path = str(tmp_path / "log.jsonl")
    log = ReplayLog(path)
    log.append(0, 1, [0.5], 1e-3, 1e-2)
    log.close()
    with open(path, "a") as f:
        f.write('{"step": 1, "seed": 2, "gs"')  # torn write
    with pytest.warns(RuntimeWarning, match="dropped 1 corrupt"):
        recs = ReplayLog.read(path)
    assert len(recs) == 1 and recs[0]["step"] == 0


def test_replay_log_torn_middle_recovers_tail(tmp_path):
    """A crash mid-append followed by a restart leaves a corrupt line in
    the MIDDLE of the log (the restart retries the step and keeps
    appending). read() must warn with the drop count and keep everything
    valid -- including records after the tear -- with the retried step
    deduplicated."""
    path = str(tmp_path / "log.jsonl")
    log = ReplayLog(path)
    log.append(0, 1, [0.5], 1e-3, 1e-2)
    log.close()
    with open(path, "a") as f:
        f.write('{"step": 1, "seed": 2, "gs"')        # torn write (crash),
    log = ReplayLog(path)          # NO trailing newline; restart must seal
    log.append(1, 2, [0.25], 1e-3, 1e-2)              # retried step
    log.append(1, 2, [0.25], 1e-3, 1e-2)              # duplicate retry
    log.append(2, 3, [0.125], 1e-3, 1e-2)
    log.close()
    with pytest.warns(RuntimeWarning, match="dropped 1 corrupt"):
        recs = ReplayLog.read(path)
    assert [r["step"] for r in recs] == [0, 1, 2]
    assert recs[1]["gs"] == [0.25]


def test_replay_log_dedup(tmp_path):
    path = str(tmp_path / "log.jsonl")
    log = ReplayLog(path)
    log.append(0, 1, [0.5], 1e-3, 1e-2)
    log.append(0, 1, [0.5], 1e-3, 1e-2)  # retried step
    log.close()
    assert len(ReplayLog.read(path)) == 1


def test_replay_into_matches_live_update(tmp_path, zo_step):
    params = _params(1)

    def loss_fn(p, _):
        return jnp.sum(p["a"]["w"] ** 2) * 1e-3 + jnp.sum(p["b"] ** 2) * 1e-3

    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=2)
    p_live = jax.tree.map(jnp.copy, params)
    recs = []
    for t in range(5):
        p_live, aux = zo_step(loss_fn, p_live, None, jnp.uint32(t), cfg,
                              estimator="vmapdir")
        recs.append({"step": t, "seed": int(aux.seed),
                     "gs": np.asarray(aux.gs).tolist(),
                     "lr": cfg.lr, "eps": cfg.eps})
    p_replay, last = replay_into(jax.tree.map(jnp.copy, params), recs, cfg)
    assert last == 4
    for a, b in zip(jax.tree.leaves(p_live), jax.tree.leaves(p_replay)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_manager_restore_snapshot_plus_log(tmp_path, zo_step):
    params = _params(2)

    def loss_fn(p, _):
        return jnp.sum(p["a"]["w"] ** 2) * 1e-3

    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=1)
    mgr = CheckpointManager(str(tmp_path), mezo_cfg=cfg, snapshot_every=3)
    p = jax.tree.map(jnp.copy, params)
    for t in range(7):
        p, aux = zo_step(loss_fn, p, None, jnp.uint32(t), cfg,
                         estimator="vmapdir")
        mgr.on_step(t, p, aux)
    # snapshot at 6 + log 0..6 -> restore resumes at 7
    restored, nxt = CheckpointManager(
        str(tmp_path), mezo_cfg=cfg, snapshot_every=3).restore(params)
    assert nxt == 7
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

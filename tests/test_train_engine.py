"""Batched multi-tenant TrainEngine: bit-parity, slots, fault recovery.

The contract under test (src/repro/train/engine.py): one vmapped fused
dispatch advancing B resident users is *bit-identical* (atol=0) to B
lone sequential runs -- losses, gs, final deltas, and the replay-log
lines themselves -- and eviction + re-admission through the AdapterStore
resumes exactly where an uninterrupted run would be.

Set REPRO_FAMILY=<family[,family]> to restrict families (the CI
family-matrix job does).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "golden"))
import capture_train_engine as ctg  # noqa: E402  (single source of scenario)

from repro.configs import get_config                      # noqa: E402
from repro.core import rng as zrng                        # noqa: E402
from repro.core.engine import build_strategy              # noqa: E402
from repro.models import build_model                      # noqa: E402
from repro.optim.quant import is_quantized, quantize_tree  # noqa: E402
from repro.runtime.trainer import (Trainer, TrainerConfig,  # noqa: E402
                                   train_multi_tenant)
from repro.serve.adapters import AdapterStore             # noqa: E402
from repro.train import (TrainEngine, TrainJob,           # noqa: E402
                         derive_user_seed)

with open(os.path.join(os.path.dirname(__file__), "golden",
                       "train_engine.json")) as f:
    GOLDEN = json.load(f)

_FAM = os.environ.get("REPRO_FAMILY")
ARCHS = [a for a, rec in GOLDEN.items()
         if not _FAM or rec["family"] in _FAM.split(",")]
MZ = ctg.MZ


def _assert_trees_equal(a, b, what=""):
    """Bit-exact tree compare; quantized leaves compare their deltas."""
    for (pa, la), (_, lb) in zip(
            jax.tree_util.tree_leaves_with_path(a, is_leaf=is_quantized),
            jax.tree_util.tree_leaves_with_path(b, is_leaf=is_quantized)):
        va = la.delta if is_quantized(la) else la
        vb = lb.delta if is_quantized(lb) else lb
        if va is None and vb is None:
            continue
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb),
            err_msg=f"{what}{jax.tree_util.keystr(pa)}")


def _fresh_base(cfg, quant="none"):
    base = build_model(cfg).init(jax.random.PRNGKey(0))
    return quantize_tree(base, with_delta=True) if quant == "int8" else base


# ---------------------------------------------------------------------------
# acceptance: B=8 batched step vs 8 sequential Trainer runs (atol=0)


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if GOLDEN[a]["family"] == "dense"])
def test_b8_engine_bit_equals_8_trainers_int8(arch, tmp_path):
    """The PR's acceptance bar: an 8-user batched TrainEngine on the
    int8 base reproduces 8 sequential Trainer runs bit-for-bit --
    per-step losses, final per-user deltas, and byte-identical
    replay-log files."""
    cfg = get_config(arch).reduced()
    U, T = 8, 3
    users = [f"u{i}" for i in range(U)]
    batches = {u: ctg.make_batches(cfg, u, T) for u in users}

    # -- 8 lone sequential Trainer runs, each logging its replay -------
    trainer_params, trainer_losses = {}, {}
    f32 = _fresh_base(cfg)
    for u in users:
        tcfg = TrainerConfig(
            estimator="fused", update="sgd", quant="int8", mezo=MZ,
            n_steps=T, seed=derive_user_seed(ctg.ENGINE_SEED, u),
            ckpt_dir=str(tmp_path / f"seq-{u}"), snapshot_every=10 ** 6,
            log_every=10 ** 6)
        tr = Trainer(cfg, tcfg, iter(batches[u]), log_fn=lambda s: None)
        trainer_params[u] = tr.train(
            params=jax.tree.map(jnp.copy, f32))
        trainer_losses[u] = list(tr.losses)

    # -- one batched engine, all 8 users resident ----------------------
    store = AdapterStore(_fresh_base(cfg, "int8"), mezo_cfg=MZ)
    eng = TrainEngine(cfg, store, n_slots=U, seed=ctg.ENGINE_SEED,
                      log_dir=str(tmp_path / "engine-logs"))
    for u in users:
        eng.submit(TrainJob(user=u, batches=batches[u], n_steps=T))
    results = {r.user: r for r in eng.run()}

    assert eng.stats.dispatches == T          # 8 users/step, not 8 loops
    for u in users:
        assert results[u].losses == trainer_losses[u], u
        _assert_trees_equal(store.materialize(u), trainer_params[u],
                            what=f"{u}:")
        with open(tmp_path / "engine-logs" / f"{u}.jsonl") as f:
            engine_log = f.read()
        with open(tmp_path / f"seq-{u}" / "replay.jsonl") as f:
            trainer_log = f.read()
        assert engine_log == trainer_log, f"{u}: replay-log lines differ"


@pytest.mark.parametrize("arch", ARCHS)
def test_engine_bit_equals_sequential_strategy(arch):
    """Every pinned family (f32 arm): batched engine vs lone sequential
    strategy runs with the derived per-user seeds, atol=0."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    strat = build_strategy("fused", "sgd")
    base = _fresh_base(cfg)
    results, store = ctg.run_engine(arch, "none")
    for r in results:
        st = strat.init_state(jax.tree.map(jnp.copy, base), MZ)
        us = np.uint32(derive_user_seed(ctg.ENGINE_SEED, r.user))
        bs = ctg.make_batches(cfg, r.user, ctg.T)
        for t in range(ctg.T):
            seed = zrng.fold_seed(jnp.uint32(us), t)
            st, aux = strat.step(model.loss, st, bs[t], seed, MZ)
            assert r.losses[t] == float(np.asarray(aux.loss)), \
                f"{r.user} step {t}"
            np.testing.assert_array_equal(
                np.asarray(r.records[t]["gs"], np.float32),
                np.asarray(aux.gs, np.float32).reshape(-1),
                err_msg=f"{r.user} step {t}")
        _assert_trees_equal(store.materialize(r.user), st.params,
                            what=f"{r.user}:")


# ---------------------------------------------------------------------------
# golden pin


@pytest.mark.parametrize("arch", ARCHS)
def test_engine_losses_and_gs_pinned(arch):
    """The fixed scenario's per-user losses/gs match the pinned capture
    (tests/golden/train_engine.json)."""
    want = GOLDEN[arch]["arms"]
    for arm, pin in want.items():
        results, _ = ctg.run_engine(arch, "int8" if arm == "int8"
                                    else "none")
        got_losses = {r.user: r.losses for r in results}
        for u, losses in pin["losses"].items():
            np.testing.assert_allclose(got_losses[u], losses, rtol=1e-6,
                                       err_msg=f"{arm}/{u}")
        got_gs = {r.user: [rec["gs"] for rec in r.records]
                  for r in results}
        for u, gs in pin["gs"].items():
            np.testing.assert_allclose(got_gs[u], gs, rtol=1e-6,
                                       err_msg=f"{arm}/{u}")


# ---------------------------------------------------------------------------
# slot table: staggered admission, ragged targets, eviction, resume


def _dense_cfg():
    arch = next((a for a in ARCHS if GOLDEN[a]["family"] == "dense"), None)
    if arch is None:
        pytest.skip("dense family filtered out by REPRO_FAMILY")
    return get_config(arch).reduced()


def test_staggered_admission_ragged_targets():
    """More jobs than slots with ragged n_steps: early finishers free
    slots mid-flight, queued jobs admit without draining the batch, and
    every user's trajectory still bit-matches a lone run."""
    cfg = _dense_cfg()
    model = build_model(cfg)
    strat = build_strategy("fused", "sgd")
    base = _fresh_base(cfg)
    targets = {"u0": 2, "u1": 4, "u2": 1, "u3": 3, "u4": 2}
    store = AdapterStore(jax.tree.map(jnp.copy, base), mezo_cfg=MZ)
    eng = TrainEngine(cfg, store, n_slots=2, seed=ctg.ENGINE_SEED)
    for u, n in targets.items():
        eng.submit(TrainJob(user=u, batches=ctg.make_batches(cfg, u, n),
                            n_steps=n))
    results = {r.user: r for r in eng.run()}
    assert eng.stats.finished == len(targets)
    assert eng.stats.user_steps == sum(targets.values())
    for u, n in targets.items():
        st = strat.init_state(jax.tree.map(jnp.copy, base), MZ)
        us = np.uint32(derive_user_seed(ctg.ENGINE_SEED, u))
        bs = ctg.make_batches(cfg, u, n)
        for t in range(n):
            st, aux = strat.step(model.loss, st, bs[t],
                                 zrng.fold_seed(jnp.uint32(us), t), MZ)
        assert results[u].losses[-1] == float(np.asarray(aux.loss)), u
        _assert_trees_equal(store.materialize(u), st.params, what=f"{u}:")


def test_mid_flight_eviction_then_resume_bit_exact():
    """Evict a user mid-run, resubmit: the resumed job starts at the
    flushed step and the final state bit-matches never having been
    evicted (slot was meanwhile reused by another user -- stale-seed
    regression guard)."""
    cfg = _dense_cfg()
    model = build_model(cfg)
    strat = build_strategy("fused", "sgd")
    base = _fresh_base(cfg)
    T = 5
    store = AdapterStore(jax.tree.map(jnp.copy, base), mezo_cfg=MZ)
    eng = TrainEngine(cfg, store, n_slots=1, seed=ctg.ENGINE_SEED)
    eng.submit(TrainJob(user="ua", batches=ctg.make_batches(cfg, "ua", T),
                        n_steps=T))
    eng.step(); eng.step()
    res = eng.evict("ua")
    assert res.evicted and res.n_steps == 2 and len(res.records) == 2
    # another user trains in the freed slot before ua returns
    eng.submit(TrainJob(user="ub", batches=ctg.make_batches(cfg, "ub", 2),
                        n_steps=2))
    eng.submit(TrainJob(user="ua", batches=ctg.make_batches(cfg, "ua", T),
                        n_steps=T))
    results = {(r.user, r.jid): r for r in eng.run()}
    resumed = results[("ua", 2)]
    assert resumed.start_step == 2 and resumed.n_steps == T
    assert len(resumed.records) == T

    st = strat.init_state(jax.tree.map(jnp.copy, base), MZ)
    us = np.uint32(derive_user_seed(ctg.ENGINE_SEED, "ua"))
    bs = ctg.make_batches(cfg, "ua", T)
    for t in range(T):
        st, _ = strat.step(model.loss, st, bs[t],
                           zrng.fold_seed(jnp.uint32(us), t), MZ)
    _assert_trees_equal(store.materialize("ua"), st.params, what="ua:")


def test_crash_recovery_from_replay_log(tmp_path):
    """Fault injection: flush to the per-user log file, lose the engine
    AND the store, rebuild both from the log alone, finish the job --
    final params bit-equal an uninterrupted run's."""
    cfg = _dense_cfg()
    model = build_model(cfg)
    strat = build_strategy("fused", "sgd")
    base = _fresh_base(cfg)
    T, log_dir = 5, str(tmp_path / "logs")

    store1 = AdapterStore(jax.tree.map(jnp.copy, base), mezo_cfg=MZ)
    eng1 = TrainEngine(cfg, store1, n_slots=1, seed=ctg.ENGINE_SEED,
                       log_dir=log_dir)
    eng1.submit(TrainJob(user="u", batches=ctg.make_batches(cfg, "u", T),
                         n_steps=T))
    eng1.step(); eng1.step(); eng1.step()
    eng1.evict("u")
    del eng1, store1                       # "crash": only the log survives

    store2 = AdapterStore(jax.tree.map(jnp.copy, base), mezo_cfg=MZ)
    store2.load("u", os.path.join(log_dir, "u.jsonl"))
    assert len(store2.records("u")) == 3      # the pre-crash flush survived
    eng2 = TrainEngine(cfg, store2, n_slots=1, seed=ctg.ENGINE_SEED,
                       log_dir=log_dir)
    eng2.submit(TrainJob(user="u", batches=ctg.make_batches(cfg, "u", T),
                         n_steps=T))
    (res,) = eng2.run()
    assert res.start_step == 3 and res.n_steps == T

    st = strat.init_state(jax.tree.map(jnp.copy, base), MZ)
    us = np.uint32(derive_user_seed(ctg.ENGINE_SEED, "u"))
    bs = ctg.make_batches(cfg, "u", T)
    for t in range(T):
        st, _ = strat.step(model.loss, st, bs[t],
                           zrng.fold_seed(jnp.uint32(us), t), MZ)
    _assert_trees_equal(store2.materialize("u"), st.params, what="u:")
    # the log file now carries the full uninterrupted-equivalent stream
    from repro.checkpoint.replay_log import ReplayLog
    assert [r["step"] for r in ReplayLog.read(
        os.path.join(log_dir, "u.jsonl"))] == list(range(T))


# ---------------------------------------------------------------------------
# admission guardrails


def test_duplicate_user_stays_queued():
    """One slot per user at a time: a second job for a resident user
    waits for the first to finish, then resumes from its records."""
    cfg = _dense_cfg()
    store = AdapterStore(_fresh_base(cfg), mezo_cfg=MZ)
    eng = TrainEngine(cfg, store, n_slots=4, seed=ctg.ENGINE_SEED)
    eng.submit(TrainJob(user="u", batches=ctg.make_batches(cfg, "u", 2),
                        n_steps=2))
    eng.submit(TrainJob(user="u", batches=ctg.make_batches(cfg, "u", 4),
                        n_steps=4))
    results = eng.run()
    assert [(r.jid, r.start_step, r.n_steps) for r in results] == \
        [(0, 0, 2), (1, 2, 4)]


def test_seed_collision_raises():
    cfg = _dense_cfg()
    store = AdapterStore(_fresh_base(cfg), mezo_cfg=MZ)
    eng = TrainEngine(cfg, store, n_slots=2, seed=ctg.ENGINE_SEED)
    eng.submit(TrainJob(user="a", batches=ctg.make_batches(cfg, "a", 2),
                        n_steps=2, seed=123))
    eng.submit(TrainJob(user="b", batches=ctg.make_batches(cfg, "b", 2),
                        n_steps=2, seed=123))
    with pytest.raises(ValueError, match="seed collision"):
        eng.run()


def test_unknown_estimator_rejected():
    cfg = _dense_cfg()
    store = AdapterStore(_fresh_base(cfg), mezo_cfg=MZ)
    with pytest.raises(ValueError, match="unknown direction estimator") as ei:
        TrainEngine(cfg, store, estimator="sequential")
    assert "fused" in str(ei.value) and "vmapdir" in str(ei.value)


def test_update_rule_mismatch_rejected():
    cfg = _dense_cfg()
    store = AdapterStore(_fresh_base(cfg), mezo_cfg=MZ)  # sgd store
    with pytest.raises(ValueError, match="update rule"):
        TrainEngine(cfg, store, update="momentum")


def test_target_already_met_finishes_without_steps():
    cfg = _dense_cfg()
    store = AdapterStore(_fresh_base(cfg), mezo_cfg=MZ)
    eng = TrainEngine(cfg, store, n_slots=1, seed=ctg.ENGINE_SEED)
    eng.submit(TrainJob(user="u", batches=ctg.make_batches(cfg, "u", 2),
                        n_steps=2))
    eng.run()
    eng.submit(TrainJob(user="u", batches=ctg.make_batches(cfg, "u", 2),
                        n_steps=2))           # target already met
    (res,) = eng.run()
    assert res.start_step == 2 and res.n_steps == 2 and res.losses == []


def test_delta_only_user_not_resumable():
    """A user known only by a lossy int8 delta cannot seed a fine-tune
    resume -- the store must refuse, not silently fork the trajectory."""
    cfg = _dense_cfg()
    store = AdapterStore(_fresh_base(cfg), mezo_cfg=MZ)
    store.put_delta("u", [])                  # content irrelevant
    with pytest.raises(ValueError, match="lossy"):
        store.materialize_state("u")


# ---------------------------------------------------------------------------
# the one-call wrapper


def test_train_multi_tenant_wrapper():
    cfg = _dense_cfg()
    jobs = [TrainJob(user=f"u{i}",
                     batches=ctg.make_batches(cfg, f"u{i}", 2), n_steps=2)
            for i in range(3)]
    engine, results = train_multi_tenant(
        cfg, jobs, n_slots=2, seed=ctg.ENGINE_SEED, mezo_cfg=MZ,
        quant="int8", log_fn=lambda s: None)
    assert engine.stats.finished == 3
    assert sorted(r.user for r in results) == ["u0", "u1", "u2"]
    assert all(len(r.losses) == 2 for r in results)

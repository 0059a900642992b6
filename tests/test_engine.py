"""ZO engine: the estimator×update matrix, the default step, TrainState
checkpointing (momentum / Adam resume), straggler-mask renormalization
across all combinations, replay parity, loss buffering, chunked stepping."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import (MezoConfig, add_scaled_z, build_strategy,
                        estimator_names, fold_seed, replay_update,
                        update_rule_names)
from repro.core.engine import TrainState
from repro.data.synthetic import lm_batches
from repro.optim.adam import AdamConfig
from repro.runtime import Trainer, TrainerConfig

ALL_COMBOS = [(e, u) for e in ("vmapdir", "fused")
              for u in ("sgd", "momentum", "stale-sgd")]

CFG = get_config("qwen3-4b").reduced()


def _batches(start=0):
    return lm_batches(4, 16, CFG.vocab, seed=3, start_step=start)


@pytest.fixture
def quad():
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (8, 8)), "b": jnp.zeros((8,))}
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    y = x @ (jnp.eye(8) * 0.1)

    def loss_fn(p, batch, perturb=None):
        # fused estimator support: materialize the ctx's z transiently --
        # bit-identical to add_scaled_z on this plain dict tree
        if perturb is not None:
            p = perturb.materialize(p)
        xx, yy = batch
        return jnp.mean((xx @ p["w"] + p["b"] - yy) ** 2)

    return params, (x, y), loss_fn


# ---------------------------------------------------------------------------
# registry


def test_registry_names_and_errors():
    assert set(estimator_names()) == {"vmapdir", "fused"}
    assert set(update_rule_names()) == {"sgd", "stale-sgd", "momentum"}
    for est, upd in ALL_COMBOS:
        # cached singletons: jit caches keyed on the strategy stay warm
        assert build_strategy(est, upd) is build_strategy(est, upd)
    for est in ("vmap", "walk"):
        with pytest.raises(ValueError, match="fused") as ei:
            build_strategy(est, "sgd")
        assert "vmapdir" in str(ei.value)
    with pytest.raises(ValueError, match="momentum"):
        build_strategy("fused", "adamw")


def test_unknown_trainer_optimizer_lists_strategies():
    with pytest.raises(ValueError) as ei:
        Trainer(CFG, TrainerConfig(optimizer="sgd"), iter(()))
    msg = str(ei.value)
    assert "'mezo'" in msg and "'adam'" in msg


def test_cli_flags_reach_strategy():
    from repro.launch.train import build_argparser, make_trainer
    args = build_argparser().parse_args(
        ["--arch", "opt-1.3b", "--reduced", "--estimator", "fused",
         "--update", "momentum", "--steps", "2", "--batch", "2",
         "--seq", "8"])
    assert make_trainer(args).strategy.name == "fused+momentum"
    args = build_argparser().parse_args(
        ["--arch", "opt-1.3b", "--reduced", "--update", "momentum"])
    assert make_trainer(args).strategy.name == "fused+momentum"
    with pytest.raises(SystemExit):
        build_argparser().parse_args(["--optimizer", "mezo-momentum"])


def _default_trainer_strategy():
    return Trainer(CFG, TrainerConfig(), iter(())).strategy


def _default_cli_strategy():
    from repro.launch.train import build_argparser, make_trainer
    args = build_argparser().parse_args(
        ["--arch", "opt-1.3b", "--reduced", "--steps", "2"])
    return make_trainer(args).strategy


@pytest.mark.parametrize("make", [_default_trainer_strategy,
                                  _default_cli_strategy],
                         ids=["trainer_config", "launch_train"])
def test_default_zo_step_is_fused_sgd(make):
    """With no estimator/update named, the ZO step is the one every
    benchmark cell runs: the same cached strategy as
    build_strategy("fused", "sgd")."""
    assert make() is build_strategy("fused", "sgd")


# ---------------------------------------------------------------------------
# the full 2×3 matrix: constructible, descends, matches the SPSA estimate


@pytest.mark.parametrize("est,upd", ALL_COMBOS)
def test_matrix_constructible_and_descends(quad, est, upd):
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=4, momentum=0.9,
                     momentum_window=4)
    strat = build_strategy(est, upd)
    assert strat.name == f"{est}+{upd}"
    state = strat.init_state(jax.tree.map(jnp.copy, params), cfg)
    losses = []
    for t in range(60):
        state, aux = strat.step(loss_fn, state, batch, jnp.uint32(t), cfg)
        losses.append(float(aux.loss))
    assert int(state.step) == 60
    assert losses[-1] < 0.9 * losses[0]


def _materialized_gs(loss_fn, params, batch, seed, cfg):
    """The materialized SPSA estimate's projected gradients g_k, computed
    eagerly on explicit theta ± eps*z_k copies, and the losses seen."""
    eps = jnp.float32(cfg.eps)
    gs, ls = [], []
    for k in range(cfg.n_directions):
        s = fold_seed(jnp.uint32(seed), jnp.uint32(k))
        lp = loss_fn(add_scaled_z(params, s, eps, dist=cfg.dist), batch)
        lm = loss_fn(add_scaled_z(params, s, -eps, dist=cfg.dist), batch)
        gs.append(float((lp - lm) / (2.0 * eps)))
        ls += [float(lp), float(lm)]
    return np.asarray(gs, np.float32), np.asarray(ls, np.float32)


@pytest.mark.parametrize("est,upd", ALL_COMBOS)
def test_matrix_matches_spsa_estimate(quad, est, upd):
    """One step of every combination is theta - lr * w * mean_k g_k z_k
    (w = 1-beta for a fresh momentum window, 1 for sgd), checked in two
    parts:

    * the update algebra, from the step's own g_k (``aux.gs``), at f32
      tolerance;
    * ``aux.gs`` against the materialized estimate's g_k. The jitted
      step and the eager estimate reduce each loss in their own order.
      A mean of N positive terms in f32 is within log2(N) * u * L of the
      exact value (pairwise summation, u = 2^-24), so the two sides' l+
      may differ by twice that, their l- too, and g_k = (l+ - l-) /
      (2 eps) by 2 * 2 * log2(N) * u * L / (2 eps).
    """
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=4, momentum=0.9,
                     momentum_window=4)
    strat = build_strategy(est, upd)
    state, aux = strat.step(
        loss_fn, strat.init_state(jax.tree.map(jnp.copy, params), cfg),
        batch, jnp.uint32(3), cfg)

    w = (1.0 - cfg.momentum) if upd == "momentum" else 1.0
    want = params
    for k, g in enumerate(np.asarray(aux.gs)):
        want = add_scaled_z(want, fold_seed(jnp.uint32(3), jnp.uint32(k)),
                            -cfg.lr * w * g / cfg.n_directions)
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)

    g_mat, losses = _materialized_gs(loss_fn, params, batch, 3, cfg)
    n = batch[1].size                      # terms in each loss's mean
    u = np.finfo(np.float32).eps / 2
    bound = 2 * 2 * np.log2(n) * u * np.abs(losses).max() / (2 * cfg.eps)
    np.testing.assert_allclose(np.asarray(aux.gs), g_mat, rtol=0,
                               atol=bound)


def test_estimators_share_replay_log_bit_exact(quad):
    """vmapdir and fused produce the same (seed, gs) record, and
    replay_update reconstructs each one's params bit-for-bit."""
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=3)
    outs = {}
    for est in ("vmapdir", "fused"):
        strat = build_strategy(est, "sgd")
        state, aux = strat.step(
            loss_fn, strat.init_state(jax.tree.map(jnp.copy, params), cfg),
            batch, jnp.uint32(11), cfg)
        outs[est] = (state.params, aux)
    np.testing.assert_allclose(np.asarray(outs["vmapdir"][1].gs),
                               np.asarray(outs["fused"][1].gs),
                               rtol=1e-6, atol=1e-7)
    for est, (p_new, aux) in outs.items():
        p_rep = replay_update(jax.tree.map(jnp.copy, params), aux.seed,
                              aux.gs, cfg)
        for a, b in zip(jax.tree.leaves(p_new), jax.tree.leaves(p_rep)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# straggler direction_mask: unbiased mean over survivors, all combinations


@pytest.mark.parametrize("est,upd", ALL_COMBOS)
def test_direction_mask_unbiased_over_survivors(quad, est, upd):
    """Masking directions 2,3 of a K=4 step must equal an unmasked K=2
    step (same folded seeds, renormalized mean) for every estimator ×
    update combination."""
    params, batch, loss_fn = quad
    mk = lambda k: MezoConfig(eps=1e-3, lr=1e-2, n_directions=k,
                              momentum=0.9, momentum_window=3)
    strat = build_strategy(est, upd)
    cfg4, cfg2 = mk(4), mk(2)
    mask = jnp.array([1.0, 1.0, 0.0, 0.0])
    sa, _ = strat.step(
        loss_fn, strat.init_state(jax.tree.map(jnp.copy, params), cfg4),
        batch, jnp.uint32(5), cfg4, mask)
    sb, _ = strat.step(
        loss_fn, strat.init_state(jax.tree.map(jnp.copy, params), cfg2),
        batch, jnp.uint32(5), cfg2)
    np.testing.assert_allclose(np.asarray(sa.params["w"]),
                               np.asarray(sb.params["w"]),
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# satellite: replay_update weight-decay f32 parity (regression)


def test_weight_decay_replay_parity(quad):
    """Live step and replay must use the identical f32 lr*weight_decay
    coefficient -- a Python-float coefficient on the replay side used to
    break bit-exactness."""
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=2, weight_decay=0.37)
    strat = build_strategy("vmapdir", "sgd")
    s1, aux = strat.step(
        loss_fn, strat.init_state(jax.tree.map(jnp.copy, params), cfg),
        batch, jnp.uint32(9), cfg)
    p2 = replay_update(jax.tree.map(jnp.copy, params), aux.seed, aux.gs,
                       cfg)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# TrainState checkpointing: momentum history and Adam moments survive


def test_manager_snapshots_full_trainstate(tmp_path, quad):
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=2, momentum=0.9,
                     momentum_window=3)
    strat = build_strategy("vmapdir", "momentum")
    mgr = CheckpointManager(str(tmp_path), mezo_cfg=cfg, snapshot_every=2,
                            update_rule=strat.update)
    state = strat.init_state(jax.tree.map(jnp.copy, params), cfg)
    for t in range(5):
        state, aux = strat.step(loss_fn, state, batch, jnp.uint32(t), cfg)
        mgr.on_step(t, state, aux)
    like = strat.init_state(jax.tree.map(jnp.copy, params), cfg)
    restored, nxt = CheckpointManager(
        str(tmp_path), mezo_cfg=cfg, snapshot_every=2,
        update_rule=strat.update).restore(like)
    assert nxt == 5
    assert int(restored.step) == 5
    # the whole state roundtrips: params AND the momentum window
    # (snapshot@4 + replay of nothing; the window is non-zero by now)
    assert float(jnp.abs(restored.opt["gs"]).sum()) > 0
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_masked_step_replay_from_log_is_exact(tmp_path, quad):
    """Straggler masks are recorded in the replay log, so a log-tail
    replay renormalizes over the same survivors the live update did."""
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=4)
    strat = build_strategy("vmapdir", "sgd")
    mgr = CheckpointManager(str(tmp_path), mezo_cfg=cfg, snapshot_every=3,
                            update_rule=strat.update)
    state = strat.init_state(jax.tree.map(jnp.copy, params), cfg)
    masks = [None, jnp.array([1.0, 0.0, 1.0, 0.0]), None,
             jnp.array([1.0, 1.0, 1.0, 0.0]), jnp.array([0.0, 1.0, 1.0, 1.0])]
    for t, m in enumerate(masks):
        state, aux = strat.step(loss_fn, state, batch, jnp.uint32(t), cfg, m)
        mgr.on_step(t, state, aux, direction_mask=m)
    # snapshot@3 + replay of the masked step 4 must match the live state
    like = strat.init_state(jax.tree.map(jnp.copy, params), cfg)
    restored, nxt = CheckpointManager(
        str(tmp_path), mezo_cfg=cfg, snapshot_every=3,
        update_rule=strat.update).restore(like)
    assert nxt == 5
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_without_update_rule_refuses_stateful_opt(tmp_path, quad):
    """A momentum-run checkpoint restored by a manager with no
    update_rule must raise instead of silently replaying with sgd."""
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=2, momentum=0.9,
                     momentum_window=3)
    strat = build_strategy("vmapdir", "momentum")
    mgr = CheckpointManager(str(tmp_path), mezo_cfg=cfg, snapshot_every=2,
                            update_rule=strat.update)
    state = strat.init_state(jax.tree.map(jnp.copy, params), cfg)
    for t in range(4):   # snapshot@2, log tail 3 -> replay needed
        state, aux = strat.step(loss_fn, state, batch, jnp.uint32(t), cfg)
        mgr.on_step(t, state, aux)
    like = strat.init_state(jax.tree.map(jnp.copy, params), cfg)
    with pytest.raises(ValueError, match="update_rule"):
        CheckpointManager(str(tmp_path), mezo_cfg=cfg,
                          snapshot_every=2).restore(like)


def test_adam_rejects_estimator_update_flags():
    with pytest.raises(ValueError, match="adam"):
        Trainer(CFG, TrainerConfig(optimizer="adam", estimator="vmapdir"),
                iter(()))


def test_momentum_crash_resume_matches_uninterrupted(tmp_path):
    """Fault injection: snapshot@8 + momentum-rule replay of step 9 +
    live steps 10..11 must equal the uninterrupted run -- i.e. the
    truncated-replay window survives the crash (the old per-step
    functions silently reset it)."""
    n = 12
    mz = MezoConfig(eps=1e-2, lr=1e-2, n_directions=2, momentum=0.9,
                    momentum_window=4)
    tc_a = TrainerConfig(update="momentum", mezo=mz, n_steps=n,
                         ckpt_dir=str(tmp_path / "a"), snapshot_every=4,
                         log_every=100)
    p_full = Trainer(CFG, tc_a, _batches()).train()

    tc_b = TrainerConfig(update="momentum", mezo=mz, n_steps=n,
                         ckpt_dir=str(tmp_path / "b"), snapshot_every=4,
                         log_every=100)
    with pytest.raises(RuntimeError):
        Trainer(CFG, tc_b, _batches()).train(fail_at=10)
    tr_c = Trainer(CFG, tc_b, _batches(start=10))
    p_res = tr_c.train()

    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_adam_crash_resume_restores_moments(tmp_path):
    """Adam degrades to snapshot-only recovery, but the snapshot now
    carries the full TrainState: resuming must restore mu/nu/count
    instead of silently re-initializing them to zero."""
    n = 8
    tc_a = TrainerConfig(optimizer="adam", adam=AdamConfig(lr=3e-3),
                         n_steps=n, ckpt_dir=str(tmp_path / "a"),
                         snapshot_every=1, log_every=100)
    p_full = Trainer(CFG, tc_a, _batches()).train()

    tc_b = TrainerConfig(optimizer="adam", adam=AdamConfig(lr=3e-3),
                         n_steps=n, ckpt_dir=str(tmp_path / "b"),
                         snapshot_every=1, log_every=100)
    with pytest.raises(RuntimeError):
        Trainer(CFG, tc_b, _batches()).train(fail_at=5)
    tr_c = Trainer(CFG, tc_b, _batches(start=5))
    p_res = tr_c.train()

    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_res)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# satellite: buffered loss host-sync must not change the history


def test_loss_history_identical_across_log_every():
    tiny = get_config("opt-1.3b").reduced(n_layers=1, d_model=32, d_ff=64,
                                          vocab=64)

    def run(log_every):
        tc = TrainerConfig(estimator="vmapdir",
                           mezo=MezoConfig(eps=1e-2, lr=1e-2,
                                           n_directions=2),
                           n_steps=7, log_every=log_every)
        tr = Trainer(tiny, tc, lm_batches(2, 8, tiny.vocab, seed=0),
                     log_fn=lambda s: None)
        tr.train()
        return tr.losses

    every_step, buffered = run(1), run(1000)
    assert len(buffered) == 7
    assert every_step == buffered


# ---------------------------------------------------------------------------
# chunked multi-step scan


def test_run_chunk_matches_stepwise(quad):
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=2)
    strat = build_strategy("vmapdir", "sgd")
    base, n = jnp.uint32(42), 5

    state = strat.init_state(jax.tree.map(jnp.copy, params), cfg)
    for i in range(n):
        state, _ = strat.step(loss_fn, state, batch, fold_seed(base, i),
                              cfg)

    stacked = jax.tree.map(lambda x: jnp.stack([x] * n), batch)
    cstate = strat.init_state(jax.tree.map(jnp.copy, params), cfg)
    cstate, auxs = strat.run_chunk(loss_fn, cstate, stacked, base, cfg)

    assert int(cstate.step) == n
    assert auxs.gs.shape == (n, cfg.n_directions)
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(cstate.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_run_chunk_resumes_step_counter(quad):
    """Chained chunks derive per-step seeds from the carried step counter,
    so two 3-step chunks equal one 6-step chunk."""
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=1)
    strat = build_strategy("vmapdir", "sgd")
    base = jnp.uint32(7)
    stack = lambda k: jax.tree.map(lambda x: jnp.stack([x] * k), batch)

    s6 = strat.init_state(jax.tree.map(jnp.copy, params), cfg)
    s6, _ = strat.run_chunk(loss_fn, s6, stack(6), base, cfg)

    s33 = strat.init_state(jax.tree.map(jnp.copy, params), cfg)
    s33, _ = strat.run_chunk(loss_fn, s33, stack(3), base, cfg)
    s33, _ = strat.run_chunk(loss_fn, s33, stack(3), base, cfg)

    assert int(s33.step) == int(s6.step) == 6
    for a, b in zip(jax.tree.leaves(s6.params),
                    jax.tree.leaves(s33.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

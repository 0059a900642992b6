"""MeZO core: descent, estimator quality, replay, direction masks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MezoConfig, add_scaled_z, build_strategy,
                        replay_update, spsa_gradient_estimate)


@pytest.fixture
def quad():
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (8, 8)), "b": jnp.zeros((8,))}
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    y = x @ (jnp.eye(8) * 0.1)

    def loss_fn(p, batch, perturb=None):
        # fused estimator support: materialize the ctx's z transiently
        if perturb is not None:
            p = perturb.materialize(p)
        xx, yy = batch
        return jnp.mean((xx @ p["w"] + p["b"] - yy) ** 2)

    return params, (x, y), loss_fn


def test_descent(quad, zo_step):
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=4)
    p = jax.tree.map(jnp.copy, params)
    losses = []
    for t in range(150):
        p, aux = zo_step(loss_fn, p, batch, jnp.uint32(t), cfg)
        losses.append(float(aux.loss))
    assert losses[-1] < 0.6 * losses[0]


def test_perturb_restore_roundtrip(quad):
    params, _, _ = quad
    p1 = add_scaled_z(params, jnp.uint32(3), 0.5)
    p2 = add_scaled_z(p1, jnp.uint32(3), -0.5)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_spsa_correlates_with_gradient(quad):
    params, batch, loss_fn = quad
    g_true = jax.grad(loss_fn)(params, batch)
    cfg = MezoConfig(eps=1e-3, n_directions=64)
    g_est = spsa_gradient_estimate(loss_fn, params, batch, jnp.uint32(3),
                                   cfg)
    cos = jnp.vdot(g_true["w"], g_est["w"]) / (
        jnp.linalg.norm(g_true["w"]) * jnp.linalg.norm(g_est["w"]))
    assert float(cos) > 0.3


@pytest.mark.parametrize("dist", ["rademacher", "gaussian"])
def test_both_distributions_descend(quad, zo_step, dist):
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=4, dist=dist)
    p = jax.tree.map(jnp.copy, params)
    l0 = float(loss_fn(p, batch))
    for t in range(100):
        p, aux = zo_step(loss_fn, p, batch, jnp.uint32(t), cfg)
    assert float(aux.loss) < l0


def test_replay_reproduces_update(quad, zo_step):
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=3)
    p1, aux = zo_step(loss_fn, jax.tree.map(jnp.copy, params), batch,
                      jnp.uint32(11), cfg, estimator="vmapdir")
    p2 = replay_update(jax.tree.map(jnp.copy, params), aux.seed, aux.gs, cfg)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_direction_mask_drops_and_renormalizes(quad, zo_step):
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=4)
    cfg1 = MezoConfig(eps=1e-3, lr=1e-2, n_directions=1)
    mask = jnp.array([1.0, 0.0, 0.0, 0.0])
    pa, _ = zo_step(loss_fn, jax.tree.map(jnp.copy, params), batch,
                    jnp.uint32(5), cfg, mask, estimator="vmapdir")
    # masked 4-direction step with only dir 0 == 1-direction step
    pb, _ = zo_step(loss_fn, jax.tree.map(jnp.copy, params), batch,
                    jnp.uint32(5), cfg1, estimator="vmapdir")
    np.testing.assert_allclose(np.asarray(pa["w"]), np.asarray(pb["w"]),
                               rtol=1e-6)


def test_weight_decay_shrinks(quad, zo_step):
    params, batch, loss_fn = quad
    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=1, weight_decay=0.5)
    p, _ = zo_step(loss_fn, jax.tree.map(jnp.copy, params), batch,
                   jnp.uint32(0), cfg)
    assert float(jnp.linalg.norm(p["w"])) < float(
        jnp.linalg.norm(params["w"])) + 0.1


def test_kernel_path_matches_jnp_path(quad, zo_step):
    params, batch, loss_fn = quad
    # pad w to kernel-eligible shape
    params = {"w": jax.random.normal(jax.random.PRNGKey(2), (256, 256))}

    def loss2(p, b, perturb=None):
        if perturb is not None:
            p = perturb.materialize(p)
        return jnp.sum(p["w"] ** 2) * 1e-4

    cfg_a = MezoConfig(eps=1e-3, lr=1e-2, use_kernel=False)
    cfg_b = MezoConfig(eps=1e-3, lr=1e-2, use_kernel=True)
    pa, _ = zo_step(loss2, jax.tree.map(jnp.copy, params), None,
                    jnp.uint32(0), cfg_a)
    pb, _ = zo_step(loss2, jax.tree.map(jnp.copy, params), None,
                    jnp.uint32(0), cfg_b)
    np.testing.assert_allclose(np.asarray(pa["w"]), np.asarray(pb["w"]),
                               rtol=1e-5, atol=1e-6)


def test_momentum_step_descends_and_beats_plain(quad, zo_step):
    params, batch, loss_fn = quad
    cfg_m = MezoConfig(eps=1e-3, lr=5e-3, n_directions=2, momentum=0.9,
                       momentum_window=8)
    cfg_p = MezoConfig(eps=1e-3, lr=5e-3, n_directions=2)

    strat = build_strategy("fused", "momentum")
    state = strat.init_state(jax.tree.map(jnp.copy, params), cfg_m)
    losses_m = []
    for t in range(120):
        state, aux = strat.step(loss_fn, state, batch, jnp.uint32(t), cfg_m)
        losses_m.append(float(aux.loss))

    p_p = jax.tree.map(jnp.copy, params)
    losses_p = []
    for t in range(120):
        p_p, aux = zo_step(loss_fn, p_p, batch, jnp.uint32(t), cfg_p)
        losses_p.append(float(aux.loss))

    assert losses_m[-1] < losses_m[0]
    # momentum should at least match plain ZO-SGD on a quadratic
    assert np.mean(losses_m[-10:]) <= np.mean(losses_p[-10:]) * 1.25


def test_momentum_beta0_matches_plain(quad, zo_step):
    """beta=0 momentum == plain step (weights collapse to newest-only)."""
    params, batch, loss_fn = quad
    cfg0 = MezoConfig(eps=1e-3, lr=1e-2, n_directions=2, momentum=0.0,
                      momentum_window=4)
    strat = build_strategy("vmapdir", "momentum")
    sa, _ = strat.step(
        loss_fn, strat.init_state(jax.tree.map(jnp.copy, params), cfg0),
        batch, jnp.uint32(3), cfg0)
    pb, _ = zo_step(loss_fn, jax.tree.map(jnp.copy, params), batch,
                    jnp.uint32(3), cfg0, estimator="vmapdir")
    np.testing.assert_allclose(np.asarray(sa.params["w"]),
                               np.asarray(pb["w"]),
                               rtol=1e-6, atol=1e-7)

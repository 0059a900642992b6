"""MeZO: memory-efficient zeroth-order fine-tuning (PocketLLM's method).

Implements SPSA (Spall 1992) with MeZO's seed-replay storage trick
(Malladi et al. 2024), as adopted by PocketLLM for on-device fine-tuning:

    z ~ RNG(seed)          (regenerated, never stored)
    l+ = L(theta + eps z);  l- = L(theta - eps z)
    g  = (l+ - l-) / (2 eps)
    theta <- theta - lr * g * z

The step itself is :func:`repro.core.engine.build_strategy`'s
estimator×update pairing (``init_state`` / ``step``). Its
:class:`MezoAux` record's ``(seed, gs)`` pair is exactly what the
replay-log checkpointer persists (~12 bytes/step/direction) -- see
repro/checkpoint/replay_log.py. This module keeps the standalone replay
and analysis helpers.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core import rng as zrng
from repro.core.engine import LossFn, MezoConfig, PyTree, SGD
from repro.core.perturb import add_scaled_z


def replay_update(params: PyTree, seed, gs, cfg: MezoConfig,
                  direction_mask=None):
    """Re-apply a logged step's update from its (seed, gs) record.

    This is the recovery path of the replay-log checkpointer: a crashed
    worker reconstructs theta_t from theta_0 and the scalar log at memory
    bandwidth, with zero forward passes. It *is* the engine's sgd update
    rule -- identical f32 arithmetic to the live step (including the f32
    ``lr * weight_decay`` coefficient), hence bit-exact replay.
    ``direction_mask`` is the logged straggler mask of the step, so
    replay renormalizes over the same surviving directions.
    """
    params, _ = SGD.update_fn(params, {}, seed, gs, direction_mask, cfg)
    return params


def spsa_gradient_estimate(loss_fn: LossFn, params: PyTree, batch: Any,
                           seed, cfg: MezoConfig) -> PyTree:
    """Materialized SPSA gradient estimate: mean_k g_k * z_k.

    Only for tests / analysis -- production paths never materialize z.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    eps = jnp.float32(cfg.eps)

    def est(k):
        s = zrng.fold_seed(seed, k)
        lp = loss_fn(add_scaled_z(params, s, eps, dist=cfg.dist), batch)
        lm = loss_fn(add_scaled_z(params, s, -eps, dist=cfg.dist), batch)
        g = (lp - lm) / (2.0 * eps)
        zero = jax.tree.map(jnp.zeros_like, params)
        return add_scaled_z(zero, s, g, dist=cfg.dist)

    grads = [est(jnp.uint32(k)) for k in range(cfg.n_directions)]
    return jax.tree.map(lambda *xs: sum(xs) / len(xs), *grads)

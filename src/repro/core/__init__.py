"""Core: PocketLLM's derivative-free (zeroth-order) fine-tuning engine."""

from repro.core.engine import (DirectionEvaluator, MezoAux, MezoConfig,
                               TrainState, UpdateRule, ZOStrategy,
                               build_strategy, estimator_names,
                               momentum_history_init, update_rule_names)
from repro.core.mezo import replay_update, spsa_gradient_estimate
from repro.core.perturb import add_scaled_z, dot_with_z, leaf_salts
from repro.core.perturb_ctx import PerturbCtx
from repro.core.rng import fold_seed, gaussian_field, rademacher_field, z_field

__all__ = [
    "DirectionEvaluator", "MezoAux", "MezoConfig", "PerturbCtx",
    "TrainState", "UpdateRule", "ZOStrategy", "build_strategy",
    "estimator_names", "momentum_history_init", "replay_update",
    "spsa_gradient_estimate", "update_rule_names", "add_scaled_z",
    "dot_with_z", "leaf_salts", "fold_seed", "gaussian_field",
    "rademacher_field", "z_field",
]

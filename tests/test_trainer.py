"""Trainer integration: fault injection + resume, straggler + adam arms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import MezoConfig
from repro.data.synthetic import lm_batches
from repro.optim.adam import AdamConfig
from repro.runtime import StragglerPolicy, Trainer, TrainerConfig

CFG = get_config("qwen3-4b").reduced()


def _batches(start=0):
    return lm_batches(4, 16, CFG.vocab, seed=3, start_step=start)


def test_crash_resume_matches_uninterrupted(tmp_path):
    n = 14
    mz = MezoConfig(eps=1e-2, lr=1e-2, n_directions=2)

    tc_a = TrainerConfig(optimizer="mezo", mezo=mz, n_steps=n,
                         ckpt_dir=str(tmp_path / "a"), snapshot_every=5,
                         log_every=100)
    tr_a = Trainer(CFG, tc_a, _batches())
    p_full = tr_a.train()

    tc_b = TrainerConfig(optimizer="mezo", mezo=mz, n_steps=n,
                         ckpt_dir=str(tmp_path / "b"), snapshot_every=5,
                         log_every=100)
    with pytest.raises(RuntimeError):
        Trainer(CFG, tc_b, _batches()).train(fail_at=9)
    # fresh process resumes from snapshot@5 + replay 6..8
    tr_c = Trainer(CFG, tc_b, _batches(start=9))
    p_res = tr_c.train()

    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_adam_arm_descends():
    tc = TrainerConfig(optimizer="adam", adam=AdamConfig(lr=3e-3),
                       n_steps=15, log_every=100)
    tr = Trainer(CFG, tc, _batches())
    tr.train()
    assert tr.losses[-1] < tr.losses[0]


def test_straggler_policy_masks():
    pol = StragglerPolicy(n_directions=4, redundancy=2)
    m = pol.mask()
    assert m.shape == (6,)
    assert m.sum() == 6  # no latency info yet -> keep all
    pol.observe([1, 1, 1, 1, 1, 50.0])
    m = pol.mask()
    assert m[5] == 0          # slow direction dropped
    assert m.sum() <= 4       # fastest-K selection
    m2 = pol.mask(slow=[0])
    assert m2[0] == 0


def test_straggler_trainer_arm():
    tc = TrainerConfig(estimator="vmapdir",
                       mezo=MezoConfig(eps=1e-2, lr=1e-2, n_directions=2),
                       n_steps=3, straggler_redundancy=2, log_every=100)
    tr = Trainer(CFG, tc, _batches())
    tr.train()
    assert len(tr.losses) == 3


def test_unknown_quant_mode_raises_with_supported_list():
    """Mirrors the estimator/update registry errors: an unknown --quant
    value must raise a ValueError naming the supported modes."""
    with pytest.raises(ValueError, match=r"int4.*none.*int8"):
        Trainer(CFG, TrainerConfig(quant="int4"), _batches())


def test_quant_rejects_gradient_baseline():
    with pytest.raises(ValueError, match="frozen"):
        Trainer(CFG, TrainerConfig(optimizer="adam", quant="int8"),
                _batches())


def test_quantized_trainer_arm_runs_and_freezes_base():
    """--quant int8 end to end on the fused strategy: losses flow, the
    int8 values stay bit-frozen, the update stream lands in the deltas."""
    from repro.optim.quant import is_quantized, quantize_tree

    tc = TrainerConfig(quant="int8",
                       mezo=MezoConfig(eps=1e-2, lr=1e-2, n_directions=2),
                       n_steps=3, log_every=100)
    tr = Trainer(CFG, tc, _batches())
    trained = tr.train()
    assert len(tr.losses) == 3
    q0 = quantize_tree(tr.model.init(jax.random.PRNGKey(tc.seed)))
    moved = 0.0
    for a, b in zip(jax.tree.leaves(trained, is_leaf=is_quantized),
                    jax.tree.leaves(q0, is_leaf=is_quantized)):
        if is_quantized(a):
            np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
            np.testing.assert_array_equal(np.asarray(a.scale),
                                          np.asarray(b.scale))
            moved += float(np.abs(np.asarray(a.delta)).sum())
    assert moved > 0.0

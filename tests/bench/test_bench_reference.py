"""The reference in bench/reference against the system under test at a
reduced size, on the CPU: the same hash, the same forward, the same ZO
step and the same adapter replay."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import traffic as T
from bench.harness.weights import leaves_of, make_leaf, make_params
from bench.reference import forward, zhash, zo
from repro.configs import get_config
from repro.core import rng as zrng
from repro.core.engine import MezoConfig, build_strategy
from repro.models import build_model

BIG = 2 ** 31 + 99


def small(arch):
    return dataclasses.replace(
        get_config(arch), n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=128, vocab=96, max_seq=32, dtype="float32", attn_chunk=0)


@pytest.fixture(scope="module")
def opt():
    cfg = small("opt-1.3b")
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return cfg, model, shapes, make_params(shapes, 7)


def test_weights_leaf_alone_equals_leaf_in_tree(opt):
    cfg, _, shapes, params = opt
    key = jax.random.PRNGKey(7)
    for i, ((path, s), leaf) in enumerate(
            zip(leaves_of(shapes), jax.tree_util.tree_leaves(params))):
        alone = make_leaf(key, i, path, tuple(s.shape),
                          jnp.dtype(s.dtype).name)
        np.testing.assert_array_equal(np.asarray(alone), np.asarray(leaf))


@pytest.mark.parametrize("shape", [(5,), (8, 128), (3, 16, 24)])
def test_hash_is_the_replay_log_hash(shape):
    seed = zrng.fold_seed(jnp.uint32(12345), 3)
    assert int(zhash.direction_seed(12345, 3)) == int(seed)
    want = zrng.z_field(seed, zrng.leaf_salt("blocks/attn/wq/w"), shape)
    got = zhash.z_full(seed, "blocks/attn/wq/w", shape)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if len(shape) == 3:
        got1 = zhash.z_layer(seed, "blocks/attn/wq/w", 2, shape[1:])
        np.testing.assert_array_equal(np.asarray(got1), np.asarray(want[2]))
    rows = jnp.asarray([0, 4, 1])
    full = zrng.z_field(seed, zrng.leaf_salt("embed/tok"), (6, 8))
    np.testing.assert_array_equal(
        np.asarray(zhash.z_rows(seed, "embed/tok", rows, 8)),
        np.asarray(full[rows]))


def test_opt_logits_match_the_program(opt):
    cfg, model, _, params = opt
    toks = jnp.asarray(T.rng(BIG, 9).integers(0, cfg.vocab, (2, 12)),
                       jnp.int32)
    want, _ = model.forward(params, {"tokens": toks})
    got = forward.lm_logits(params, toks, n_heads=cfg.n_heads)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_roberta_logits_and_loss_match_the_program():
    cfg = small("roberta-large")
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = make_params(shapes, 3)
    batch = {k: jnp.asarray(v) for k, v in T.train_batch(
        {"task": "cls", "batch": 4, "seq": 10, "n_classes": 2}, cfg.vocab,
        BIG, 0).items()}
    want, _ = model.forward(params, batch)
    got = forward.cls_logits(params, batch["tokens"], n_heads=cfg.n_heads)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        float(forward.loss(params, batch, task="cls", n_heads=cfg.n_heads)),
        float(model.loss(params, batch)), rtol=1e-5)


def test_zo_steps_match_the_fused_step(opt):
    """Three reference steps against the program's fused ZO-SGD step
    (jnp path, f32): perturbed losses, gs and the final weights."""
    cfg, model, shapes, _ = opt
    tr = {"task": "lm", "batch": 2, "seq": 8}
    mezo = MezoConfig(lr=1e-3, eps=1e-3)
    strat = build_strategy("fused", "sgd")
    state = strat.init_state(make_params(shapes, 11), mezo)
    batches = [{k: jnp.asarray(v) for k, v in
                T.train_batch(tr, cfg.vocab, BIG, s).items()}
               for s in (1, 2, 3)]
    prog_gs = []
    for step, batch in zip((1, 2, 3), batches):
        seed = zrng.fold_seed(jnp.uint32(77), step)
        state, aux = strat.step(model.loss, state, batch, seed, mezo)
        prog_gs.append(float(aux.gs[0]))
    ref = zo.zo_steps(make_params(shapes, 11), batches, 77, 1, mezo.lr,
                      mezo.eps, "lm", cfg.n_heads)
    np.testing.assert_allclose(ref["gs"], prog_gs, rtol=1e-3, atol=1e-3)
    for a, b in zip(jax.tree_util.tree_leaves(ref["params"]),
                    jax.tree_util.tree_leaves(state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_replay_matches_the_adapter_store(opt):
    from repro.serve.adapters import AdapterStore

    cfg, _, shapes, _ = opt
    recs = T.adapter_records({"records": 3, "lr": 1e-3, "eps": 1e-3,
                              "gs_sigma": 5.0}, BIG, 0)
    store = AdapterStore(make_params(shapes, 5), mezo_cfg=MezoConfig())
    store.put("u", recs)
    want = store.materialize("u")
    got = zo.replay(make_params(shapes, 5), recs)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

"""Fused perturbed-forward path: kernel parity, ctx/salt consistency,
the fused step's equivalence with the materialized vmapdir step."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import MezoConfig, PerturbCtx, add_scaled_z, replay_update
from repro.core import rng as zrng
from repro.data.synthetic import lm_batches, sst2_batches
from repro.kernels import ops, ref
from repro.models import build_model

KEY = jax.random.PRNGKey(0)

# non-square and non-divisible shapes on purpose
MM_SHAPES = [(8, 128, 128), (16, 96, 160), (32, 100, 60), (7, 33, 130)]


def _tiny_model(**overrides):
    kw = dict(n_layers=2, d_model=64, d_ff=128, vocab=128)
    kw.update(overrides)
    cfg = get_config("opt-1.3b").reduced(**kw)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v)
             for k, v in next(lm_batches(2, 16, cfg.vocab, seed=1)).items()}
    return model, params, batch


# ---------------------------------------------------------------------------
# kernel-level parity


@pytest.mark.parametrize("dist", ["rademacher", "gaussian"])
@pytest.mark.parametrize("mkn", MM_SHAPES)
def test_zo_matmul_interpret_matches_ref(mkn, dist):
    m, k, n = mkn
    x = jax.random.normal(KEY, (m, k), jnp.float32) * 0.1
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (k, n), jnp.float32) * 0.1
    got = ops.zo_matmul(x, w, 7, 123, 0.01, dist=dist)
    want = ref.zo_matmul_ref(x, w, jnp.uint32(7), 123, 0.01, dist=dist)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dist", ["rademacher", "gaussian"])
def test_zo_matmul_prehashed_matches_stacked_slice(dist):
    """Kernel tiles for a layer-slice of a scan-stacked (L, K, N) leaf must
    reproduce the full leaf's z-field (the fused-forward RNG contract)."""
    seed, salt, (L, k, n) = jnp.uint32(11), 4242, (3, 32, 256)
    full_z = zrng.z_field(seed, salt, (L, k, n), dist=dist)
    x = jax.random.normal(KEY, (8, k), jnp.float32) * 0.1
    w = jax.random.normal(jax.random.fold_in(KEY, 2), (k, n), jnp.float32) * 0.1
    for layer in (0, L - 1):
        base = zrng.fold_leading(zrng.leaf_base(seed, salt), layer)
        got = ops.zo_matmul(x, w, base, 0, 0.5, dist=dist,
                            prime_offset=1, prehashed=True)
        want = x @ (w + 0.5 * full_z[layer])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dist", ["rademacher", "gaussian"])
def test_rank0_slice_matches_stacked_vector(dist):
    """Slicing a stacked (L,) leaf down to a scalar must reproduce the
    full vector field -- no extra avalanche on the rank-0 path."""
    seed, salt = jnp.uint32(11), 4242
    full = zrng.z_field(seed, salt, (5,), dist=dist)
    for layer in range(5):
        base = zrng.fold_leading(zrng.leaf_base(seed, salt), layer)
        got = zrng.z_field(None, 0, (), dist=dist, prime_offset=1, base=base)
        np.testing.assert_array_equal(np.asarray(full[layer]),
                                      np.asarray(got))


@pytest.mark.parametrize("dist", ["rademacher", "gaussian"])
def test_z_rows_matches_field_gather(dist):
    seed, salt = jnp.uint32(5), 99
    full = zrng.z_field(seed, salt, (64, 48), dist=dist)
    ids = jnp.array([[0, 63, 7], [5, 5, 31]])
    got = zrng.z_rows(zrng.leaf_base(seed, salt), ids, 48, dist=dist)
    np.testing.assert_array_equal(np.asarray(full)[np.asarray(ids)],
                                  np.asarray(got))


# ---------------------------------------------------------------------------
# ctx-forward consistency: the fused loss must see exactly the z-fields
# add_scaled_z applies to the stacked parameter tree (salt/path contract)


@pytest.mark.parametrize("dist", ["rademacher", "gaussian"])
@pytest.mark.parametrize("arch", ["opt-1.3b", "qwen3-4b",
                                  "granite-moe-1b-a400m"])
def test_ctx_forward_matches_perturbed_params(arch, dist):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v)
             for k, v in next(lm_batches(2, 16, cfg.vocab, seed=1)).items()}
    seed, eps = jnp.uint32(9), jnp.float32(1e-3)
    la = float(model.loss(add_scaled_z(params, seed, eps, dist=dist), batch))
    lb = float(model.loss(params, batch,
                          perturb=PerturbCtx(seed=seed, coeff=eps, dist=dist)))
    np.testing.assert_allclose(la, lb, rtol=1e-6, atol=1e-6)


def test_ctx_forward_matches_perturbed_params_cls():
    cfg = get_config("roberta-large").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v)
             for k, v in next(sst2_batches(2, 16, cfg.vocab, seed=1)).items()}
    seed, eps = jnp.uint32(4), jnp.float32(1e-3)
    la = float(model.loss(add_scaled_z(params, seed, eps), batch))
    lb = float(model.loss(params, batch,
                          perturb=PerturbCtx(seed=seed, coeff=eps)))
    np.testing.assert_allclose(la, lb, rtol=1e-6, atol=1e-6)


def test_ctx_kernel_path_matches_jnp_path():
    """use_kernel=True routes MXU-aligned projections through the Pallas
    kernel (interpret mode here) -- values must match the jnp fallback."""
    model, params, batch = _tiny_model(d_model=128, d_ff=256, vocab=256)
    ctx = PerturbCtx(seed=jnp.uint32(3), coeff=jnp.float32(1e-3))
    lj = float(model.loss(params, batch, perturb=ctx))
    lk = float(model.loss(params, batch,
                          perturb=dataclasses.replace(ctx, use_kernel=True)))
    np.testing.assert_allclose(lj, lk, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# step-level equivalence


def test_fused_step_matches_vmapdir_tight(zo_step):
    model, params, batch = _tiny_model()
    mcfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=3)
    pf, auxf = zo_step(model.loss, jax.tree.map(jnp.copy, params), batch,
                       jnp.uint32(7), mcfg)
    pv, auxv = zo_step(model.loss, jax.tree.map(jnp.copy, params), batch,
                       jnp.uint32(7), mcfg, estimator="vmapdir")
    np.testing.assert_allclose(np.asarray(auxf.gs), np.asarray(auxv.gs),
                               rtol=1e-6, atol=1e-7)
    for a, b in zip(jax.tree.leaves(pf), jax.tree.leaves(pv)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_fused_step_replay_bit_exact(zo_step):
    """Fused updates apply to the unwritten base point, so the (seed, gs)
    replay log reconstructs them bit-for-bit (checkpointer contract)."""
    model, params, batch = _tiny_model()
    mcfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=2)
    pf, aux = zo_step(model.loss, jax.tree.map(jnp.copy, params), batch,
                      jnp.uint32(13), mcfg)
    pr = replay_update(jax.tree.map(jnp.copy, params), aux.seed, aux.gs, mcfg)
    for a, b in zip(jax.tree.leaves(pf), jax.tree.leaves(pr)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_step_descends(zo_step):
    model, params, batch = _tiny_model()
    mcfg = MezoConfig(eps=1e-2, lr=5e-3, n_directions=4)
    p = jax.tree.map(jnp.copy, params)
    losses = []
    for t in range(30):
        p, aux = zo_step(model.loss, p, batch, jnp.uint32(t), mcfg)
        losses.append(float(aux.loss))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("arch", ["rwkv6-7b", "jamba-v0.1-52b",
                                  "whisper-base"])
def test_fused_step_matches_vmapdir_all_families(arch, zo_step):
    """The block-registry runtime threads PerturbCtx through every
    family, so the fused estimator's projected gradients match vmapdir's
    (which perturbs the whole tree) on hybrid / rwkv6 / encdec too --
    the three families that used to take a transient materialize copy."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v)
             for k, v in next(lm_batches(2, 16, cfg.vocab, seed=1)).items()}
    if cfg.family == "encdec":
        batch["enc_embeds"] = jax.random.normal(
            jax.random.PRNGKey(3), (2, cfg.enc_len, cfg.d_model))
    mcfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=2)
    pf, auxf = zo_step(model.loss, jax.tree.map(jnp.copy, params), batch,
                       jnp.uint32(5), mcfg)
    pv, auxv = zo_step(model.loss, jax.tree.map(jnp.copy, params), batch,
                       jnp.uint32(5), mcfg, estimator="vmapdir")
    np.testing.assert_allclose(np.asarray(auxf.gs), np.asarray(auxv.gs),
                               rtol=1e-6, atol=1e-7)

"""Hypothesis property tests on system invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional test dep (pip install -e .[test]); tier-1 runs without")

from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import rng as zrng
from repro.core.engine import _direction_coeffs
from repro.models.sharding import fit_spec
from repro.models.transformer import softmax_xent
from repro.optim.compression import int8_dequantize, int8_quantize
from jax.sharding import Mesh, PartitionSpec as P

SETTINGS = dict(max_examples=25, deadline=None)


@given(seed=st.integers(0, 2**32 - 1), salt=st.integers(0, 2**32 - 1),
       rows=st.integers(1, 40), cols=st.integers(1, 40),
       r0=st.integers(0, 1000), c0=st.integers(0, 1000))
@settings(**SETTINGS)
def test_rng_tile_equals_slice(seed, salt, rows, cols, r0, c0):
    """Any tile with offsets == the same slice of a bigger field."""
    full = zrng.z_field(jnp.uint32(seed), salt, (r0 + rows, c0 + cols))
    tile = zrng.z_field(jnp.uint32(seed), salt, (rows, cols),
                        offsets=(r0, c0))
    np.testing.assert_array_equal(np.asarray(full[r0:, c0:]),
                                  np.asarray(tile))


@given(k=st.integers(1, 16), lr=st.floats(1e-6, 1.0),
       data=st.data())
@settings(**SETTINGS)
def test_direction_coeffs_sum_preserved(k, lr, data):
    """Masked renormalization keeps |sum coeffs| == lr (unbiased scale)."""
    mask = np.array(data.draw(st.lists(st.sampled_from([0.0, 1.0]),
                                       min_size=k, max_size=k)), np.float32)
    coeffs = np.asarray(_direction_coeffs(k, jnp.float32(lr), mask))
    if mask.sum() == 0:
        return
    np.testing.assert_allclose(-coeffs.sum(), lr, rtol=1e-5)
    assert (coeffs[mask == 0] == 0).all()


@given(b=st.integers(1, 4), s=st.integers(1, 8), v=st.integers(2, 30),
       seed=st.integers(0, 1000))
@settings(**SETTINGS)
def test_softmax_xent_matches_numpy(b, s, v, seed):
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((b, s, v)).astype(np.float32) * 3
    targets = rng.integers(0, v, (b, s))
    got = float(softmax_xent(jnp.asarray(logits), jnp.asarray(targets)))
    ex = np.exp(logits - logits.max(-1, keepdims=True))
    p = ex / ex.sum(-1, keepdims=True)
    want = -np.log(np.take_along_axis(p, targets[..., None], -1)).mean()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@given(seed=st.integers(0, 1000), scale=st.floats(1e-3, 1e3))
@settings(**SETTINGS)
def test_int8_compression_bounded_error(seed, scale):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal((64,)).astype(np.float32) * scale)
    q, s = int8_quantize(g)
    back = int8_dequantize(q, s)
    # error bounded by one quantization bucket
    assert float(jnp.max(jnp.abs(back - g))) <= float(s) + 1e-6


@given(dim=st.integers(1, 64), nd=st.integers(1, 3),
       data=st.data())
@settings(max_examples=25, deadline=None)
def test_fit_spec_always_divides(dim, nd, data):
    devs = np.array(jax.devices() * 16)[:16].reshape(4, 4)
    mesh = Mesh(devs, ("data", "model"))
    axes = data.draw(st.lists(st.sampled_from([None, "data", "model"]),
                              min_size=nd, max_size=nd, unique_by=id))
    shape = tuple(data.draw(st.integers(1, 64)) for _ in range(nd))
    spec = fit_spec(shape, P(*axes), mesh)
    sizes = {"data": 4, "model": 4}
    for d, entry in enumerate(spec):
        if entry is None:
            continue
        names = (entry,) if isinstance(entry, str) else entry
        prod = int(np.prod([sizes[n] for n in names]))
        assert shape[d] % prod == 0


# ---- multi-tenant seed isolation (train.engine) ---------------------------

from repro.train.engine import derive_user_seed  # noqa: E402

_name = st.text(st.characters(min_codepoint=33, max_codepoint=126),
                min_size=1, max_size=12)


@given(engine_seed=st.integers(0, 2**32 - 1), data=st.data())
@settings(**SETTINGS)
def test_user_leaf_z_streams_pairwise_distinct(engine_seed, data):
    """No two (user, leaf) pairs in a batch draw the same z stream:
    per-user base seeds fold per-leaf salts through the avalanche hash,
    so every (user, leaf) gets its own counter stream."""
    from hypothesis import assume
    users = data.draw(st.lists(_name, min_size=2, max_size=4, unique=True))
    leaves = data.draw(st.lists(_name, min_size=2, max_size=3, unique=True))
    assume(len({zrng.leaf_salt(u) for u in users}) == len(users))
    assume(len({zrng.leaf_salt(p) for p in leaves}) == len(leaves))
    streams = {}
    for u in users:
        us = jnp.uint32(derive_user_seed(engine_seed, u))
        for path in leaves:
            z = np.asarray(zrng.z_field(
                zrng.fold_seed(us, 0), zrng.leaf_salt(path), (2, 32)))
            streams[(u, path)] = z.tobytes()
    assert len(set(streams.values())) == len(streams), \
        "two (user, leaf) pairs drew identical z streams"


@given(engine_seed=st.integers(0, 2**32 - 1), data=st.data())
@settings(**SETTINGS)
def test_derive_user_seed_injective_over_users(engine_seed, data):
    """Distinct users (distinct crc32 salts) get distinct base seeds."""
    from hypothesis import assume
    users = data.draw(st.lists(_name, min_size=2, max_size=8, unique=True))
    assume(len({zrng.leaf_salt(u) for u in users}) == len(users))
    seeds = {derive_user_seed(engine_seed, u) for u in users}
    assert len(seeds) == len(users)


@given(engine_seed=st.integers(0, 2**32 - 1),
       step=st.integers(0, 10_000), data=st.data())
@settings(**SETTINGS)
def test_per_step_seed_independent_of_slot_order(engine_seed, step, data):
    """Slot reassignment never reuses a stale seed: the per-step seed is
    a pure function of (engine_seed, user, step), so any permutation of
    users across the slot table computes the same per-user seeds."""
    users = data.draw(st.lists(_name, min_size=2, max_size=4, unique=True))
    perm = data.draw(st.permutations(users))
    base = {u: np.uint32(derive_user_seed(engine_seed, u)) for u in users}
    direct = {u: int(np.asarray(zrng.fold_seed(
        jnp.uint32(base[u]), jnp.uint32(step)))) for u in users}
    # recompute through a permuted "slot table" (vectorized, as step() does)
    tbl = np.asarray([base[u] for u in perm], np.uint32)
    folded = np.asarray(zrng.fold_seed(
        tbl, np.full(len(perm), step, np.uint32)), np.uint32)
    for slot, u in enumerate(perm):
        assert int(folded[slot]) == direct[u]


@given(seed=st.integers(0, 2**32 - 1), n_slots=st.integers(1, 6),
       k=st.integers(1, 16), temp=st.floats(0.1, 3.0),
       steps=st.integers(1, 4))
@settings(**SETTINGS)
def test_seeded_sampling_reproducible_across_step_keys(seed, n_slots, k,
                                                       temp, steps):
    """The engine's sampling chain -- one key split per step, fold_in per
    slot, top-k draw -- is a pure function of (seed, step, slot): replays
    reproduce bit-identically, and per-slot streams stay distinct."""
    from repro.serve import sampling

    rng = np.random.default_rng(seed % 2**16)
    logits = jnp.asarray(rng.normal(size=(n_slots, 32)).astype(np.float32))

    def chain():
        key = jax.random.PRNGKey(seed)
        toks = []
        for _ in range(steps):
            key, ks = sampling.step_keys(key, n_slots)
            toks.append(np.asarray(
                sampling.sample_topk(ks, logits, k, temp)))
        return np.stack(toks), np.asarray(key)

    t1, k1 = chain()
    t2, k2 = chain()
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(k1, k2)
    assert t1.shape == (steps, n_slots)
    assert np.all((t1 >= 0) & (t1 < 32))

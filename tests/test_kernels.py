"""Per-kernel shape/dtype sweeps asserting allclose vs the jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import zo_perturb as zp

KEY = jax.random.PRNGKey(0)

ADD_SHAPES = [(8, 128), (128, 128), (256, 512), (384, 640), (100, 300)]
MM_SHAPES = [(8, 128, 128), (128, 256, 128), (64, 384, 256), (32, 100, 60)]


@pytest.mark.parametrize("dist", ["rademacher", "gaussian"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", ADD_SHAPES)
def test_zo_add_sweep(shape, dtype, dist):
    w = jax.random.normal(KEY, shape, jnp.float32).astype(dtype)
    got = ops.zo_add(w, 42, 777, 0.125, dist=dist)
    want = ref.zo_add_ref(w, jnp.uint32(42), 777, 0.125, dist=dist)
    assert got.dtype == w.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dist", ["rademacher", "gaussian"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mkn", MM_SHAPES)
def test_zo_matmul_sweep(mkn, dtype, dist):
    m, k, n = mkn
    x = (jax.random.normal(KEY, (m, k), jnp.float32) * 0.1).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(KEY, 1), (k, n), jnp.float32)
         * 0.1).astype(dtype)
    got = ops.zo_matmul(x, w, 7, 123, 0.01, dist=dist)
    want = ref.zo_matmul_ref(x, w, jnp.uint32(7), 123, 0.01, dist=dist)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_zo_add_block_invariance():
    """Result must not depend on the BlockSpec tiling."""
    w = jax.random.normal(KEY, (256, 256), jnp.float32)
    a = ops.zo_add(w, 3, 9, 1.0, block=(256, 256))
    b = ops.zo_add(w, 3, 9, 1.0, block=(64, 128))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mkn,blocks_a,blocks_b", [
    ((128, 256, 128), (128, 256, 128), (64, 64, 64)),
    # the picked tiling (whole dims here) against 128-cubed tiles
    ((256, 512, 384), None, (128, 128, 128)),
])
def test_zo_matmul_block_invariance(mkn, blocks_a, blocks_b):
    m, k, n = mkn
    if blocks_a is None:
        assert zp.matmul_blocks(m, k, n, jnp.float32, jnp.float32) \
            != (128, 128, 128)
    x = jax.random.normal(KEY, (m, k), jnp.float32) * 0.1
    w = jax.random.normal(jax.random.fold_in(KEY, 2), (k, n),
                          jnp.float32) * 0.1
    a = ops.zo_matmul(x, w, 5, 6, 0.5, blocks=blocks_a)
    b = ops.zo_matmul(x, w, 5, 6, 0.5, blocks=blocks_b)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


# ---- block picker ---------------------------------------------------------

BF, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8
# (m, k, n, x dtype, w dtype, scaled): the train cells' projections --
# opt-1.3b at 8 x 512 rows (bf16, and its int8 base), roberta-large at
# 64 x 128 rows (f32) -- and ragged shapes for the whole-dim fallback
PICK_CASES = [(4096, k, n, xd, wd, sc)
              for k, n in [(2048, 2048), (2048, 8192), (8192, 2048)]
              for xd, wd, sc in [(BF, BF, False), (F32, F32, False),
                                 (BF, I8, True)]] + \
             [(8192, k, n, F32, F32, False)
              for k, n in [(1024, 1024), (1024, 4096), (4096, 1024)]] + \
             [(200, 2048, 8192, BF, BF, False), (32, 100, 60, F32, F32, False),
              (100, 384, 300, BF, BF, False)]


@pytest.mark.parametrize("highest", [False, True])
@pytest.mark.parametrize("m,k,n,xd,wd,scaled", PICK_CASES)
def test_matmul_blocks_tile_and_fit(m, k, n, xd, wd, scaled, highest):
    bm, bk, bn = zp.matmul_blocks(m, k, n, xd, wd, scaled, highest)
    for dim, b, align in ((m, bm, 8), (k, bk, 128), (n, bn, 128)):
        assert dim % b == 0
        assert b % align == 0 or b == dim
    assert zp.matmul_vmem_bytes(bm, bk, bn, xd, wd, scaled, highest) \
        <= zp.VMEM_BUDGET


@pytest.mark.parametrize("m,k,n,xd,highest,want", [
    (4096, 2048, 2048, BF, False, (1024, 512, 1024)),
    (4096, 2048, 8192, BF, False, (1024, 512, 1024)),
    (4096, 8192, 2048, BF, False, (1024, 512, 1024)),
    (8192, 1024, 4096, F32, False, (1024, 512, 512)),
    (8192, 1024, 4096, F32, True, (1024, 256, 512)),
])
def test_matmul_blocks_at_cell_shapes(m, k, n, xd, highest, want):
    """The tiles the train cells' projections run with: f32 operands
    need twice the VMEM of bf16 ones, and a dot at HIGHEST (roberta-large
    runs its f32 products so) more again, so they get smaller tiles."""
    assert zp.matmul_blocks(m, k, n, xd, xd, highest=highest) == want


def _grid(fn, *args):
    """The grid of the one pallas_call inside ``fn(*args)``."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn.params["grid_mapping"].grid
            for sub in jax.core.jaxprs_in_params(eqn.params):
                got = find(sub)
                if got is not None:
                    return got
        return None
    return find(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("xd,wd,scaled,precision", [
    (BF, BF, False, None), (F32, F32, False, None), (BF, I8, True, None),
    (F32, F32, False, "highest")])
def test_zo_matmul_users_picks_scalar_blocks(xd, wd, scaled, precision):
    """Each lane of the user-batched kernel tiles as a lone call does (its
    bit-equality rests on that), at opt-1.3b's FFN shape; both pick for
    the precision JAX's default asks of their dot."""
    u, m, k, n = 4, 4096, 2048, 8192
    sd = jax.ShapeDtypeStruct
    sc = sd((n,), F32) if scaled else None
    with jax.default_matmul_precision(precision):
        one = _grid(lambda x, w, s: zp.zo_matmul(x, w, 1, 0, 0.5, scale=s),
                    sd((m, k), xd), sd((k, n), wd), sc)
        many = _grid(lambda x, w, s: zp.zo_matmul_users(
            x, w, jnp.arange(u, dtype=jnp.uint32), 0, jnp.ones(u),
            scale=s), sd((u, m, k), xd), sd((k, n), wd), sc)
    bm, bk, bn = zp.matmul_blocks(m, k, n, xd, wd, scaled,
                                  highest=precision == "highest")
    assert one == (m // bm, n // bn, k // bk)
    assert many == (u,) + one


def test_zero_coeff_is_identity_matmul():
    x = jax.random.normal(KEY, (64, 128), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (128, 64), jnp.float32)
    got = ops.zo_matmul(x, w, 0, 0, 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


# ---- user-batched variants (multi-tenant TrainEngine hot path) ------------

U_SEEDS = jnp.asarray([42, 7, 1000, 3], jnp.uint32)
U_COEFFS = jnp.asarray([0.125, -0.5, 0.01, 0.0], jnp.float32)


@pytest.mark.parametrize("dist", ["rademacher", "gaussian"])
def test_zo_matmul_users_bit_equals_scalar_loop(dist):
    """One user-batched dispatch == U lone zo_matmul calls, bit-exact
    (same block shapes => same per-lane accumulation order)."""
    u, m, k, n = len(U_SEEDS), 64, 128, 128
    x = jax.random.normal(KEY, (u, m, k), jnp.float32) * 0.1
    w = jax.random.normal(jax.random.fold_in(KEY, 4), (k, n),
                          jnp.float32) * 0.1
    got = ops.zo_matmul_users(x, w, U_SEEDS, 123, U_COEFFS, dist=dist)
    assert got.shape == (u, m, n)
    for i in range(u):
        want = ops.zo_matmul(x[i], w, U_SEEDS[i], 123, U_COEFFS[i],
                             dist=dist)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want),
                                      err_msg=f"user lane {i}")


def test_zo_matmul_users_int8_scale_equals_scalar_loop():
    """The quantized variant (shared int8 base + per-channel scales).

    The dequant expression ``w*scale + coeff*z`` has two multiplies, and
    XLA may contract the mul+add pair differently across the two
    (otherwise textually identical) kernels, so this path is pinned to
    one-ulp agreement rather than atol=0; the single-multiply f32 path
    above stays bit-exact.
    """
    u, m, k, n = len(U_SEEDS), 32, 128, 128
    x = jax.random.normal(KEY, (u, m, k), jnp.float32) * 0.1
    q = jax.random.randint(jax.random.fold_in(KEY, 5), (k, n), -127, 128,
                           jnp.int8)
    scale = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 6), (n,),
                                      jnp.float32)) * 0.01 + 1e-4
    got = ops.zo_matmul_users(x, q, U_SEEDS, 9, U_COEFFS, scale=scale)
    for i in range(u):
        want = ops.zo_matmul(x[i], q, U_SEEDS[i], 9, U_COEFFS[i],
                             scale=scale)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=1e-6, atol=1e-6,
                                   err_msg=f"user lane {i}")


@pytest.mark.parametrize("dist", ["rademacher", "gaussian"])
def test_zo_add_users_bit_equals_scalar_loop(dist):
    u, m, n = len(U_SEEDS), 128, 256
    w = jax.random.normal(jax.random.fold_in(KEY, 7), (u, m, n), jnp.float32)
    got = ops.zo_add_users(w, U_SEEDS, 77, U_COEFFS, dist=dist)
    for i in range(u):
        want = ops.zo_add(w[i], U_SEEDS[i], 77, U_COEFFS[i], dist=dist)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want),
                                      err_msg=f"user lane {i}")


def test_zo_matmul_users_prehashed_matches_raw():
    """The ctx hot path passes prehashed per-(user, leaf) bases; they
    must draw the same streams as the raw (seed, salt) form."""
    from repro.core import rng as zrng
    u, m, k, n = len(U_SEEDS), 32, 128, 128
    x = jax.random.normal(KEY, (u, m, k), jnp.float32) * 0.1
    w = jax.random.normal(jax.random.fold_in(KEY, 8), (k, n),
                          jnp.float32) * 0.1
    raw = ops.zo_matmul_users(x, w, U_SEEDS, 55, U_COEFFS)
    base = zrng.leaf_base(U_SEEDS, 55)
    pre = ops.zo_matmul_users(x, w, base, 0, U_COEFFS, prehashed=True)
    np.testing.assert_array_equal(np.asarray(raw), np.asarray(pre))

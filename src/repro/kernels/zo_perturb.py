"""Pallas TPU kernels for zeroth-order perturbation (the paper's hot spot).

PocketLLM's memory claim rests on never materializing the perturbation
``z``. On a phone that means regenerating from a CPU PRNG into registers;
the TPU-native rendering is to regenerate ``z`` *tiles in VMEM* inside the
kernel so z never exists in HBM at all:

  * ``zo_add_kernel``     -- W' = W + coeff * z(seed)   (perturb / fused
                             restore+update sweep of a MeZO step)
  * ``zo_matmul_kernel``  -- Y  = X @ (W + coeff * z(seed))  (perturbed
                             forward matmul: the perturbation is fused
                             into the MXU pipeline; W is read once and z
                             costs zero HBM bytes; W may be one layer of
                             a scan-stacked (L, K, N) leaf, read in place)

Both kernels also take an optional per-output-channel ``scale`` vector
marking W as an *int8 quantized base* (optim/quant.py): the tile is then
dequantized in VMEM (``w*scale``) before the perturbation/dot, so the
resident base stays ~1 byte/param in HBM and the dequant costs zero extra
memory traffic.

The RNG is the same counter-based avalanche hash as repro.core.rng, keyed
by absolute (row, col) coordinates, so full-array references in ref.py
reproduce kernel tiles bit-exactly for any BlockSpec tiling.

Block shapes: zo_matmul takes the largest (8, 128)-aligned tiles whose
VMEM footprint fits the chip's default scoped VMEM (:func:`matmul_blocks`,
decided from the call's shapes, dtypes and dot precision): each grid
step costs a fixed overhead, and z is regenerated once per M block, so
small tiles pay both many times over. zo_add is a pure VPU/memory kernel
and uses (256, 256) tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_U32 = jnp.uint32

# keep in sync with repro.core.rng (duplicated to keep the kernel module
# importable without touching jax device state through core's __init__)
_DIM_PRIMES = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D)


def _avalanche(x):
    x = x ^ (x >> 15)
    x = x * _U32(0x2C1B3C6D)
    x = x ^ (x >> 12)
    x = x * _U32(0x297A2D39)
    x = x ^ (x >> 15)
    return x


def _to_f32(x):
    return x.astype(jnp.int32).astype(jnp.float32)


def _tile_z(seed, salt, shape, row0, col0, dist: str,
            prime_offset: int = 0, prehashed: bool = False):
    """z tile of ``shape`` at absolute offset (row0, col0), f32.

    prehashed: ``seed`` is already ``avalanche(step_seed ^ salt)`` (plus any
    leading-coordinate folds -- core.rng.leaf_base / fold_leading), letting a
    2-D kernel tile reproduce the field of a slice of a stacked (L, m, n)
    leaf. prime_offset selects the per-dimension primes accordingly.
    """
    h = jnp.asarray(seed, _U32) if prehashed \
        else _avalanche(jnp.asarray(seed, _U32) ^ _U32(salt))
    ri = jax.lax.broadcasted_iota(_U32, shape, 0) + jnp.asarray(row0, _U32)
    ci = jax.lax.broadcasted_iota(_U32, shape, 1) + jnp.asarray(col0, _U32)
    h = _avalanche(h ^ (ri * _U32(_DIM_PRIMES[prime_offset])))
    h = _avalanche(h ^ (ci * _U32(_DIM_PRIMES[prime_offset + 1])))
    # Mosaic has no uint32 -> f32 cast; going through int32 is exact here
    # (h >> 31 is 0/1, h >> 8 < 2**24), so tiles stay bit-equal to core.rng
    if dist == "rademacher":
        return 1.0 - 2.0 * _to_f32(h >> 31)
    # gaussian (Box-Muller)
    h2 = _avalanche(h ^ _U32(0x68E31DA4))
    u1 = (_to_f32(h >> 8) + 1.0) * (1.0 / 16777216.0)
    u2 = _to_f32(h2 >> 8) * (1.0 / 16777216.0)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(6.283185307179586 * u2)


# ---------------------------------------------------------------------------
# W + coeff * z


def _sizes(dim: int, align: int) -> list[int]:
    """Every block size the TPU's tiling accepts along ``dim``: the
    multiples of ``align`` (8 for a sublane axis, 128 for a lane axis)
    that divide it, else the whole dim."""
    return [b for b in range(align, dim + 1, align) if dim % b == 0] or [dim]


def _pick(dim: int, want: int, align: int) -> int:
    """Largest of :func:`_sizes` that is <= want, else the whole dim."""
    return max((b for b in _sizes(dim, align) if b <= want), default=dim)


def _zo_add_kernel(seed_ref, coeff_ref, w_ref, o_ref, *, salt, bm, bn, dist,
                   prime_offset, prehashed):
    i, j = pl.program_id(0), pl.program_id(1)
    z = _tile_z(seed_ref[0], salt, (bm, bn), i * bm, j * bn, dist,
                prime_offset, prehashed)
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] = (w + coeff_ref[0] * z).astype(o_ref.dtype)


def _zo_add_q_kernel(seed_ref, coeff_ref, w_ref, s_ref, o_ref, *, salt, bm,
                     bn, dist, prime_offset, prehashed):
    """Quantized-base variant: W is int8, s the (1, bn) per-channel scale
    tile; dequant happens in VMEM, fused with the perturbation."""
    i, j = pl.program_id(0), pl.program_id(1)
    z = _tile_z(seed_ref[0], salt, (bm, bn), i * bm, j * bn, dist,
                prime_offset, prehashed)
    w = w_ref[...].astype(jnp.float32) * s_ref[...]
    o_ref[...] = (w + coeff_ref[0] * z).astype(o_ref.dtype)


# Scoped VMEM a Mosaic kernel may hold without asking for more
# (``vmem_limit_bytes``): the compiler's default on a TPU v5e.
VMEM_BUDGET = 16 << 20


def _dot_at_highest() -> bool:
    """Whether the perturbed matmul's f32 dot runs at HIGHEST, as JAX's
    default matmul precision asks where it is set (one bf16 MXU pass
    otherwise)."""
    return jax.config.jax_default_matmul_precision in ("highest", "float32")


def matmul_vmem_bytes(bm: int, bk: int, bn: int, x_dtype, w_dtype,
                      scaled: bool = False, highest: bool = False) -> int:
    """VMEM the perturbed matmul holds for one (bm, bk, bn) grid step:
    double-buffered x, w and output tiles (the output has x's dtype), the
    (1, bn) scale row padded to 8 sublanes when ``scaled``, and in f32
    the accumulator, x's tile cast for the dot and the perturbed weight
    tile (z is folded into it as it is made). A dot at HIGHEST splits x's
    tile into bf16 parts: four more f32 x tiles, by what the compiler
    reserves for a v5e."""
    xb, wb = jnp.dtype(x_dtype).itemsize, jnp.dtype(w_dtype).itemsize
    pipelined = bm * bk * xb + bk * bn * wb + bm * bn * xb \
        + (8 * bn * 4 if scaled else 0)
    x_tiles = 5 if highest else 1
    return 2 * pipelined + 4 * (bm * bn + x_tiles * bm * bk + bk * bn)


def matmul_blocks(m: int, k: int, n: int, x_dtype, w_dtype,
                  scaled: bool = False,
                  highest: bool = False) -> tuple[int, int, int]:
    """(bm, bk, bn) for X (m, k) @ W (k, n): of the tilings
    :func:`_sizes` allows whose :func:`matmul_vmem_bytes` fits
    :data:`VMEM_BUDGET`, the one with the fewest grid steps; ties go to
    the larger bm (z is regenerated m / bm times), then the larger bn (x
    is read n / bn times). Where none fits, the smallest tiling."""
    cands = [(bm, bk, bn) for bm in _sizes(m, 8) for bk in _sizes(k, 128)
             for bn in _sizes(n, 128)]
    fits = [b for b in cands
            if matmul_vmem_bytes(*b, x_dtype, w_dtype, scaled, highest)
            <= VMEM_BUDGET]
    if not fits:
        return cands[0]
    return max(fits, key=lambda b: (b[0] * b[1] * b[2], b[0], b[2]))


def _matmul_tiling(blocks, m, k, n, x_dtype, w_dtype, scaled):
    """``blocks=None`` picks the tiling; a given (bm, bk, bn) is snapped
    to the nearest accepted sizes at or below it."""
    if blocks is None:
        return matmul_blocks(m, k, n, x_dtype, w_dtype, scaled,
                             _dot_at_highest())
    return (_pick(m, blocks[0], 8), _pick(k, blocks[1], 128),
            _pick(n, blocks[2], 128))


@functools.partial(jax.jit,
                   static_argnames=("salt", "dist", "block", "interpret",
                                    "prime_offset", "prehashed"))
def zo_add(w, seed, salt: int, coeff, dist: str = "rademacher",
           block=(256, 256), interpret: bool = False,
           prime_offset: int = 0, prehashed: bool = False, scale=None):
    """W + coeff*z for a 2-D leaf; z regenerated in VMEM, never in HBM.

    scale: per-output-channel (N,) f32 scales marking ``w`` as an int8
    quantized base -- the kernel then computes ``w*scale + coeff*z``
    (dequant fused into the same tile pass; output f32). HBM reads drop
    to ~1/4: the int8 values plus an (N,) scale vector.
    """
    m, n = w.shape
    bm, bn = _pick(m, block[0], 8), _pick(n, block[1], 128)
    grid = (m // bm, n // bn)
    seed = jnp.asarray(seed, _U32).reshape(1)
    coeff = jnp.asarray(coeff, jnp.float32).reshape(1)
    if scale is None:
        return pl.pallas_call(
            functools.partial(_zo_add_kernel, salt=salt, bm=bm, bn=bn,
                              dist=dist, prime_offset=prime_offset,
                              prehashed=prehashed),
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # seed
                pl.BlockSpec(memory_space=pltpu.SMEM),  # coeff
                pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
            interpret=interpret,
        )(seed, coeff, w)
    scale = jnp.asarray(scale, jnp.float32).reshape(1, n)
    return pl.pallas_call(
        functools.partial(_zo_add_q_kernel, salt=salt, bm=bm, bn=bn,
                          dist=dist, prime_offset=prime_offset,
                          prehashed=prehashed),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seed
            pl.BlockSpec(memory_space=pltpu.SMEM),  # coeff
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(seed, coeff, w, scale)


# ---------------------------------------------------------------------------
# user-batched W[u] + coeff[u] * z(seed[u])


def _zo_add_users_kernel(seed_ref, coeff_ref, w_ref, o_ref, *, salt, bm, bn,
                         dist, prime_offset, prehashed):
    u, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    z = _tile_z(seed_ref[u], salt, (bm, bn), i * bm, j * bn, dist,
                prime_offset, prehashed)
    w = w_ref[0].astype(jnp.float32)
    o_ref[0] = (w + coeff_ref[u] * z).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("salt", "dist", "block", "interpret",
                                    "prime_offset", "prehashed"))
def zo_add_users(w, seeds, salt: int, coeffs, dist: str = "rademacher",
                 block=(256, 256), interpret: bool = False,
                 prime_offset: int = 0, prehashed: bool = False):
    """User-batched :func:`zo_add`: W (U, M, N) per-user stacked leaves,
    seeds/coeffs (U,) -- ``out[u] = W[u] + coeffs[u] * z(seeds[u])``.

    One dispatch sweeps every user's leaf; per-tile arithmetic (block
    shapes, z regeneration, accumulation) is identical to U scalar
    :func:`zo_add` calls, so the batch is bit-exact with the loop. The
    user axis rides the grid's *leading* (outermost, slowest) dimension:
    lane-local tile order is preserved and the (U,) seed/coeff vectors
    sit in SMEM, indexed by ``program_id(0)``.
    """
    u, m, n = w.shape
    bm, bn = _pick(m, block[0], 8), _pick(n, block[1], 128)
    seeds = jnp.asarray(seeds, _U32).reshape(u)
    coeffs = jnp.asarray(coeffs, jnp.float32).reshape(u)
    return pl.pallas_call(
        functools.partial(_zo_add_users_kernel, salt=salt, bm=bm, bn=bn,
                          dist=dist, prime_offset=prime_offset,
                          prehashed=prehashed),
        grid=(u, m // bm, n // bn),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seeds (U,)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # coeffs (U,)
            pl.BlockSpec((1, bm, bn), lambda uu, i, j: (uu, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda uu, i, j: (uu, i, j)),
        out_shape=jax.ShapeDtypeStruct((u, m, n), w.dtype),
        interpret=interpret,
    )(seeds, coeffs, w)


# ---------------------------------------------------------------------------
# X @ (W + coeff * z)


def _zo_matmul_kernel(seed_ref, coeff_ref, x_ref, w_ref, o_ref, acc_ref, *,
                      salt, bk, bn, n_k, dist, prime_offset, prehashed):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    j = pl.program_id(1)
    z = _tile_z(seed_ref[0], salt, (bk, bn), k * bk, j * bn, dist,
                prime_offset, prehashed)
    w = w_ref[...].astype(jnp.float32) + coeff_ref[0] * z
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _zo_matmul_q_kernel(seed_ref, coeff_ref, x_ref, w_ref, s_ref, o_ref,
                        acc_ref, *, salt, bk, bn, n_k, dist, prime_offset,
                        prehashed):
    """Quantized-base variant of :func:`_zo_matmul_kernel`: the W tile
    arrives int8, the (1, bn) per-channel scale tile rides along, and
    ``dequant + coeff*z`` happens in VMEM before the MXU dot -- the base
    never exists dequantized in HBM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    j = pl.program_id(1)
    z = _tile_z(seed_ref[0], salt, (bk, bn), k * bk, j * bn, dist,
                prime_offset, prehashed)
    w = w_ref[...].astype(jnp.float32) * s_ref[...] + coeff_ref[0] * z
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _after_prefetch(kernel):
    """``kernel`` with the scalar-prefetch layer ref dropped: only the
    index maps read it."""
    return lambda layer_ref, *refs: kernel(*refs)


@functools.partial(jax.jit,
                   static_argnames=("salt", "dist", "blocks", "interpret",
                                    "prime_offset", "prehashed"))
def zo_matmul(x, w, seed, salt: int, coeff, dist: str = "rademacher",
              blocks=None, interpret: bool = False,
              prime_offset: int = 0, prehashed: bool = False, scale=None,
              layer=None):
    """Y = X @ (W + coeff * z(seed)). X: (M, K), W: (K, N), or a
    layer-stacked W: (L, K, N) read at ``layer``.

    The perturbed weight tile lives only in VMEM: HBM traffic is exactly
    the unperturbed matmul's (X, W read once; Y written once).

    A stacked W is never sliced: ``layer`` rides SMEM as a scalar-prefetch
    operand and the weight's BlockSpec fetches that layer's tiles from
    the stack in place; a 2-D W is layer 0 of a stack of one. Tiles, z
    and accumulation order are those of the 2-D call on ``w[layer]``, so
    the output is bit-identical to it; the z-field of the layer comes, as
    there, from a ``seed`` with the layer folded in (``prehashed``).

    scale: per-output-channel f32 scales, (N,) -- or (L, N) for a stacked
    W -- marking ``w`` as an int8 quantized base; the kernel then computes
    ``X @ (w*scale + coeff*z)`` with dequantization fused into the same
    VMEM tile pass (weight HBM reads ~1/4 of the f32 kernel's, z still
    zero bytes; the prehashed-salt scheme is untouched).

    prehashed/prime_offset: see :func:`_tile_z` -- lets the kernel compute
    the perturbed forward for one layer of a scan-stacked (L, K, N) leaf
    while staying bit-exact with the full-leaf reference field.

    blocks: None picks (bm, bk, bn) from the shapes, the dtypes and the
    dot's precision (:func:`matmul_blocks`); a tuple pins the tiling.
    """
    if w.ndim == 2:                       # layer 0 of a stack of one
        w, layer = w[None], 0
    m, k = x.shape
    _, k2, n = w.shape
    assert k == k2
    bm, bk, bn = _matmul_tiling(blocks, m, k, n, x.dtype, w.dtype,
                                scale is not None)
    grid = (m // bm, n // bn, k // bk)
    seed = jnp.asarray(seed, _U32).reshape(1)
    coeff = jnp.asarray(coeff, jnp.float32).reshape(1)
    kern = functools.partial(
        _zo_matmul_kernel if scale is None else _zo_matmul_q_kernel,
        salt=salt, bk=bk, bn=bn, n_k=grid[2], dist=dist,
        prime_offset=prime_offset, prehashed=prehashed)
    # index maps take (i, j, kk) and the layer ref
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((bm, bk), lambda i, j, kk, l: (i, kk)),
        pl.BlockSpec((None, bk, bn), lambda i, j, kk, l: (l[0], kk, j)),
    ]
    args = [seed, coeff, x, w]
    if scale is not None:
        in_specs.append(
            pl.BlockSpec((None, 1, bn), lambda i, j, kk, l: (l[0], 0, j)))
        args.append(jnp.asarray(scale, jnp.float32).reshape(-1, 1, n))
    return pl.pallas_call(
        _after_prefetch(kern),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, l: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype), interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *args)


# ---------------------------------------------------------------------------
# user-batched X[u] @ (W + coeff[u] * z(seed[u])) -- one resident base,
# B users' perturbed forwards in one dispatch


def _zo_matmul_users_kernel(seed_ref, coeff_ref, x_ref, w_ref, o_ref,
                            acc_ref, *, salt, bk, bn, n_k, dist,
                            prime_offset, prehashed):
    u, j, k = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    z = _tile_z(seed_ref[u], salt, (bk, bn), k * bk, j * bn, dist,
                prime_offset, prehashed)
    w = w_ref[...].astype(jnp.float32) + coeff_ref[u] * z
    acc_ref[...] += jnp.dot(x_ref[0].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _zo_matmul_users_q_kernel(seed_ref, coeff_ref, x_ref, w_ref, s_ref,
                              o_ref, acc_ref, *, salt, bk, bn, n_k, dist,
                              prime_offset, prehashed):
    """Quantized shared base: the int8 W tile is read once per (j, k)
    revisit and dequantized in VMEM with each user's perturbation --
    U tenants' forwards never materialize a f32 base in HBM."""
    u, j, k = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    z = _tile_z(seed_ref[u], salt, (bk, bn), k * bk, j * bn, dist,
                prime_offset, prehashed)
    w = w_ref[...].astype(jnp.float32) * s_ref[...] + coeff_ref[u] * z
    acc_ref[...] += jnp.dot(x_ref[0].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("salt", "dist", "blocks", "interpret",
                                    "prime_offset", "prehashed"))
def zo_matmul_users(x, w, seeds, salt: int, coeffs,
                    dist: str = "rademacher", blocks=None,
                    interpret: bool = False, prime_offset: int = 0,
                    prehashed: bool = False, scale=None):
    """User-batched :func:`zo_matmul`: ``Y[u] = X[u] @ (W +
    coeffs[u] * z(seeds[u]))``. X: (U, M, K); W: (K, N), SHARED across
    users (the single resident base); seeds/coeffs: (U,).

    This is the multi-tenant hot path: one dispatch evaluates B users'
    perturbed forwards against one copy of the weights. The user axis is
    the grid's outermost dimension with the k-reduction innermost, and
    block sizes are picked from one lane's shapes exactly as the scalar
    kernel picks them, so each lane's accumulation order -- and therefore
    its bits -- is identical to a lone :func:`zo_matmul` call with that
    user's (seed, coeff).

    scale: per-output-channel (N,) f32 scales marking ``w`` as an int8
    quantized base; dequant fuses into the same VMEM tile pass, so U
    tenants share ~1 byte/param of resident weight HBM.
    """
    u, m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bk, bn = _matmul_tiling(blocks, m, k, n, x.dtype, w.dtype,
                                scale is not None)
    grid = (u, m // bm, n // bn, k // bk)
    seeds = jnp.asarray(seeds, _U32).reshape(u)
    coeffs = jnp.asarray(coeffs, jnp.float32).reshape(u)
    if scale is None:
        kern = functools.partial(_zo_matmul_users_kernel, salt=salt, bk=bk,
                                 bn=bn, n_k=grid[3], dist=dist,
                                 prime_offset=prime_offset,
                                 prehashed=prehashed)
        return pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # seeds (U,)
                pl.BlockSpec(memory_space=pltpu.SMEM),  # coeffs (U,)
                pl.BlockSpec((1, bm, bk), lambda uu, i, j, kk: (uu, i, kk)),
                pl.BlockSpec((bk, bn), lambda uu, i, j, kk: (kk, j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda uu, i, j, kk: (uu, i, j)),
            out_shape=jax.ShapeDtypeStruct((u, m, n), x.dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
        )(seeds, coeffs, x, w)
    scale = jnp.asarray(scale, jnp.float32).reshape(1, n)
    kern = functools.partial(_zo_matmul_users_q_kernel, salt=salt, bk=bk,
                             bn=bn, n_k=grid[3], dist=dist,
                             prime_offset=prime_offset, prehashed=prehashed)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bm, bk), lambda uu, i, j, kk: (uu, i, kk)),
            pl.BlockSpec((bk, bn), lambda uu, i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda uu, i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda uu, i, j, kk: (uu, i, j)),
        out_shape=jax.ShapeDtypeStruct((u, m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(seeds, coeffs, x, w, scale)

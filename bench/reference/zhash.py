"""The ZO perturbation z as the replay-log format defines it.

A replay record ``(seed, gs)`` means ``theta += coeff * z(seed)`` with z
regenerated per leaf from the step seed and the leaf's pytree path; the
hash below is that format's contract, copied so that the reference
shares no code with the system under test. Per element of a leaf of
shape ``(d0, d1, ...)`` at path ``p``::

    h = avalanche(seed ^ crc32(p))
    for each dim i:  h = avalanche(h ^ (index_i * PRIME[i]))
    z = 1 - 2 * (h >> 31)                    (Rademacher, the default)

and a direction ``k`` of step seed ``s`` uses ``avalanche(s ^ k *
PRIME[1])``. Arithmetic is uint32 with wraparound.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

PRIMES = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1,
          0xD3A2646D, 0xFD7046C5, 0xB55A4F09)
U32 = jnp.uint32


def avalanche(x):
    x = jnp.asarray(x, U32)
    x = x ^ (x >> 15)
    x = x * U32(0x2C1B3C6D)
    x = x ^ (x >> 12)
    x = x * U32(0x297A2D39)
    x = x ^ (x >> 15)
    return x


def salt(path: str) -> int:
    return zlib.crc32(path.encode("utf-8")) & 0xFFFFFFFF


def direction_seed(step_seed, k):
    return avalanche(jnp.asarray(step_seed, U32)
                     ^ (jnp.asarray(k, U32) * U32(PRIMES[1])))


def leaf_state(seed, path: str):
    return avalanche(jnp.asarray(seed, U32) ^ U32(salt(path)))


def _sign(h):
    return 1.0 - 2.0 * (h >> 31).astype(jnp.float32)


def z_full(seed, path: str, shape):
    """z of the whole leaf, f32."""
    h = leaf_state(seed, path)
    if len(shape) == 0:
        return _sign(avalanche(h))
    for d, n in enumerate(shape):
        iota = jax.lax.broadcasted_iota(U32, tuple(shape), d)
        h = avalanche(h ^ (iota * U32(PRIMES[d])))
    return _sign(h)


def z_layer(seed, path: str, layer, shape):
    """z of slice ``layer`` of a leaf stacked ``(L, *shape)``."""
    h = avalanche(leaf_state(seed, path)
                  ^ (jnp.asarray(layer, U32) * U32(PRIMES[0])))
    for d, n in enumerate(shape):
        iota = jax.lax.broadcasted_iota(U32, tuple(shape), d)
        h = avalanche(h ^ (iota * U32(PRIMES[d + 1])))
    return _sign(h)


def z_rows(seed, path: str, rows, n_cols: int):
    """Rows ``rows`` of z of a ``(R, n_cols)`` leaf, f32."""
    h = avalanche(leaf_state(seed, path)
                  ^ (jnp.asarray(rows, U32) * U32(PRIMES[0])))
    ci = jax.lax.broadcasted_iota(U32, h.shape + (n_cols,), h.ndim)
    return _sign(avalanche(h[..., None] ^ (ci * U32(PRIMES[1]))))

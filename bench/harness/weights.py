"""Random weights from the seed, made on the device in one jitted call.

Every leaf is ``mean + std * N(0, 1)`` with ``std`` a power of two, so
the product is exact and a leaf comes out bit-equal whether it is made
with the whole tree or alone (:func:`make_leaf`), which is how the
output check regenerates the starting weights. Rules by leaf path:
norm scales ~ 1 + 2**-6 N, norm biases and projection biases ~ 2**-6 N,
output projections (``wo``, ``w_out``) ~ 2**-9 N, every other matrix and
embedding ~ 2**-6 N (about the 0.02 of OPT and RoBERTa's init).
"""

from __future__ import annotations

from functools import partial
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp

STD = 2.0 ** -6
OUT_STD = 2.0 ** -9


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def leaf_rule(path: str) -> Tuple[float, float]:
    """(mean, std) of the leaf at ``path``."""
    parts = path.split("/")
    if parts[-1] == "scale":
        return 1.0, STD
    if parts[-1] in ("b", "bias"):
        return 0.0, STD
    if any(p in ("wo", "w_out") for p in parts):
        return 0.0, OUT_STD
    return 0.0, STD


def leaves_of(shapes) -> List[Tuple[str, Any]]:
    """(path, ShapeDtypeStruct) of every leaf, in flattening order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return [(path_str(p), s) for p, s in flat]


def _leaf(key, index: int, path: str, shape, dtype):
    mean, std = leaf_rule(path)
    k = jax.random.fold_in(key, index)
    z = jax.random.normal(k, shape, jnp.float32) * jnp.float32(std)
    if mean:
        z = z + jnp.float32(mean)
    return z.astype(dtype)


@partial(jax.jit, static_argnames=("index", "path", "shape", "dtype"))
def make_leaf(key, index: int, path: str, shape, dtype):
    """Leaf ``index`` of :func:`make_params` alone."""
    return _leaf(key, index, path, shape, dtype)


def make_params(shapes, seed32: int):
    """The whole parameter tree with ``shapes``' structure, one call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    spec = tuple((path_str(p), tuple(s.shape), jnp.dtype(s.dtype).name)
                 for p, s in flat)

    @jax.jit
    def build(key):
        return [_leaf(key, i, path, shape, jnp.dtype(dt))
                for i, (path, shape, dt) in enumerate(spec)]

    leaves = build(jax.random.PRNGKey(seed32))
    return jax.tree_util.tree_unflatten(treedef, leaves)

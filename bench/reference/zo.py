"""Reference ZO-SGD steps (MeZO, one direction) and adapter replay.

A step at seed ``s`` evaluates ``l+- = L(theta +- eps z)`` with
``z = z(direction_seed(s, 0))``, takes ``gs = (l+ - l-) / (2 eps)`` and
stores ``theta <- theta - lr * gs * z`` in each leaf's own dtype (the
update is added in float32, then rounded to the leaf's dtype, as the
replay-log format states). The forwards are ``forward.loss``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from bench.reference import forward, zhash

F32 = jnp.float32


def step_seed(train_seed: int, step: int):
    """The step seed a trainer with ``train_seed`` uses at ``step``."""
    return zhash.direction_seed(jnp.uint32(train_seed), step)


@partial(jax.jit, static_argnames=("path",), donate_argnums=(0,))
def _update_leaf(w, seed, coeff, path: str):
    z = zhash.z_full(seed, path, w.shape)
    return (w.astype(F32) + coeff * z).astype(w.dtype)


def apply_update(params, dir_seed, coeff):
    """theta + coeff * z(dir_seed), leaf by leaf (donates ``params``)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        p = "/".join(str(getattr(q, "key", q)) for q in path)
        out.append(_update_leaf(leaf, dir_seed, jnp.asarray(coeff, F32), p))
    return jax.tree_util.tree_unflatten(treedef, out)


def make_loss(task: str, n_heads: int, mm: Callable = forward.mm_f32):
    @jax.jit
    def loss(params, batch, seed, coeff):
        return forward.loss(params, batch, task=task, n_heads=n_heads,
                            perturb=(seed, coeff), mm=mm)
    return loss


def zo_steps(params, batches, train_seed: int, first_step: int, lr: float,
             eps: float, task: str, n_heads: int,
             mm: Callable = forward.mm_f32) -> Dict[str, Any]:
    """Run ``len(batches)`` reference steps from ``params`` (donated).
    Returns per-step ``lp``, ``lm``, ``gs`` and the final params."""
    loss = make_loss(task, n_heads, mm)
    out: Dict[str, Any] = {"lp": [], "lm": [], "gs": []}
    eps32 = F32(eps)
    for i, batch in enumerate(batches):
        s = zhash.direction_seed(step_seed(train_seed, first_step + i), 0)
        lp = loss(params, batch, s, eps32)
        lm = loss(params, batch, s, -eps32)
        gs = (lp - lm) / (2.0 * eps32)
        params = apply_update(params, s, -F32(lr) * gs)
        out["lp"].append(float(lp))
        out["lm"].append(float(lm))
        out["gs"].append(float(gs))
    out["params"] = params
    return out


def replay(params, records):
    """Materialise an adapter: every record's update, in order."""
    for rec in records:
        k = len(rec["gs"])
        for d, g in enumerate(rec["gs"]):
            s = zhash.direction_seed(jnp.uint32(rec["seed"]), d)
            coeff = (-F32(rec["lr"]) * F32(1.0 / k)) * F32(g)
            params = apply_update(params, s, coeff)
    return params

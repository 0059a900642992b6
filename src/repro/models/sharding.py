"""Parameter / activation sharding rules for the (pod, data, model) mesh.

Megatron-style tensor parallelism over the ``model`` axis:

  * embeddings + lm_head: vocab-sharded,
  * attention: head axis sharded (wq/wk/wv column-, wo row-parallel),
  * MLP: w_in column-, w_out row-parallel,
  * MoE: the *expert* axis sharded (expert parallelism); router replicated,
  * mamba/rwkv: d_inner / channel projections column/row-sharded,
  * norms/scalars: replicated.

Params are replicated across ``pod`` and ``data`` (ZO direction
parallelism needs no param sharding across pods -- cross-pod traffic is
scalars only).

Rules are matched on the flattened path string, most-specific-first.
``spec_tree(params_shape_tree)`` returns a PartitionSpec pytree suitable
for jax.jit in_shardings / ShapeDtypeStruct sharding.
"""

from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

PyTree = Any

# (path regex, spec builder given leaf ndim). Leading scan axis (stacked
# blocks) is detected by ndim relative to the rule's base rank.
_RULES = [
    # embeddings: vocab-sharded
    (r"embed/tok$", lambda nd: P("model", None)),
    (r"embed/pos$", lambda nd: P(None, None)),
    (r"lm_head/w$", lambda nd: _stk(nd, 2, P(None, "model"))),
    (r"cls_head/w$", lambda nd: P(None, None)),
    # attention
    (r"(attn|self|cross)/wq/w$", lambda nd: _stk(nd, 2, P(None, "model"))),
    (r"(attn|self|cross)/wk/w$", lambda nd: _stk(nd, 2, P(None, "model"))),
    (r"(attn|self|cross)/wv/w$", lambda nd: _stk(nd, 2, P(None, "model"))),
    (r"(attn|self|cross)/wo/w$", lambda nd: _stk(nd, 2, P("model", None))),
    (r"(attn|self|cross)/w[qkv]/b$", lambda nd: _stk(nd, 1, P("model"))),
    (r"(attn|self|cross)/wo/b$", lambda nd: _stk(nd, 1, P(None))),
    # dense MLPs (incl. moe shared expert). Gated w_in uses the
    # interleaved (D, F, 2) layout (see layers.mlp_init): shard F.
    (r"(mlp|shared)/w_in/w$", lambda nd: _gated_or_flat_in(nd)),
    (r"(mlp|shared)/w_out/w$", lambda nd: _stk(nd, 2, P("model", None))),
    (r"(mlp|shared)/w_in/b$", lambda nd: _stk(nd, 1, P("model"))),
    (r"(mlp|shared)/w_out/b$", lambda nd: _stk(nd, 1, P(None))),
    # MoE: expert-parallel over the expert axis
    (r"moe/router$", lambda nd: _stk(nd, 2, P(None, None))),
    # w_in: flat (E, D, F) or gated-interleaved (E, D, F, 2), +stack axis
    (r"moe/w_in$", lambda nd: _stk(nd, 3, P("model", None, None))
     or _stk(nd - 1, 3, P("model", None, None, None))),
    (r"moe/w_out$", lambda nd: _stk(nd, 3, P("model", None, None))),
]

# fsdp_params=True: expert weights additionally sharded over ``data`` on
# the per-expert hidden dim (storage), gathered per layer inside the EP
# shard_map (ZeRO-3 style). Required when params/chip exceeds HBM with
# model-only sharding (kimi-k2: 2 TB expert weights -> 8 GB/chip in 2-D).
_FSDP_RULES = [
    (r"moe/w_in$", lambda nd: _stk(nd, 3, P("model", None, "data"))
     or _stk(nd - 1, 3, P("model", None, "data", None))),
    (r"moe/w_out$", lambda nd: _stk(nd, 3, P("model", "data", None))),
]

_RULES += [
    # mamba
    (r"mamba/in_proj/w$", lambda nd: _stk(nd, 2, P(None, "model"))),
    (r"mamba/out_proj/w$", lambda nd: _stk(nd, 2, P("model", None))),
    (r"mamba/(conv_w|conv_b|x_proj/w|dt_proj/w|dt_proj/b|A_log|D)",
     lambda nd: None),  # replicate small SSM innards
    # rwkv6
    (r"tm/w[rkvg]/w$", lambda nd: _stk(nd, 2, P(None, "model"))),
    (r"tm/wo/w$", lambda nd: _stk(nd, 2, P("model", None))),
    (r"cm/wk/w$", lambda nd: _stk(nd, 2, P(None, "model"))),
    (r"cm/wv/w$", lambda nd: _stk(nd, 2, P("model", None))),
    (r"cm/wr/w$", lambda nd: _stk(nd, 2, P(None, None))),
]


def maybe_shard(x, *spec):
    """with_sharding_constraint iff an ambient mesh with the named axes is
    active (jax.set_mesh). No-op in mesh-less CPU smoke tests, so model
    code can annotate activations unconditionally."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return x
    names = set(am.axis_names)
    if any(a not in names for a in jax.tree.leaves(list(spec))
           if isinstance(a, str)):
        return x
    # drop axes that don't divide the dim
    fixed = []
    sizes = dict(zip(am.axis_names, am.axis_sizes))
    for d, a in enumerate(spec):
        if a is None:
            fixed.append(None)
            continue
        axes = (a,) if isinstance(a, str) else tuple(a)
        prod = 1
        keep = []
        for ax in axes:
            if x.shape[d] % (prod * sizes[ax]) == 0:
                keep.append(ax)
                prod *= sizes[ax]
        fixed.append(tuple(keep) if len(keep) > 1 else
                     (keep[0] if keep else None))
    return jax.lax.with_sharding_constraint(x, P(*fixed))


def _gated_or_flat_in(nd: int, shape=None):
    # rank 2 = ungated (D, F); rank 3 = gated (D, F, 2) OR stacked
    # ungated (L, D, F), told apart by the trailing dim of 2;
    # rank 4 = stacked gated (L, D, F, 2).
    if nd == 2:
        return P(None, "model")
    if nd == 3:
        if shape is not None and shape[-1] == 2:
            return P(None, "model", None)      # gated (D, F, 2)
        return P(None, None, "model")          # stacked ungated (L, D, F)
    if nd == 4:
        return P(None, None, "model", None)
    return None


def _stk(nd: int, base: int, spec: P):
    """Prepend None for a stacked scan axis when leaf rank = base+1."""
    if nd == base:
        return spec
    if nd == base + 1:
        return P(None, *spec)
    return None


def _path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def leaf_spec(path: str, ndim: int, shape=None, fsdp: bool = False) -> P:
    rules = (_FSDP_RULES + _RULES) if fsdp else _RULES
    for pat, fn in rules:
        if re.search(pat, path):
            try:
                s = fn(ndim, shape)
            except TypeError:
                s = fn(ndim)
            if s is not None:
                return s
            break
    return P()  # replicate


def spec_tree(params: PyTree, fsdp: bool = False,
              use_tp: bool = True) -> PyTree:
    """PartitionSpec pytree for a params (or ShapeDtypeStruct) pytree."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    if not use_tp:   # small models: replicate weights, pure DP
        specs = [P() for _ in leaves]
    else:
        specs = [leaf_spec(_path_str(p), l.ndim, tuple(l.shape), fsdp)
                 for p, l in leaves]
    return jax.tree_util.tree_unflatten(treedef, specs)


def fit_spec(shape, spec: P, mesh) -> P:
    """Drop sharded axes that do not evenly divide their dim (replicate
    instead) -- e.g. odd vocab sizes like granite's 49155."""
    fixed = []
    for d, entry in enumerate(spec):
        if entry is None:
            fixed.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        keep, prod = [], 1
        for ax in axes:
            if ax not in mesh.axis_names:
                continue
            sz = _axis_size(mesh, ax)
            if shape[d] % (prod * sz) == 0:
                keep.append(ax)
                prod *= sz
        fixed.append(tuple(keep) if len(keep) > 1 else
                     (keep[0] if keep else None))
    return P(*fixed)


def fit_specs(tree: PyTree, specs: PyTree, mesh) -> PyTree:
    return jax.tree.map(lambda l, s: fit_spec(l.shape, s, mesh), tree, specs)


def sharding_tree(params: PyTree, mesh) -> PyTree:
    specs = fit_specs(params, spec_tree(params), mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


# ---------------------------------------------------------------------------
# batch / cache shardings (shape- and mesh-aware: axes that do not divide
# a dim are dropped rather than producing an invalid sharding)


def _axis_size(mesh, name) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def _fit(mesh, dim: int, *names):
    """Largest prefix of ``names`` whose product divides ``dim``."""
    chosen = []
    prod = 1
    for n in names:
        if n is None or n not in mesh.axis_names:
            continue
        sz = _axis_size(mesh, n)
        if dim % (prod * sz) == 0:
            chosen.append(n)
            prod *= sz
    if not chosen:
        return None
    return tuple(chosen) if len(chosen) > 1 else chosen[0]


def batch_spec(batch_like: PyTree, mesh, data_axes=("data",)) -> PyTree:
    """Shard the leading (batch) dim of every batch leaf over data axes."""
    def spec(l):
        if l.ndim == 0:
            return P()
        return P(_fit(mesh, l.shape[0], *data_axes),
                 *(None,) * (l.ndim - 1))
    return jax.tree.map(spec, batch_like)


# cache leaf name -> (dims meaning). KV caches shard *sequence* over the
# model axis (sequence-parallel cache: kv_heads are too few to shard
# 16-way and the cache dominates decode memory; attention over the
# sharded axis lowers to a partial-softmax combine).
_CACHE_LAYOUTS = {
    # name: (batch_dim, seq_dim, model_dim). Every unified StateCache
    # leaf is (n_layers, B, ...) -- batch always dim 1 (models/runtime).
    "k": (1, 2, None), "v": (1, 2, None),
    "xk": (1, None, None), "xv": (1, None, None),
    "conv": (1, None, None),          # (nb, B, w, di)
    "ssm": (1, None, 2),              # (nb, B, di, n): di over model
    "state": (1, 2, None),            # (L, B, H, hd, hd): H over model
    "x_prev": (1, None, None),        # rwkv token-shift buffers
    # paged KV pools (L, n_pages, page_size, KV, hd): no batch axis --
    # slots address the shared pool through a page table, so shard the
    # page axis the way dense K/V shards its sequence axis
    "k_pages": (None, 1, None), "v_pages": (None, 1, None),
}


def cache_spec(cache_like: PyTree, mesh) -> PyTree:
    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache_like)
    out = []
    for path, l in leaves:
        name = str(getattr(path[-1], "key", path[-1]))
        bd, sd, md = _CACHE_LAYOUTS.get(name, (None, None, None))
        spec = [None] * l.ndim
        if bd is not None and bd < l.ndim:
            spec[bd] = _fit(mesh, l.shape[bd], "data")
        if sd is not None and sd < l.ndim:
            # sequence (or page/head) axis over model; spill onto data
            # when no batch axis is using it (long-context batch=1
            # decode, or a pool leaf with no batch axis at all)
            if bd is None or spec[bd] is None:
                spec[sd] = _fit(mesh, l.shape[sd], "model", "data")
            else:
                spec[sd] = _fit(mesh, l.shape[sd], "model")
        if md is not None and md < l.ndim:
            spec[md] = _fit(mesh, l.shape[md], "model")
        out.append(P(*spec))
    return jax.tree_util.tree_unflatten(treedef, out)

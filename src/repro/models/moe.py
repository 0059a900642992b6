"""Top-k Mixture-of-Experts with sort-based capacity dispatch (EP).

Design notes (vs. the GShard one-hot dispatch einsum): the one-hot
dispatch tensor is (tokens, experts, capacity) which for kimi-k2
(T_local=64k, E=384) is tens of GB per device. We instead sort the
(token, expert) assignment list by expert id and scatter rows into an
(E, C, D) buffer -- O(T*k*D) memory, the true lower bound for top-k.

Sharding: the token axis is data-sharded; the expert axis of the buffers
and of the expert weights is model-sharded (expert parallelism). The
token->expert redistribution lowers to an all-to-all under SPMD.

Overflowing tokens beyond capacity are dropped (standard capacity-factor
semantics); their combine weight is zero so the residual path carries
them unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.sharding import maybe_shard


def moe_init(cfg, key, d_model=None):
    d = d_model or cfg.d_model
    e, f = cfg.n_experts, cfg.expert_dff or cfg.d_ff
    ks = jax.random.split(key, 4)
    gated = cfg.act in ("swiglu", "geglu")
    # gated: interleaved (E, D, F, 2) so up/gate pairs stay on one shard
    # under any F-dim sharding (same rationale as layers.mlp_init)
    win_shape = (e, d, f, 2) if gated else (e, d, f)
    p = {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32) * 0.02),
        "w_in": (jax.random.normal(ks[1], win_shape, jnp.float32)
                 * 0.02).astype(L._dt(cfg)),
        "w_out": (jax.random.normal(ks[2], (e, f, d), jnp.float32)
                  * 0.02 / max(cfg.n_layers, 1) ** 0.5).astype(L._dt(cfg)),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_init(
            cfg, ks[3], d_ff=cfg.n_shared_experts * f, d_model=d)
    return p


def _expert_ffn(cfg, w_in, w_out, x):
    """x: (E, C, D) -> (E, C, D), per-expert weights stacked on dim 0."""
    if cfg.act in ("swiglu", "geglu"):
        h = jnp.einsum("ecd,edfg->ecfg", x, w_in)
        u, g = h[..., 0], h[..., 1]
        h = u * (jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g))
    elif cfg.act == "gelu":
        h = jnp.einsum("ecd,edf->ecf", x, w_in)
        h = jax.nn.gelu(h)
    else:
        h = jnp.einsum("ecd,edf->ecf", x, w_in)
        h = jax.nn.relu(h)
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.topk * cfg.capacity_factor / cfg.n_experts)
    return max(8, c)


def _ambient_mesh():
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def moe_apply_ep(cfg, p, x):
    """Expert-parallel MoE via shard_map over the ``model`` axis.

    Beyond-paper optimization (EXPERIMENTS.md §Perf): the jit-auto version
    below scatters into a *globally-shaped* (E, C, D) buffer, which XLA
    partitions with a full-buffer all-reduce per layer (~GBs/chip). Here
    each model shard owns E/model_size experts, selects its own tokens
    from the (TP-replicated) activations locally, and the only collective
    is the psum of the combined (T, D) output -- the same AR Megatron
    pays for an MLP block. Bit-identical results to moe_apply (same
    router, same capacity semantics, per-shard capacity C/shards).
    """
    am = _ambient_mesh()
    mesh_axes = set(am.axis_names or ()) if am is not None else set()
    if "model" not in mesh_axes:
        return moe_apply(cfg, p, x)
    sizes = dict(zip(am.axis_names, am.axis_sizes))
    n_model = sizes["model"]
    if cfg.n_experts % n_model:
        return moe_apply(cfg, p, x)

    b, s, d = x.shape
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh_axes)
    prod = 1
    chosen = []
    for a in batch_axes:
        if b % (prod * sizes[a]) == 0:
            chosen.append(a)
            prod *= sizes[a]
    bspec = tuple(chosen) if len(chosen) > 1 else (
        chosen[0] if chosen else None)
    e_local = cfg.n_experts // n_model
    t_local = (b // prod) * s
    c = capacity(t_local, cfg)

    fsdp = cfg.fsdp_params and "data" in mesh_axes
    gated = cfg.act in ("swiglu", "geglu")
    if fsdp and gated and b * s <= 8192:
        # decode-sized token counts: moving 2 TB of expert weights over
        # ICI for a few thousand tokens is backwards -- keep the weights
        # stationary, replicate the (tiny) tokens instead
        return _moe_ep_weights_stationary(cfg, p, x, am, sizes)
    # fold the always-on shared expert into the same psum as the routed
    # experts: its w_out partial sum rides the existing AR instead of
    # paying a second x-shaped all-reduce per MoE layer
    fold_shared = bool(cfg.n_shared_experts) and gated and "shared" in p

    def inner(xl, router, w_in, w_out, *shared_w):
        bl, sl, dl = xl.shape
        t = bl * sl
        xf = xl.reshape(t, dl)
        me = jax.lax.axis_index("model")
        if fsdp:
            # ZeRO-3 style: expert weights stored F-sharded over `data`;
            # gather this layer's local experts just-in-time (transient,
            # freed after the einsums -- the storage stays 2-D sharded)
            w_in = jax.lax.all_gather(w_in, "data", axis=2, tiled=True)
            w_out = jax.lax.all_gather(w_out, "data", axis=1, tiled=True)
            # (F is axis 2 for both the gated (E,D,F,2) and flat (E,D,F)
            # layouts, so the gather axis is layout-independent)
        logits = (xf.astype(jnp.float32) @ router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, cfg.topk)
        gate = gate / (gate.sum(-1, keepdims=True) + 1e-9)
        density = jnp.mean(
            jax.nn.one_hot(idx[:, 0], cfg.n_experts, dtype=jnp.float32), 0)
        if chosen:  # global token mean, matching the auto-sharded path
            density = jax.lax.pmean(density, tuple(chosen))
            probs_mean = jax.lax.pmean(probs.mean(0), tuple(chosen))
        else:
            probs_mean = probs.mean(0)
        aux = cfg.n_experts * jnp.mean(probs_mean * density)

        lo = me * e_local
        flat_e = idx.reshape(-1) - lo
        flat_t = jnp.repeat(jnp.arange(t), cfg.topk)
        flat_g = gate.reshape(-1)
        mine = (flat_e >= 0) & (flat_e < e_local)
        sort_key = jnp.where(mine, flat_e, e_local)   # sentinel tail
        order = jnp.argsort(sort_key, stable=True)
        sk, st, sg, sm = (sort_key[order], flat_t[order], flat_g[order],
                          mine[order])
        sec = jnp.clip(sk, 0, e_local - 1)
        starts = jnp.searchsorted(sk, jnp.arange(e_local))
        pos = jnp.arange(t * cfg.topk) - starts[sec]
        keep = sm & (pos < c)
        slot = jnp.where(keep, sec * c + pos, 0)
        buf = jnp.zeros((e_local * c, dl), xl.dtype)
        rows = jnp.where(keep[:, None], xf[st], 0).astype(xl.dtype)
        buf = buf.at[slot].add(rows).reshape(e_local, c, dl)
        yexp = _expert_ffn(cfg, w_in, w_out, buf).reshape(e_local * c, dl)
        contrib = yexp[slot] * (sg * keep).astype(xl.dtype)[:, None]
        out = jax.ops.segment_sum(contrib, st, num_segments=t)
        if fold_shared:
            sw_in, sw_out = shared_w
            h = jnp.einsum("td,dfg->tfg", xf, sw_in)
            act = (jax.nn.silu(h[..., 1]) if cfg.act == "swiglu"
                   else jax.nn.gelu(h[..., 1]))
            out = out + (h[..., 0] * act) @ sw_out
        # psum in the activation dtype (bf16): each partial is already a
        # <= topk-expert sum; halves both the combine HBM traffic and the
        # AR wire bytes vs an f32 reduction (EXPERIMENTS.md Sec Perf it.3)
        out = jax.lax.psum(out, "model")
        return out.reshape(bl, sl, dl), aux

    P_ = jax.sharding.PartitionSpec
    win_rest = (None,) if gated else ()
    win_spec = P_("model", None, "data" if fsdp else None, *win_rest)
    wout_spec = P_("model", "data", None) if fsdp else P_("model", None, None)
    args = [x, p["router"], p["w_in"], p["w_out"]]
    in_specs = [P_(bspec, None, None), P_(), win_spec, wout_spec]
    if fold_shared:
        args += [p["shared"]["w_in"]["w"], p["shared"]["w_out"]["w"]]
        in_specs += [P_(None, "model", None), P_("model", None)]
    out, aux = jax.shard_map(
        inner, mesh=am,
        in_specs=tuple(in_specs),
        out_specs=(P_(bspec, None, None), P_()),
        check_vma=False,
    )(*args)

    if cfg.n_shared_experts and not fold_shared:
        out = out + L.mlp_apply(cfg, p["shared"], x)
    return out, aux


def _moe_ep_weights_stationary(cfg, p, x, am, sizes):
    """Inference-MoE dispatch for tiny token counts (decode).

    Tokens are all-gathered across the batch axes (KBs), every
    (model, data) shard computes its experts' F-slice partials in place,
    and one psum over (model, data) returns the combined output -- zero
    weight movement. The train path (t >> weight bytes) instead gathers
    weights (see moe_apply_ep).
    """
    b, s, d = x.shape
    mesh_axes = set(am.axis_names or ())
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh_axes)
    prod = 1
    chosen = []
    for a in batch_axes:
        if b % (prod * sizes[a]) == 0:
            chosen.append(a)
            prod *= sizes[a]
    bspec = tuple(chosen) if len(chosen) > 1 else (
        chosen[0] if chosen else None)
    n_model = sizes["model"]
    e_local = cfg.n_experts // n_model
    t_all = b * s
    c = capacity(t_all, cfg)
    P_ = jax.sharding.PartitionSpec

    def inner(xl, router, w_in, w_out, *shared_w):
        if chosen:
            xl = jax.lax.all_gather(xl, tuple(chosen), axis=0, tiled=True)
        bl, sl, dl = xl.shape
        t = bl * sl
        xf = xl.reshape(t, dl)
        me = jax.lax.axis_index("model")
        logits = xf.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, cfg.topk)
        gate = gate / (gate.sum(-1, keepdims=True) + 1e-9)
        density = jnp.mean(
            jax.nn.one_hot(idx[:, 0], cfg.n_experts, dtype=jnp.float32), 0)
        aux = cfg.n_experts * jnp.mean(probs.mean(0) * density)

        lo = me * e_local
        flat_e = idx.reshape(-1) - lo
        flat_t = jnp.repeat(jnp.arange(t), cfg.topk)
        flat_g = gate.reshape(-1)
        mine = (flat_e >= 0) & (flat_e < e_local)
        sort_key = jnp.where(mine, flat_e, e_local)
        order = jnp.argsort(sort_key, stable=True)
        sk, st, sg, sm = (sort_key[order], flat_t[order], flat_g[order],
                          mine[order])
        sec = jnp.clip(sk, 0, e_local - 1)
        starts = jnp.searchsorted(sk, jnp.arange(e_local))
        pos = jnp.arange(t * cfg.topk) - starts[sec]
        keep = sm & (pos < c)
        slot = jnp.where(keep, sec * c + pos, 0)
        buf = jnp.zeros((e_local * c, dl), xl.dtype)
        rows = jnp.where(keep[:, None], xf[st], 0).astype(xl.dtype)
        buf = buf.at[slot].add(rows).reshape(e_local, c, dl)
        # expert FFN on the LOCAL F-slice: (E_l, D, F_l, 2) x (E_l, F_l, D)
        h = jnp.einsum("ecd,edfg->ecfg", buf, w_in)
        act = (jax.nn.silu(h[..., 1]) if cfg.act == "swiglu"
               else jax.nn.gelu(h[..., 1]))
        yexp = jnp.einsum("ecf,efd->ecd", h[..., 0] * act,
                          w_out).reshape(e_local * c, dl)
        contrib = yexp[slot] * (sg * keep).astype(xl.dtype)[:, None]
        out = jax.ops.segment_sum(contrib, st, num_segments=t)
        if shared_w:
            sw_in, sw_out = shared_w
            hs = jnp.einsum("td,dfg->tfg", xf, sw_in)
            acts = (jax.nn.silu(hs[..., 1]) if cfg.act == "swiglu"
                    else jax.nn.gelu(hs[..., 1]))
            out = out + (hs[..., 0] * acts) @ sw_out
        out = jax.lax.psum(out, ("model",) + tuple(chosen))
        out = out.reshape(bl, sl, dl)
        if chosen:
            sizes_c = [sizes[a] for a in chosen]
            idx_flat = jnp.int32(0)
            for a, sz in zip(chosen, sizes_c):
                idx_flat = idx_flat * sz + jax.lax.axis_index(a)
            out = jax.lax.dynamic_slice_in_dim(
                out, idx_flat * (bl // int(np.prod(sizes_c))),
                bl // int(np.prod(sizes_c)), axis=0)
        return out, aux

    fold_shared = bool(cfg.n_shared_experts) and "shared" in p
    args = [x, p["router"], p["w_in"], p["w_out"]]
    in_specs = [P_(bspec, None, None), P_(),
                P_("model", None, "data", None),
                P_("model", "data", None)]
    if fold_shared:
        args += [p["shared"]["w_in"]["w"], p["shared"]["w_out"]["w"]]
        in_specs += [P_(None, "model", None), P_("model", None)]
    out, aux = jax.shard_map(
        inner, mesh=am, in_specs=tuple(in_specs),
        out_specs=(P_(bspec, None, None), P_()),
        check_vma=False,
    )(*args)
    return out, aux


def moe_apply(cfg, p, x, rng_aux=None):
    """x: (B, S, D) -> (B, S, D). Returns (out, aux_loss)."""
    b, s, d = x.shape
    tt = b * s
    e, k = cfg.n_experts, cfg.topk
    c = capacity(tt, cfg)
    xf = x.reshape(tt, d)

    logits = (xf.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                       # (T, k)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), 0)
    aux = e * jnp.mean(probs.mean(0) * density)

    # ---- sort-based dispatch -------------------------------------------
    flat_e = idx.reshape(-1)                                  # (T*k,)
    flat_t = jnp.repeat(jnp.arange(tt), k)
    flat_g = gate.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    starts = jnp.searchsorted(se, jnp.arange(e))              # (E,)
    pos = jnp.arange(tt * k) - starts[se]
    keep = pos < c
    slot = se * c + jnp.where(keep, pos, 0)

    buf = jnp.zeros((e * c, d), x.dtype)
    rows = jnp.where(keep[:, None], xf[st], 0).astype(x.dtype)
    buf = buf.at[slot].add(rows)
    # expert-parallel: buffers live expert-sharded over the model axis;
    # the scatter above is the token->expert all-to-all under SPMD
    buf = maybe_shard(buf.reshape(e, c, d), "model", None, None)

    yexp = _expert_ffn(cfg, p["w_in"], p["w_out"], buf)
    yexp = maybe_shard(yexp, "model", None, None).reshape(e * c, d)

    # ---- combine --------------------------------------------------------
    contrib = yexp[slot] * (sg * keep).astype(x.dtype)[:, None]
    out = jax.ops.segment_sum(contrib, st, num_segments=tt)
    out = out.astype(x.dtype).reshape(b, s, d)

    if cfg.n_shared_experts:
        out = out + L.mlp_apply(cfg, p["shared"], x)
    return out, aux

"""Generic backbone engine: one forward/loss/cache/decode/prefill for
every architecture family.

A family is described *declaratively* by a :class:`ModelPlan` -- an
(optional) encoder :class:`StackPlan` plus the main stack, each a tuple
of :class:`Sublayer` rows naming a norm leaf, a mixer param path, and a
registered block type (``repro.models.blocks``). The engine then owns
the one residual pattern every family shares::

    for each layer (lax.scan over stacked (L, ...) leaves):
        for each sublayer:  x = x + block(norm(x))

and derives all five model functions from it:

* ``forward`` -- full-sequence, threads a :class:`PerturbCtx` into every
  block (``ctx.scope(stack)/.at_layer(l)/.scope(mixer path)``), so the
  fused ZO perturbed forward works identically for dense, MoE, hybrid,
  rwkv6, and enc-dec -- no family ever materializes a transient
  perturbed parameter copy;
* ``loss`` -- the ZO objective (CE + aux for LMs, CLS head for
  encoder classification);
* ``init_cache`` -- the unified StateCache: a nested dict mirroring the
  param tree (``{scope: {mixer path: {leaf: (L, B, ...)}}}``); every
  leaf has layers on axis 0 and batch on axis 1, for every family
  (serving scatters/merges slots with one tree.map, no per-family axis
  table);
* ``decode_step`` / ``prefill`` -- the scan walks (layer params, layer
  state) together; blocks marked ``mutable_state=False`` (cross-attn
  K/V) are read from the original buffers and never copied through the
  scan.

Family assembly (which sublayers exist, how init keys route) lives in
``repro.models.transformer``; this module is family-agnostic.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.perturb_ctx import LayerSlice
from repro.core.perturb_ctx import sub as _sub
from repro.models import layers as L
from repro.models.blocks import RunCtx, get_block
from repro.models.config import ModelConfig
from repro.optim.quant import is_quantized

PyTree = Any
AUX_LOSS_WEIGHT = 0.01


# ---------------------------------------------------------------------------
# plans


@dataclasses.dataclass(frozen=True)
class Sublayer:
    """One residual unit: ``x = x + block(norm(x))``.

    ``ln`` / ``mixer`` are '/'-separated param paths *within* the layer
    dict (hybrid nests them under ``sub_i``); ``block`` names a
    registered :class:`~repro.models.blocks.BlockType`; ``opts`` are
    static kwargs forwarded to the block (e.g. ``("causal", False)`` for
    encoder self-attention).
    """
    ln: str
    mixer: str
    block: str
    opts: Tuple[Tuple[str, Any], ...] = ()


@dataclasses.dataclass(frozen=True)
class StackPlan:
    """A scanned stack of identical layers under ``params[scope]``."""
    scope: str
    n_layers: int
    sublayers: Tuple[Sublayer, ...]


@dataclasses.dataclass(frozen=True)
class ModelPlan:
    cfg: ModelConfig
    stack: StackPlan                     # the decoded / backbone stack
    encoder: Optional[StackPlan] = None  # enc-dec only (stateless)


# ---------------------------------------------------------------------------
# nested-path helpers ('/'-separated paths inside a layer dict)


def _get(d, path: str):
    for part in path.split("/"):
        d = d[part]
    return d


def _set(d, path: str, val):
    parts = path.split("/")
    for part in parts[:-1]:
        d = d.setdefault(part, {})
    d[parts[-1]] = val


def _copy_tree(d):
    return {k: _copy_tree(v) if isinstance(v, dict) else v
            for k, v in d.items()}


def _scoped(ctx, path: str):
    """ctx.scope() down a '/'-separated path (None passes through)."""
    if ctx is None:
        return None
    for part in path.split("/"):
        ctx = ctx.scope(part)
    return ctx


def _decode_positions(pos):
    """Learned-pos embedding indices for a scalar or per-slot pos."""
    pos = jnp.asarray(pos)
    return pos[:, None] if pos.ndim else jnp.full((1,), pos)


# ---------------------------------------------------------------------------
# the one residual loop, in three modes


def _stack_apply(cfg, stack: StackPlan, params, x, rc: RunCtx, ctx):
    """Full-sequence stack: scan over stacked layer params. The perturb
    ctx binds the scan index (``at_layer``) so per-layer z slices match
    each stacked leaf's field. Leaves the fused kernel reads in place
    (``ctx.in_place``) are not scanned: the body gets each as a
    :class:`LayerSlice` of the whole stack."""
    leaves, treedef = jax.tree_util.tree_flatten(params[stack.scope],
                                                 is_leaf=is_quantized)
    sctx = None if ctx is None else ctx.scope(stack.scope)
    in_place = [sctx is not None and sctx.in_place(lf) for lf in leaves]

    def body(carry, xs):
        sliced, li = xs
        sliced = iter(sliced)
        bp = treedef.unflatten([LayerSlice(lf, li) if keep else next(sliced)
                                for lf, keep in zip(leaves, in_place)])
        h, aux = carry
        bctx = None if sctx is None else sctx.at_layer(li)
        for sl in stack.sublayers:
            bt = get_block(sl.block)
            z = L.norm_apply(cfg, _get(bp, sl.ln), h, _scoped(bctx, sl.ln))
            y, a = bt.apply(cfg, _get(bp, sl.mixer), z, rc,
                            ctx=_scoped(bctx, sl.mixer), **dict(sl.opts))
            h = h + y
            aux = aux + a
        return (h, aux), None

    n_layers = jax.tree_util.tree_leaves(leaves)[0].shape[0]
    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.float32(0.0)),
        ([lf for lf, keep in zip(leaves, in_place) if not keep],
         jnp.arange(n_layers, dtype=jnp.uint32)))
    return x, aux


def _stack_seq(cfg, stack: StackPlan, params, state, x, rc: RunCtx,
               mode: str):
    """Stateful stack walk (mode 'decode' or 'prefill'): the scan
    consumes (layer params, layer state) and emits updated state for
    every mutable-state block."""
    blocks_p = params[stack.scope]

    def body(h, xs):
        bp, ls = xs
        new = {}
        for sl in stack.sublayers:
            bt = get_block(sl.block)
            z = L.norm_apply(cfg, _get(bp, sl.ln), h)
            opts = dict(sl.opts)
            if bt.stateful:
                fn = bt.decode_step if mode == "decode" else bt.prefill
                y, ns = fn(cfg, _get(bp, sl.mixer), _get(ls, sl.mixer),
                           z, rc, **opts)
                if bt.mutable_state:
                    _set(new, sl.mixer, ns)
            else:
                y, _ = bt.apply(cfg, _get(bp, sl.mixer), z, rc, **opts)
            h = h + y
        return h, new

    x, stacked = jax.lax.scan(body, x, (blocks_p, state))
    out = _copy_tree(state)           # read-only leaves keep their buffers
    for sl in stack.sublayers:
        bt = get_block(sl.block)
        if bt.stateful and bt.mutable_state:
            _set(out, sl.mixer, _get(stacked, sl.mixer))
    return x, out


def _window_scan(bt, cfg, bp, ls, z, rc, opts):
    """Generic verify fallback for recurrent blocks (mamba, rwkv):
    run ``decode_step`` once per window offset and stack every mutable
    state leaf along a leading (W,) axis -- offset i's entry is the
    state after consuming window tokens 0..i, so the serving engine can
    commit exactly the accepted prefix and discard the rest (the
    recurrent analogue of the page-table rollback)."""
    zw = jnp.moveaxis(z, 1, 0)[:, :, None, :]       # (W, B, 1, D)

    def step(carry, zi):
        y, ns = bt.decode_step(cfg, bp, carry, zi, rc, **opts)
        return ns, (y, ns)

    _, (ys, states) = jax.lax.scan(step, ls, zw)
    y = jnp.moveaxis(ys[:, :, 0, :], 0, 1)          # (B, W, D)
    return y, states                                # leaves: (W, B, ...)


def _stack_verify(cfg, stack: StackPlan, params, state, x, rc: RunCtx):
    """Stateful stack walk over a speculative-verify window: paged
    blocks score the whole window in one call (``BlockType.verify``);
    recurrent blocks fall back to a per-offset decode_step scan whose
    mutable state gains a leading (W,) axis (see :func:`_window_scan`);
    read-only state (cross-attn K/V) passes through untouched."""
    blocks_p = params[stack.scope]

    def body(h, xs):
        bp, ls = xs
        new = {}
        for sl in stack.sublayers:
            bt = get_block(sl.block)
            z = L.norm_apply(cfg, _get(bp, sl.ln), h)
            opts = dict(sl.opts)
            if not bt.stateful:
                y, _ = bt.apply(cfg, _get(bp, sl.mixer), z, rc, **opts)
            elif bt.verify is not None:
                y, ns = bt.verify(cfg, _get(bp, sl.mixer),
                                  _get(ls, sl.mixer), z, rc, **opts)
                if bt.mutable_state:
                    _set(new, sl.mixer, ns)
            elif not bt.mutable_state:      # read-only: window in one call
                y, _ = bt.decode_step(cfg, _get(bp, sl.mixer),
                                      _get(ls, sl.mixer), z, rc, **opts)
            else:
                y, ns = _window_scan(bt, cfg, _get(bp, sl.mixer),
                                     _get(ls, sl.mixer), z, rc, opts)
                _set(new, sl.mixer, ns)
            h = h + y
        return h, new

    x, stacked = jax.lax.scan(body, x, (blocks_p, state))
    out = _copy_tree(state)           # read-only leaves keep their buffers
    for sl in stack.sublayers:
        bt = get_block(sl.block)
        if bt.stateful and bt.mutable_state:
            _set(out, sl.mixer, _get(stacked, sl.mixer))
    return x, out


def _stack_chunk(cfg, stack: StackPlan, params, state, x, rc: RunCtx):
    """Stateful stack walk over one prompt chunk written straight into
    the page pool: paged blocks take the whole chunk in one call
    (``BlockType.prefill_paged`` -- K/V scattered through ``rc.pages``,
    read via the flash-prefill sweep); recurrent blocks (mamba, rwkv)
    advance their dense state through their ordinary multi-token
    ``prefill`` scan -- final state only, no per-offset snapshots, which
    is what separates this from :func:`_stack_verify` (prefill never
    rolls back); read-only state (cross-attn K/V) passes through."""
    blocks_p = params[stack.scope]

    def body(h, xs):
        bp, ls = xs
        new = {}
        for sl in stack.sublayers:
            bt = get_block(sl.block)
            z = L.norm_apply(cfg, _get(bp, sl.ln), h)
            opts = dict(sl.opts)
            if not bt.stateful:
                y, _ = bt.apply(cfg, _get(bp, sl.mixer), z, rc, **opts)
            elif bt.prefill_paged is not None:
                y, ns = bt.prefill_paged(cfg, _get(bp, sl.mixer),
                                         _get(ls, sl.mixer), z, rc, **opts)
                if bt.mutable_state:
                    _set(new, sl.mixer, ns)
            elif not bt.mutable_state:      # read-only: chunk in one call
                y, _ = bt.decode_step(cfg, _get(bp, sl.mixer),
                                      _get(ls, sl.mixer), z, rc, **opts)
            else:
                y, ns = bt.prefill(cfg, _get(bp, sl.mixer),
                                   _get(ls, sl.mixer), z, rc, **opts)
                _set(new, sl.mixer, ns)
            h = h + y
        return h, new

    x, stacked = jax.lax.scan(body, x, (blocks_p, state))
    out = _copy_tree(state)           # read-only leaves keep their buffers
    for sl in stack.sublayers:
        bt = get_block(sl.block)
        if bt.stateful and bt.mutable_state:
            _set(out, sl.mixer, _get(stacked, sl.mixer))
    return x, out


# ---------------------------------------------------------------------------
# model functions (what build_model wires into the Model facade)


def forward(plan: ModelPlan, params, batch, last_only=False, perturb=None):
    """Train / prefill forward -> (logits, aux). ``perturb`` switches on
    the fused perturbed forward uniformly across families."""
    cfg = plan.cfg
    x = L.embed_apply(cfg, params["embed"], batch["tokens"],
                      ctx=_sub(perturb, "embed"))
    n_prefix = 0
    if "patch_embeds" in batch:                    # vlm: prepend stub patches
        x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
        n_prefix = batch["patch_embeds"].shape[1]
    enc_out = None
    if plan.encoder is not None:
        e = batch["enc_embeds"].astype(L._dt(cfg))
        erc = RunCtx(positions=jnp.arange(e.shape[1])[None])
        e, _ = _stack_apply(cfg, plan.encoder, params, e, erc, perturb)
        enc_out = L.norm_apply(cfg, params["ln_enc"], e,
                               _sub(perturb, "ln_enc"))
    rc = RunCtx(positions=jnp.arange(x.shape[1])[None],
                kv_mask=batch.get("attn_mask"), enc_out=enc_out)
    x, aux = _stack_apply(cfg, plan.stack, params, x, rc, perturb)
    x = L.norm_apply(cfg, params["ln_f"], x, _sub(perturb, "ln_f"))
    if cfg.n_classes:                  # CLS pooling + head (roberta/SST-2);
        with jax.named_scope(obs.CLS_HEAD):        # last_only has no meaning
            cls = x[:, 0].astype(jnp.float32)
            return L.dense(params["cls_head"], jnp.tanh(cls),
                           _sub(perturb, "cls_head")), aux
    if n_prefix:
        x = x[:, n_prefix:]
    if last_only:          # prefill: only the next-token logits are needed
        x = x[:, -1:]
    return L.unembed(cfg, params["embed"], params.get("lm_head"), x,
                     ctx=perturb), aux


def softmax_xent(logits, targets, mask=None):
    """Cross entropy that never materializes an f32 copy of the logits.

    Two measured pathologies avoided (EXPERIMENTS.md Sec Perf):
      * ``take_along_axis`` on vocab-sharded logits all-gathers the full
        logits across the model axis -- replaced by a one-hot masked sum
        (local + tiny psum);
      * upcasting logits to f32 with multiple consumers (lse AND gold)
        writes a full f32 logits tensor to HBM (12.9 GB/chip/pass on
        granite train_4k) -- instead, max/gold read the bf16 logits and
        the f32 exp-sum is a single-consumer fusion into its reduce.
    """
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    sumexp = jnp.sum(
        jnp.exp((logits - m[..., None]).astype(jnp.float32)), axis=-1)
    lse = m.astype(jnp.float32) + jnp.log(sumexp)
    gold = jnp.sum(
        jnp.where(jnp.arange(logits.shape[-1]) == targets[..., None],
                  logits, jnp.zeros((), logits.dtype)),
        axis=-1).astype(jnp.float32)
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / (jnp.sum(mask) + 1e-9)
    return jnp.mean(nll)


def loss(plan: ModelPlan, params, batch, perturb=None):
    """The ZO objective. ``perturb`` (a PerturbCtx) switches on the fused
    perturbed forward: params stay untouched, every weight use applies
    coeff*z in place (see core/perturb_ctx.py) -- in every family."""
    logits, aux = forward(plan, params, batch, perturb=perturb)
    with jax.named_scope(obs.LOSS):
        if plan.cfg.n_classes:                        # roberta/SST-2 path
            return softmax_xent(logits, batch["label"])
        ce = softmax_xent(logits, batch["targets"], batch.get("loss_mask"))
        return ce + AUX_LOSS_WEIGHT * aux


def init_cache(plan: ModelPlan, bsz, max_len, dtype):
    """The unified StateCache: every leaf is (n_layers, B, ...) -- layer
    stack on axis 0, batch on axis 1, regardless of family."""
    cfg = plan.cfg
    sub: dict = {}
    for sl in plan.stack.sublayers:
        bt = get_block(sl.block)
        if not bt.stateful:
            continue
        spec = bt.state_spec(cfg, bsz, max_len, dtype)
        _set(sub, sl.mixer,
             {name: jnp.zeros((plan.stack.n_layers,) + shape, dt)
              for name, (shape, dt) in spec.items()})
    return {plan.stack.scope: sub}


def plan_pages(plan: ModelPlan) -> bool:
    """True iff any sublayer of the main stack has pageable state."""
    return any(get_block(sl.block).paged_state_spec is not None
               for sl in plan.stack.sublayers)


def init_paged_cache(plan: ModelPlan, bsz, n_pages, page_size, dtype,
                     max_len=None):
    """Paged StateCache: pageable leaves (attention K/V) become
    ``(n_layers, n_pages, page_size, ...)`` pool leaves shared by every
    slot through a page table; everything else (mamba/rwkv recurrent
    state -- O(1) per slot) keeps the dense (n_layers, B, ...) layout.
    Physical page 0 is reserved as the trash page (unallocated table
    entries and masked-out writes land there), so allocators hand out
    pages 1..n_pages-1.
    """
    cfg = plan.cfg
    sub: dict = {}
    for sl in plan.stack.sublayers:
        bt = get_block(sl.block)
        if not bt.stateful:
            continue
        if bt.paged_state_spec is not None:
            spec = bt.paged_state_spec(cfg, dtype)
            leaves = {name: jnp.zeros(
                (plan.stack.n_layers, n_pages, page_size) + shape, dt)
                for name, (shape, dt) in spec.items()}
        else:
            spec = bt.state_spec(cfg, bsz, max_len or cfg.max_seq, dtype)
            leaves = {name: jnp.zeros((plan.stack.n_layers,) + shape, dt)
                      for name, (shape, dt) in spec.items()}
        _set(sub, sl.mixer, leaves)
    return {plan.stack.scope: sub}


def decode_step(plan: ModelPlan, params, cache, tokens, pos, pages=None,
                write_mask=None):
    """tokens: (B, 1) -> logits (B, 1, V); cache updated at ``pos``
    (scalar, or (B,) for continuous batching). With a paged cache,
    ``pages`` is the (B, n_live) physical page table slice and
    ``write_mask`` optionally confines state writes to a slot subset
    (masked slots scatter into the trash page)."""
    cfg = plan.cfg
    x = L.embed_apply(cfg, params["embed"], tokens,
                      positions=_decode_positions(pos))
    rc = RunCtx(pos=pos, pages=pages, write_mask=write_mask)
    x, state = _stack_seq(cfg, plan.stack, params, cache[plan.stack.scope],
                          x, rc, "decode")
    x = L.norm_apply(cfg, params["ln_f"], x)
    logits = L.unembed(cfg, params["embed"], params.get("lm_head"), x)
    return logits, {plan.stack.scope: state}


def verify_window(plan: ModelPlan, params, cache, tokens, pos, pages=None,
                  write_mask=None):
    """Speculative-verify scoring call: tokens (B, W) at per-slot
    positions ``pos .. pos + W - 1`` -> logits (B, W, V). Paged K/V for
    the whole window is written through the page table (so the pool
    afterwards holds the *verifier's* K/V at every window position);
    recurrent state leaves come back with a leading (W,) axis -- one
    snapshot per window offset -- for the engine's accept-prefix commit.
    ``write_mask`` is (B, W): offsets past a slot's live window scatter
    into the trash page."""
    cfg = plan.cfg
    pos = jnp.asarray(pos)
    positions = pos[:, None] + jnp.arange(tokens.shape[1])[None, :]
    x = L.embed_apply(cfg, params["embed"], tokens, positions=positions)
    rc = RunCtx(pos=pos, pages=pages, write_mask=write_mask)
    x, state = _stack_verify(cfg, plan.stack, params,
                             cache[plan.stack.scope], x, rc)
    x = L.norm_apply(cfg, params["ln_f"], x)
    logits = L.unembed(cfg, params["embed"], params.get("lm_head"), x)
    return logits, {plan.stack.scope: state}


def prefill_chunk(plan: ModelPlan, params, cache, tokens, pos, pages=None,
                  write_mask=None):
    """Chunked prefill into a paged cache: tokens (B, C) at per-slot
    positions ``pos .. pos + C - 1`` -> (logits (B, C, V), cache). Paged
    K/V for the chunk is written through the page table (the admission
    reservation guarantees ``pages`` covers ``pos + C - 1``); recurrent
    state leaves advance in place through each block's prefill scan --
    no dense B=1 prompt cache, no install scatter. ``write_mask`` is
    (B,) or (B, C): masked slots/offsets scatter into the trash page."""
    cfg = plan.cfg
    pos = jnp.asarray(pos)
    positions = pos[:, None] + jnp.arange(tokens.shape[1])[None, :]
    x = L.embed_apply(cfg, params["embed"], tokens, positions=positions)
    rc = RunCtx(pos=pos, positions=positions, pages=pages,
                write_mask=write_mask)
    x, state = _stack_chunk(cfg, plan.stack, params,
                            cache[plan.stack.scope], x, rc)
    x = L.norm_apply(cfg, params["ln_f"], x)
    logits = L.unembed(cfg, params["embed"], params.get("lm_head"), x)
    return logits, {plan.stack.scope: state}


def prefill(plan: ModelPlan, params, cache, tokens):
    """Fused prefill: one jitted call over the whole (B, P) prompt writes
    cache positions [0, P) and returns next-token logits (B, 1, V) --
    P decode_step dispatches collapsed into one layer-scan."""
    cfg = plan.cfg
    x = L.embed_apply(cfg, params["embed"], tokens)
    rc = RunCtx(positions=jnp.arange(tokens.shape[1])[None])
    x, state = _stack_seq(cfg, plan.stack, params, cache[plan.stack.scope],
                          x, rc, "prefill")
    x = L.norm_apply(cfg, params["ln_f"], x[:, -1:])
    logits = L.unembed(cfg, params["embed"], params.get("lm_head"), x)
    return logits, {plan.stack.scope: state}

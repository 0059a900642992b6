"""Family assembly over the block-registry runtime.

``build_model(cfg)`` returns a :class:`Model` bundle of pure functions:

  init(key)                          -> params
  forward(params, batch)             -> (logits, aux)   (train / prefill)
  loss(params, batch, perturb=...)   -> scalar          (the ZO objective)
  init_cache(bsz)                    -> StateCache pytree
  decode_step(params, cache, tok, pos) -> (logits, cache)
  prefill(params, cache, prompt)     -> (logits, cache)  (fused)

All five families share ONE implementation of forward / loss /
init_cache / decode_step / prefill -- the generic backbone engine in
:mod:`repro.models.runtime`, driven by a declarative :class:`ModelPlan`
assembled here from ``ModelConfig``. A family is just

  * a plan: which (norm, mixer) sublayers each layer holds, resolved
    against the block registry (``repro.models.blocks``), and
  * an init: how RNG keys route into each block's ``init`` (kept
    family-specific so parameter trees are bit-identical to the
    pre-registry layout -- existing checkpoints, replay logs, and leaf
    salts are untouched).

Because the engine threads ``PerturbCtx`` through every block uniformly,
the fused ZO perturbed forward works for every family -- no family
materializes a transient perturbed parameter copy in its loss path.

``prefill`` runs a whole (B, P) prompt in ONE call, writing cache
positions [0, P) and returning the next-token logits (B, 1, V) -- the
serving engine's replacement for P per-token ``decode_step`` dispatches.
``decode_step`` accepts ``pos`` as a scalar (whole batch at one
position) or as a (B,) vector (continuous batching). Layer stacks are
``lax.scan``-ed over stacked (L, ...) params so the HLO is O(1) in
depth -- essential for compiling 61-layer 1T-param configs.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models import layers as L
from repro.models import runtime as RT
from repro.models.config import ModelConfig
from repro.models.runtime import (AUX_LOSS_WEIGHT, ModelPlan, StackPlan,
                                  Sublayer, softmax_xent)

__all__ = ["Model", "build_model", "build_plan", "softmax_xent",
           "AUX_LOSS_WEIGHT"]

PyTree = Any


@dataclasses.dataclass(frozen=True, eq=False)
class Model:
    cfg: ModelConfig
    init: Callable
    forward: Callable
    loss: Callable
    init_cache: Callable
    decode_step: Optional[Callable] = None
    prefill: Optional[Callable] = None
    plan: Optional[ModelPlan] = None
    # paged serving: None when no sublayer has pageable state (rwkv6's
    # recurrent state never pages -- a "paged" engine then runs the
    # dense layout). Signature: init_paged_cache(bsz, n_pages,
    # page_size, max_len=None); decode_step takes pages=/write_mask=.
    init_paged_cache: Optional[Callable] = None
    # speculative decoding's exact scoring call over a paged cache:
    # verify_window(params, cache, toks (B, W), pos (B,), pages=,
    # write_mask=(B, W)) -> (logits (B, W, V), cache). None whenever
    # init_paged_cache is None (the verify window reads the page pool).
    verify_window: Optional[Callable] = None
    # chunked prefill straight into the page pool: prefill_chunk(params,
    # cache, toks (B, C), pos (B,), pages=, write_mask=) -> (logits
    # (B, C, V), cache) -- attention K/V written through the page table,
    # recurrent state advanced in place. None whenever init_paged_cache
    # is None (the chunk writes into the shared pool).
    prefill_chunk: Optional[Callable] = None


def _no_decode(*_args, **_kwargs):
    """Decode-path stub for encoder-only architectures."""
    raise ValueError("encoder-only arch has no decode path")


# ===========================================================================
# plans: which sublayers each family's layer holds


def _lm_plan(cfg: ModelConfig) -> ModelPlan:
    """Decoder-only LM (dense / moe / vlm-backbone) and the encoder-only
    classifier: [attn, ffn] per layer."""
    ffn = "moe" if cfg.n_experts else "mlp"
    return ModelPlan(cfg, StackPlan("blocks", cfg.n_layers, (
        Sublayer("ln_attn", "attn", "attention"),
        Sublayer("ln_ffn", ffn, ffn))))


def _hybrid_plan(cfg: ModelConfig) -> ModelPlan:
    """Hybrid (jamba): super-blocks of ``block_len`` sublayers -- mamba
    everywhere except ``attn_index``, an FFN (MoE on odd sublayers when
    configured) after each mixer."""
    subs = []
    for i in range(cfg.block_len):
        if i == cfg.attn_index:
            subs.append(Sublayer(f"sub_{i}/ln", f"sub_{i}/attn", "attention"))
        else:
            subs.append(Sublayer(f"sub_{i}/ln", f"sub_{i}/mamba", "mamba"))
        ffn = "moe" if cfg.n_experts and i % 2 == 1 else "mlp"
        subs.append(Sublayer(f"sub_{i}/ln_ffn", f"sub_{i}/{ffn}", ffn))
    return ModelPlan(cfg, StackPlan("blocks", cfg.n_layers // cfg.block_len,
                                    tuple(subs)))


def _rwkv_plan(cfg: ModelConfig) -> ModelPlan:
    return ModelPlan(cfg, StackPlan("blocks", cfg.n_layers, (
        Sublayer("ln1", "tm", "rwkv_timemix"),
        Sublayer("ln2", "cm", "rwkv_channelmix"))))


def _encdec_plan(cfg: ModelConfig) -> ModelPlan:
    """Encoder-decoder (whisper): stub conv frontend -> enc_embeds in the
    batch; decoder = [self-attn, cross-attn, mlp] per layer."""
    enc = StackPlan("enc_blocks", cfg.enc_layers, (
        Sublayer("ln_attn", "attn", "attention", (("causal", False),)),
        Sublayer("ln_ffn", "mlp", "mlp")))
    dec = StackPlan("dec_blocks", cfg.dec_layers, (
        Sublayer("ln_self", "self", "attention", (("causal", True),)),
        Sublayer("ln_cross", "cross", "cross_attention"),
        Sublayer("ln_ffn", "mlp", "mlp")))
    return ModelPlan(cfg, dec, encoder=enc)


_PLANS = {"dense": _lm_plan, "moe": _lm_plan, "encoder": _lm_plan,
          "hybrid": _hybrid_plan, "ssm": _rwkv_plan, "encdec": _encdec_plan}


def build_plan(cfg: ModelConfig) -> ModelPlan:
    if cfg.family not in _PLANS:
        raise ValueError(f"unknown family {cfg.family}")
    return _PLANS[cfg.family](cfg)


# ===========================================================================
# inits: family-specific RNG-key routing into block inits. The exact
# split/fold sequences are load-bearing: they pin parameter trees
# bit-identical across refactors (golden parity suite).


def _lm_block_init(cfg, key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"ln_attn": L.norm_init(cfg, k1),
         "attn": B.get_block("attention").init(cfg, k2),
         "ln_ffn": L.norm_init(cfg, k3)}
    if cfg.n_experts:
        p["moe"] = B.get_block("moe").init(cfg, k4)
    else:
        p["mlp"] = B.get_block("mlp").init(cfg, k4)
    return p


def _lm_init(cfg, key):
    ke, kb, kn, kh = jax.random.split(key, 4)
    blocks = jax.vmap(lambda k: _lm_block_init(cfg, k))(
        jax.random.split(kb, cfg.n_layers))
    p = {"embed": L.embed_init(cfg, ke), "blocks": blocks,
         "ln_f": L.norm_init(cfg, kn)}
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(kh, cfg.d_model, cfg.vocab, L._dt(cfg))
    if cfg.n_classes:
        p["cls_head"] = L.dense_init(kh, cfg.d_model, cfg.n_classes,
                                     jnp.float32, bias=True)
    return p


def _hybrid_block_init(cfg, key):
    nb = cfg.block_len
    ks = jax.random.split(key, 2 * nb)
    p = {}
    for i in range(nb):
        sub = {"ln": L.norm_init(cfg, ks[2 * i])}
        if i == cfg.attn_index:
            sub["attn"] = B.get_block("attention").init(cfg, ks[2 * i + 1])
        else:
            sub["mamba"] = B.get_block("mamba").init(cfg, ks[2 * i + 1])
        kf = jax.random.fold_in(ks[2 * i + 1], 7)
        sub["ln_ffn"] = L.norm_init(cfg, jax.random.fold_in(kf, 1))
        if cfg.n_experts and i % 2 == 1:
            sub["moe"] = B.get_block("moe").init(cfg, kf)
        else:
            sub["mlp"] = B.get_block("mlp").init(cfg, kf)
        p[f"sub_{i}"] = sub
    return p


def _hybrid_init(cfg, key):
    nb = cfg.n_layers // cfg.block_len
    ke, kb, kn, kh = jax.random.split(key, 4)
    blocks = jax.vmap(lambda k: _hybrid_block_init(cfg, k))(
        jax.random.split(kb, nb))
    return {"embed": L.embed_init(cfg, ke), "blocks": blocks,
            "ln_f": L.norm_init(cfg, kn),
            "lm_head": L.dense_init(kh, cfg.d_model, cfg.vocab, L._dt(cfg))}


def _rwkv_init(cfg, key):
    ke, kb, kn, kh = jax.random.split(key, 4)

    def block(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        return {"ln1": L.norm_init(cfg, k1),
                "tm": B.get_block("rwkv_timemix").init(cfg, k2),
                "ln2": L.norm_init(cfg, k3),
                "cm": B.get_block("rwkv_channelmix").init(cfg, k4)}

    blocks = jax.vmap(block)(jax.random.split(kb, cfg.n_layers))
    return {"embed": L.embed_init(cfg, ke), "blocks": blocks,
            "ln_f": L.norm_init(cfg, kn),
            "lm_head": L.dense_init(kh, cfg.d_model, cfg.vocab, L._dt(cfg))}


def _encdec_init(cfg, key):
    ke, kenc, kdec, kn = jax.random.split(key, 4)
    attn_init = B.get_block("attention").init
    mlp_init = B.get_block("mlp").init

    def enc_block(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        return {"ln_attn": L.norm_init(cfg, k1), "attn": attn_init(cfg, k2),
                "ln_ffn": L.norm_init(cfg, k3), "mlp": mlp_init(cfg, k4)}

    def dec_block(k):
        k1, k2, k3, k4, k5, k6 = jax.random.split(k, 6)
        return {"ln_self": L.norm_init(cfg, k1), "self": attn_init(cfg, k2),
                "ln_cross": L.norm_init(cfg, k3), "cross": attn_init(cfg, k4),
                "ln_ffn": L.norm_init(cfg, k5), "mlp": mlp_init(cfg, k6)}

    return {
        "embed": L.embed_init(cfg, ke),
        "enc_blocks": jax.vmap(enc_block)(jax.random.split(kenc, cfg.enc_layers)),
        "dec_blocks": jax.vmap(dec_block)(jax.random.split(kdec, cfg.dec_layers)),
        "ln_enc": L.norm_init(cfg, kn),
        "ln_f": L.norm_init(cfg, jax.random.fold_in(kn, 1)),
    }


_INITS = {"dense": _lm_init, "moe": _lm_init, "encoder": _lm_init,
          "hybrid": _hybrid_init, "ssm": _rwkv_init, "encdec": _encdec_init}


# ===========================================================================
# the facade


@functools.lru_cache(maxsize=None)
def build_model(cfg: ModelConfig) -> Model:
    """Memoized on the (frozen, hashable) config: every caller holding
    the same config shares ONE Model instance, so the jitted serving /
    decode entry points traced against its bound functions hit the
    compilation cache across engines instead of re-tracing per engine
    (the dominant cost of the pre-paging decode baseline)."""
    plan = build_plan(cfg)
    dtype = L._dt(cfg)
    init = partial(_INITS[cfg.family], cfg)
    if cfg.family == "encoder":
        return Model(
            cfg=cfg, plan=plan, init=init,
            forward=partial(RT.forward, plan),
            loss=partial(RT.loss, plan),
            init_cache=_no_decode,
            decode_step=None,
        )
    return Model(
        cfg=cfg, plan=plan, init=init,
        forward=partial(RT.forward, plan),
        loss=partial(RT.loss, plan),
        init_cache=lambda bsz, max_len=None: RT.init_cache(
            plan, bsz, max_len or cfg.max_seq, dtype),
        decode_step=partial(RT.decode_step, plan),
        prefill=None if cfg.n_classes else partial(RT.prefill, plan),
        init_paged_cache=(
            (lambda bsz, n_pages, page_size, max_len=None:
             RT.init_paged_cache(plan, bsz, n_pages, page_size, dtype,
                                 max_len=max_len))
            if RT.plan_pages(plan) else None),
        verify_window=(partial(RT.verify_window, plan)
                       if RT.plan_pages(plan) else None),
        prefill_chunk=(partial(RT.prefill_chunk, plan)
                       if RT.plan_pages(plan) else None),
    )

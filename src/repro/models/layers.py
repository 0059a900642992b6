"""Shared neural layers: norms, RoPE, attention (GQA/MQA, chunked), MLPs.

Conventions:
  * params are plain nested dicts of jnp arrays,
  * activations flow in the param dtype (bf16 on TPU), softmax/norm math
    in f32,
  * every parameterized apply-fn takes an optional ``ctx``
    (:class:`repro.core.perturb_ctx.PerturbCtx`, scoped to its param
    sub-dict). ``ctx=None`` is the plain forward; with a ctx, dense
    weights compute X @ (W + coeff*z) through the fused ZO kernel and all
    other leaves add a transient coeff*z -- the perturbed forward of the
    fused MeZO step, bit-compatible with perturbing the param tree,
  * attention is memory-efficient: for long sequences the query axis is
    processed in chunks under ``lax.scan`` so the (S, T) score tensor is
    never materialized in full (prefill_32k / train_4k would otherwise
    need hundreds of GB of scores per device).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.perturb_ctx import sub as _sub
from repro.kernels import flash_attention as _flash
from repro.kernels import ops as _ops
from repro.optim.quant import deq as _deq
from repro.optim.quant import take_rows as _take_rows

# ---------------------------------------------------------------------------
# norms


def rmsnorm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def norm_init(cfg, key):
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                "bias": jnp.zeros((cfg.d_model,), jnp.float32)}
    return {"scale": jnp.ones((cfg.d_model,), jnp.float32)}


def norm_apply(cfg, p, x, ctx=None):
    if ctx is not None:
        p = {k: ctx.perturb(k, v) for k, v in p.items()}
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


# ---------------------------------------------------------------------------
# rotary position embedding (full / partial per rope_pct)


def rope_cos_sin(positions, head_dim: int, rope_pct: float, theta: float):
    """positions: int array (...,). Returns cos/sin of shape (..., rot/2)."""
    rot = int(head_dim * rope_pct)
    rot -= rot % 2
    if rot == 0:
        return None
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos_sin):
    """x: (..., S, H, hd); cos/sin: (..., S, rot/2) broadcast over H."""
    if cos_sin is None:
        return x
    cos, sin = cos_sin
    rot2 = cos.shape[-1]
    xr, xp = x[..., :2 * rot2], x[..., 2 * rot2:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr, xp], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# dense projections


def dense_init(key, d_in, d_out, dtype, scale=0.02, bias=False):
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
               ).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x, ctx=None):
    """ctx=None is the plain forward (quantized weights dequantize
    transiently at the use site); with a ctx the perturbation -- and for
    a quantized base the dequant too -- fuses into the matmul."""
    y = x @ _deq(p["w"]) if ctx is None else ctx.matmul(x, p["w"], "w")
    if "b" in p:
        y = y + (_deq(p["b"]) if ctx is None else ctx.perturb("b", p["b"]))
    return y


# ---------------------------------------------------------------------------
# attention


def _sdpa(q, k, v, mask, dtype):
    """q: (B, S, KV, G, hd); k/v: (B, T, KV, hd); mask broadcastable to
    (B, KV, G, S, T). Softmax in f32."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    scores = jnp.einsum("bskgh,btkh->bkgst", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    scores = jnp.where(mask, scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(dtype), v)
    return out


def attention(q, k, v, *, causal: bool, q_offset=0,
              kv_mask: Optional[jnp.ndarray] = None, chunk: int = 0):
    """GQA attention. q: (B, S, H, hd); k/v: (B, T, KV, hd).

    kv_mask is (B, T) key validity shared by every query row, or
    (B, S, T) with a mask per query row (speculative verify windows:
    each candidate token has its own position limit).

    chunk > 0 and S % chunk == 0 and S > chunk: scan over query chunks so
    peak score memory is (B, H, chunk, T) instead of (B, H, S, T).
    """
    b, s, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, hd)
    kv_pos = jnp.arange(t)

    def block_mask(q_pos):
        m = jnp.ones((q_pos.shape[0], t), bool)
        if causal:
            m = q_pos[:, None] >= kv_pos[None, :]
        m = m[None, None, None]                      # (1,1,1,S,T)
        if kv_mask is not None:
            if kv_mask.ndim == 3:                    # per-query-row masks
                rows = jnp.take(kv_mask, q_pos - q_offset, axis=1)
                m = m & rows[:, None, None, :, :]    # (B,1,1,S,T)
            else:
                m = m & kv_mask[:, None, None, None, :]  # (B,1,1,1,T)
        return m

    if chunk and s > chunk and s % chunk == 0:
        nc = s // chunk
        qc = qg.reshape(b, nc, chunk, kvh, g, hd).transpose(1, 0, 2, 3, 4, 5)

        def body(_, inp):
            qi, ci = inp
            q_pos = q_offset + ci * chunk + jnp.arange(chunk)
            return None, _sdpa(qi, k, v, block_mask(q_pos), q.dtype)

        _, out = jax.lax.scan(body, None, (qc, jnp.arange(nc)))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, hd)
        return out

    q_pos = q_offset + jnp.arange(s)
    out = _sdpa(qg, k, v, block_mask(q_pos), q.dtype)
    return out.reshape(b, s, h, hd)


def attn_init(cfg, key, d_model=None):
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    bias = cfg.norm == "layernorm"
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, cfg.n_heads * hd, _dt(cfg), bias=bias),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, _dt(cfg), bias=bias),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, _dt(cfg), bias=bias),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, _dt(cfg),
                         scale=0.02 / max(cfg.n_layers, 1) ** 0.5, bias=bias),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def attn_project_qkv(cfg, p, x, ctx=None):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = dense(p["wq"], x, _sub(ctx, "wq")).reshape(b, s, cfg.n_heads, hd)
    k = dense(p["wk"], x, _sub(ctx, "wk")).reshape(b, s, cfg.n_kv_heads, hd)
    v = dense(p["wv"], x, _sub(ctx, "wv")).reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        qn = p["q_norm"] if ctx is None else ctx.perturb("q_norm", p["q_norm"])
        kn = p["k_norm"] if ctx is None else ctx.perturb("k_norm", p["k_norm"])
        q = rmsnorm(q, qn)
        k = rmsnorm(k, kn)
    return q, k, v


def attn_apply(cfg, p, x, *, positions=None, kv_mask=None, causal=None,
               ctx=None):
    """Self-attention over x: (B, S, D). positions: (B, S) or None.

    The core runs as the Pallas flash kernel where
    :func:`repro.kernels.flash_attention.takes` says it can, else as the
    jnp :func:`attention`."""
    b, s, _ = x.shape
    q, k, v = attn_project_qkv(cfg, p, x, ctx)
    if cfg.pos == "rope":
        pos = positions if positions is not None else jnp.arange(s)[None]
        cs = rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_pct,
                          cfg.rope_theta)
        q, k = apply_rope(q, cs), apply_rope(k, cs)
    causal = cfg.causal if causal is None else causal
    with jax.named_scope(obs.ATTENTION):
        if _flash.takes(q.shape, k.shape, q.dtype, causal=causal,
                        kv_mask=kv_mask, backend=_ops.BACKEND):
            obs.attention_core(obs.ATTN_KERNEL)
            out = _ops.flash_attention(q, k, v, causal=causal)
        else:
            obs.attention_core(obs.ATTN_JNP)
            out = attention(q, k, v, causal=causal, kv_mask=kv_mask,
                            chunk=cfg.attn_chunk)
    return dense(p["wo"], out.reshape(b, s, -1), _sub(ctx, "wo"))


def cross_attn_apply(cfg, p, x, enc_kv, ctx=None):
    """Decoder cross-attention (whisper): kv from encoder output (the
    K/V projections perturb where kv is computed -- blocks/cross_attention)."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = dense(p["wq"], x, _sub(ctx, "wq")).reshape(b, s, cfg.n_heads, hd)
    k, v = enc_kv
    out = attention(q, k, v, causal=False, chunk=0)
    return dense(p["wo"], out.reshape(b, s, -1), _sub(ctx, "wo"))


# ---------------------------------------------------------------------------
# MLPs


def _dt(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def mlp_init(cfg, key, d_ff=None, d_model=None):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    bias = cfg.norm == "layernorm"
    k1, k2 = jax.random.split(key)
    gated = cfg.act in ("swiglu", "geglu")
    if gated:
        # interleaved (D, F, 2) layout: up/gate pairs live on the SAME
        # tensor-parallel shard, so the split below is shard-local. The
        # flat (D, 2F) layout splits across the model axis and costs a
        # collective-permute of the whole hidden per layer (measured:
        # 57 GB/chip/step on qwen3-4b train_4k -- EXPERIMENTS.md Sec Perf)
        w = (jax.random.normal(k1, (d, f, 2), jnp.float32) * 0.02
             ).astype(_dt(cfg))
        p_in = {"w": w}
    else:
        p_in = dense_init(k1, d, f, _dt(cfg), bias=bias)
    return {
        "w_in": p_in,
        "w_out": dense_init(k2, f, d, _dt(cfg),
                            scale=0.02 / max(cfg.n_layers, 1) ** 0.5,
                            bias=bias),
    }


def mlp_apply(cfg, p, x, ctx=None):
    if cfg.act in ("swiglu", "geglu"):
        # gated w_in is an interleaved (D, F, 2) leaf: its z-field spans 3
        # dims, so the 2-D fused kernel doesn't apply -- transient perturb
        w_in = _deq(p["w_in"]["w"]) if ctx is None else \
            ctx.perturb("w_in/w", p["w_in"]["w"])
        h = jnp.einsum("...d,dfg->...fg", x, w_in)
        u, g = h[..., 0], h[..., 1]
        gate = jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g)
        h = u * gate
    else:
        h = dense(p["w_in"], x, _sub(ctx, "w_in"))
        h = jax.nn.gelu(h) if cfg.act == "gelu" else jax.nn.relu(h)
    return dense(p["w_out"], h, _sub(ctx, "w_out"))


# ---------------------------------------------------------------------------
# embedding


def embed_init(cfg, key):
    e = {"tok": (jax.random.normal(key, (cfg.vocab, cfg.d_model), jnp.float32)
                 * 0.02).astype(_dt(cfg))}
    if cfg.pos == "learned":
        e["pos"] = (jax.random.normal(jax.random.fold_in(key, 1),
                                      (cfg.max_seq, cfg.d_model), jnp.float32)
                    * 0.02).astype(_dt(cfg))
    return e


def embed_apply(cfg, p, tokens, positions=None, ctx=None):
    """ctx (scoped to "embed") perturbs only the gathered rows: O(S*D)
    transient z, never the (V, D) table."""
    if ctx is None:
        x = _take_rows(p["tok"], tokens)
    else:
        x = ctx.take("tok", p["tok"], tokens)
    if cfg.pos == "learned":
        pos = positions if positions is not None else jnp.arange(tokens.shape[-1])
        if ctx is None:
            x = x + _take_rows(p["pos"], pos)
        else:
            x = x + ctx.take("pos", p["pos"], pos)
    return x


def unembed(cfg, embed_p, head_p, x, ctx=None):
    """Final projection to vocab logits (tied or untied). ctx is scoped to
    the param-tree ROOT here (the two branches touch different leaves)."""
    with jax.named_scope(obs.LM_HEAD):
        if cfg.tie_embeddings or head_p is None:
            if ctx is None:
                return x @ _deq(embed_p["tok"]).T
            # tied head reads the embedding transposed; the row-major
            # z-field doesn't transpose into kernel tiles, so perturb
            # transiently
            return x @ ctx.scope("embed").perturb("tok", embed_p["tok"]).T
        return dense(head_p, x, _sub(ctx, "lm_head"))

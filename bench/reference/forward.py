"""Plain float32 forwards of the benchmark's two models, in jax.numpy.

Both are pre-LayerNorm transformers over the parameter tree the replay
log addresses (``embed/tok``, ``embed/pos``, ``blocks/...`` stacked on a
leading layer axis, ``ln_f``, ``lm_head`` or ``cls_head``):

* opt-1.3b, a causal LM: ``x = tok[ids] + pos[0..S)``; per layer
  ``x += attn(LN(x))``, ``x += fc2(relu(fc1(LN(x))))``; logits
  ``LN(x) @ lm_head``. Departures from facebook/opt-1.3b, which the
  system under test shares: the LM head is its own matrix (OPT ties it
  to the embedding), positions start at 0 (OPT offsets them by 2).
* roberta-large, an encoder with a 2-class head: the same layer without
  the causal mask and with GELU; logits ``tanh(LN(x)[:, 0]) @
  cls_head``. Departures from FacebookAI/roberta-large, shared with the
  system: LayerNorm before each sublayer (RoBERTa normalises after the
  residual), GELU in its tanh form, no token-type embedding, no dense
  layer in the classification head before the tanh.

Every matrix product goes through ``mm``, which is float32 at
``Precision.HIGHEST``; the control of the output check passes an ``mm``
one precision below the model's (``control_mm``).

``perturb=(seed, coeff)`` evaluates the model at ``theta + coeff *
z(seed)`` with z regenerated per leaf (``zhash``), one layer at a time.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from bench.reference import zhash

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def mm_f32(x, w):
    return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm_fp8(x, w):
    """The control: both operands rounded to float8 e4m3 (per row of x,
    per column of w), products summed in float32."""
    return jnp.matmul(_fp8(x.astype(F32), -1), _fp8(w.astype(F32), -2),
                      precision=HIGHEST)


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(F32)).astype(jnp.bfloat16)


def mm_high(x, w):
    """The control of a float32 model at ``HIGHEST``: three bfloat16
    passes, as ``Precision.HIGH`` makes them (x_hi w_hi + x_hi w_lo +
    x_lo w_hi, summed in float32), spelled out so that every backend
    computes the same."""
    (xh, xl), (wh, wl) = _split_bf16(x.astype(F32)), _split_bf16(
        w.astype(F32))

    def dot(a, b):
        return jnp.matmul(a, b, preferred_element_type=F32)

    return dot(xh, wh) + dot(xh, wl) + dot(xl, wh)


def control_mm(dtype: str) -> Callable:
    """The matrix product one precision below the model's: float8 for a
    bfloat16 model, three-pass ``HIGH`` for a float32 one."""
    return {"bfloat16": mm_fp8, "float32": mm_high}[dtype]


def _leaf(params, path: str, perturb, layer=None):
    """The f32 value of leaf ``path`` (slice ``layer`` when stacked),
    perturbed when ``perturb`` is given."""
    node = params
    for part in path.split("/"):
        node = node[part]
    w = node if layer is None else node[layer]
    w = w.astype(F32)
    if perturb is None:
        return w
    seed, coeff = perturb
    z = (zhash.z_full(seed, path, w.shape) if layer is None
         else zhash.z_layer(seed, path, layer, w.shape))
    return w + jnp.asarray(coeff, F32) * z


def _embed_rows(params, path, ids, perturb):
    node = params
    for part in path.split("/"):
        node = node[part]
    rows = node.astype(F32)[ids]
    if perturb is None:
        return rows
    seed, coeff = perturb
    return rows + jnp.asarray(coeff, F32) * zhash.z_rows(
        seed, path, ids, node.shape[1])


def layer_norm(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _dense(params, prefix, x, perturb, layer, mm):
    y = mm(x, _leaf(params, f"{prefix}/w", perturb, layer))
    node = params
    for part in prefix.split("/"):
        node = node[part]
    if "b" in node:
        y = y + _leaf(params, f"{prefix}/b", perturb, layer)
    return y


def _block(params, x, layer, *, n_heads: int, causal: bool, act: str,
           perturb, mm):
    b, s, d = x.shape
    hd = d // n_heads
    lf = lambda p: _leaf(params, p, perturb, layer)            # noqa: E731
    dn = lambda p, h: _dense(params, p, h, perturb, layer, mm)  # noqa: E731

    h = layer_norm(x, lf("blocks/ln_attn/scale"), lf("blocks/ln_attn/bias"))
    q = dn("blocks/attn/wq", h).reshape(b, s, n_heads, hd)
    k = dn("blocks/attn/wk", h).reshape(b, s, n_heads, hd)
    v = dn("blocks/attn/wv", h).reshape(b, s, n_heads, hd)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))    # (B,H,S,hd)
    scores = mm(q / jnp.sqrt(F32(hd)), k.transpose(0, 1, 3, 2))
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = mm(probs, v).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + dn("blocks/attn/wo", att)

    h = layer_norm(x, lf("blocks/ln_ffn/scale"), lf("blocks/ln_ffn/bias"))
    h = dn("blocks/mlp/w_in", h)
    h = jax.nn.relu(h) if act == "relu" else jax.nn.gelu(h, approximate=True)
    return x + dn("blocks/mlp/w_out", h)


def hidden(params, tokens, *, n_heads: int, causal: bool, act: str,
           perturb: Optional[Tuple] = None, mm: Callable = mm_f32):
    """Final-LayerNorm hidden states (B, S, D), f32."""
    s = tokens.shape[1]
    x = (_embed_rows(params, "embed/tok", tokens, perturb)
         + _embed_rows(params, "embed/pos", jnp.arange(s), perturb)[None])
    n_layers = params["blocks"]["ln_attn"]["scale"].shape[0]

    def body(x, layer):
        return _block(params, x, layer, n_heads=n_heads, causal=causal,
                      act=act, perturb=perturb, mm=mm), None

    x, _ = jax.lax.scan(body, x, jnp.arange(n_layers))
    return layer_norm(x, _leaf(params, "ln_f/scale", perturb),
                      _leaf(params, "ln_f/bias", perturb))


def lm_logits(params, tokens, *, n_heads: int, perturb=None, mm=mm_f32):
    """opt-1.3b: (B, S, V) next-token logits."""
    x = hidden(params, tokens, n_heads=n_heads, causal=True, act="relu",
               perturb=perturb, mm=mm)
    return _dense(params, "lm_head", x, perturb, None, mm)


def cls_logits(params, tokens, *, n_heads: int, perturb=None, mm=mm_f32):
    """roberta-large: (B, n_classes) logits from the first position."""
    x = hidden(params, tokens, n_heads=n_heads, causal=False, act="gelu",
               perturb=perturb, mm=mm)
    return _dense(params, "cls_head", jnp.tanh(x[:, 0]), perturb, None, mm)


def xent(logits, labels):
    """Mean cross entropy, f32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def loss(params, batch, *, task: str, n_heads: int, perturb=None,
         mm=mm_f32):
    """The fine-tune objective of a batch, as the system defines it."""
    if task == "lm":
        return xent(lm_logits(params, batch["tokens"], n_heads=n_heads,
                              perturb=perturb, mm=mm), batch["targets"])
    return xent(cls_logits(params, batch["tokens"], n_heads=n_heads,
                           perturb=perturb, mm=mm), batch["label"])

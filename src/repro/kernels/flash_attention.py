"""Forward-only flash attention (Pallas TPU), GQA + causal.

ZO fine-tuning needs no backward pass, so the *inference* kernel is the
training kernel -- no stored softmax statistics, no recompute policy.
Online softmax over K/V tiles keeps each (bq, bk) score tile in VMEM;
the (S, T) score matrix never exists in HBM. The jnp ``attention`` it
replaces in the train forward writes the whole f32 score tensor and
reads it back through mask and softmax: for opt-1.3b at batch 8 x seq
512 that is 268 MB per layer-forward, 54 ms of a 228 ms step on a v5e.

Layout: the kernel reads the projections' own (B, S, H*hd) and
(B, T, KV*hd) layouts, so every block is lane-dense and no transpose to
head-major exists. Grid (B, KV / hkv): one step takes the whole
sequence of hkv KV heads and their G query heads each -- (S, hkv*G*hd)
queries against (T, hkv*hd) keys and values -- so q, k, v and the
output cross HBM once, in a few dozen steps. Inside a step a static
walk takes each head's query rows bq at a time over the key tiles they
see: whole tiles of up to bk keys, then, when causal, the (bq, bq) tile
on the diagonal, the only one masked; tiles above the diagonal cost
neither a dot nor a fetch. Heads narrower than 128 lanes (hd 64, no
GQA) share their 128-lane group: each reads the whole group with the
other heads' query lanes zeroed, so no operand is shifted across lanes.

Arithmetic is the jnp path's: the dots take the operands' dtype with f32
accumulation (bf16 probabilities against bf16 V), softmax and the
running (m, l, acc) are f32, and f32 operands take the ambient default
matmul precision (HIGHEST where it is set). :func:`attention_blocks`
sizes the tiles from the shapes against the scoped VMEM; :func:`takes`
says whether the kernel takes a call at all.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_decode import MXU_HEAD_DIMS, check_head_dim
from repro.kernels.zo_perturb import VMEM_BUDGET, _dot_at_highest, _sizes

_NEG_INF = -1e30
# S and T must be multiples of this: the score tile's lane axis is a
# slice of T, and its rows a slice of S
ROW_TILE = 128
# the score tile's sides at most (rows x keys)
MAX_BQ, MAX_BK = 128, 512


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _seen(s: int, t: int, bq: int, causal: bool) -> int:
    """Scores the query blocks of bq rows compute against t keys."""
    if not causal:
        return s * t
    return sum(bq * min(t, r0 + bq) for r0 in range(0, s, bq))


def attention_vmem_bytes(hkv: int, g: int, hd: int, s: int, t: int,
                         bq: int, dtype, causal: bool = True,
                         highest: bool = False) -> int:
    """VMEM one grid step holds: double-buffered query and output blocks
    (s, hkv*G*hd) and K and V blocks (t, hkv*hd), lanes padded to 128;
    since the walk over heads and tiles is unrolled and the compiler
    keeps each tile's values apart, 5 bytes for every score of every
    head (15 where f32 dots at HIGHEST split their operands into bf16
    parts); and 1 MiB besides. Fitted to what the compiler reserves for
    a v5e (topology compiles of 5 shapes in bf16 and 4 in f32, 2-16
    heads a step), and at or above it in each."""
    isz = jnp.dtype(dtype).itemsize
    pipelined = 2 * (2 * s * _lanes(hkv * g * hd)
                     + 2 * t * _lanes(hkv * hd)) * isz
    per_score = 15 if highest else 5
    return (pipelined + per_score * hkv * g * _seen(s, t, bq, causal)
            + (1 << 20))


def attention_blocks(s: int, t: int, h: int, kvh: int, hd: int, dtype,
                     causal: bool = True, highest: bool = False
                     ) -> Optional[Tuple[int, int, int]]:
    """(hkv, bq, bk) for q (B, s, h, hd) against K/V (B, t, kvh, hd): the
    score tile's rows and keys are the largest aligned sizes within
    MAX_BQ and MAX_BK; hkv, the KV heads a grid step takes, is the most
    whose :func:`attention_vmem_bytes` fits :data:`VMEM_BUDGET` among
    those whose blocks the tiling accepts (hkv*hd a multiple of 128, or
    every head). None where none fits."""
    bq = max(b for b in _sizes(s, 8) if b <= MAX_BQ) if s > MAX_BQ else s
    bk = max(b for b in _sizes(t, 128) if b <= MAX_BK) if t > MAX_BK else t
    for n in range(kvh, 0, -1):
        if kvh % n == 0 and (n * hd % 128 == 0 or n == kvh) and \
                attention_vmem_bytes(n, h // kvh, hd, s, t, bq, dtype,
                                     causal, highest) <= VMEM_BUDGET:
            return n, bq, bk
    return None


def _highest(dtype) -> bool:
    """Whether the kernel's dots run at HIGHEST: f32 operands under a
    default matmul precision of HIGHEST (bf16 products are exact)."""
    return jnp.dtype(dtype) == jnp.float32 and _dot_at_highest()


def takes(q_shape, k_shape, dtype, *, causal: bool = True, kv_mask=None,
          backend: str = "tpu") -> bool:
    """Whether :func:`flash_attention` runs this self-attention core on
    the chip: a TPU backend, no key mask, bf16 or f32 operands, a head
    dim the MXU tiles, S and T multiples of :data:`ROW_TILE` (equal when
    causal), and a tiling that fits VMEM. Never raises; the caller takes
    the jnp ``attention`` otherwise."""
    _, s, h, hd = q_shape
    t, kvh = k_shape[1], k_shape[2]
    return (backend == "tpu" and kv_mask is None and hd in MXU_HEAD_DIMS
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
            and kvh > 0 and h % kvh == 0
            and s % ROW_TILE == 0 and t % ROW_TILE == 0
            and (s == t or not causal)
            and attention_blocks(s, t, h, kvh, hd, dtype, causal,
                                 _highest(dtype)) is not None)


def _key_tiles(r0: int, bq: int, bk: int, t: int, causal: bool):
    """(k0, width, masked) of the key tiles that query rows r0 .. r0+bq-1
    see, in order: whole tiles of up to bk keys, then, when causal, the
    (bq, bq) tile on the diagonal, which alone needs the mask."""
    hi = min(t, r0) if causal else t
    out = [(k0, min(bk, hi - k0), False) for k0 in range(0, hi, bk)]
    if causal and r0 < t:
        out.append((r0, min(bq, t - r0), True))
    return out


def _head_groups(hkv: int, g: int, hd: int):
    """How the query heads of a grid step read their lanes: a list of
    (q/output lanes, K/V lanes, per-head lane masks or None). Heads
    narrower than 128 lanes without GQA read the whole 128-lane group
    they share, each with the other heads' query lanes zeroed, so no
    operand is shifted across lanes and the output is stored whole; the
    zeros add nothing to a score, and each head's lanes of its P.V are
    its own. Other heads read their own lanes."""
    if g == 1 and hd < 128 and 128 % hd == 0 and hkv * hd % 128 == 0:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1) // hd
        masks = [lane == i for i in range(128 // hd)]
        return [(slice(c * 128, (c + 1) * 128),) * 2 + (masks,)
                for c in range(hkv * hd // 128)]
    return [(slice((c * g + j) * hd, (c * g + j + 1) * hd),
             slice(c * hd, (c + 1) * hd), None)
            for c in range(hkv) for j in range(g)]


def _attend(q, k_ref, v_ref, kv_lanes, r0, diag, *, bk, causal, precision):
    """Online softmax of one head's query rows r0 .. r0+bq-1 over the key
    tiles they see; returns the normalized (bq, lanes) output in f32."""
    t = k_ref.shape[0]
    m = l = acc = None
    for k0, w, masked in _key_tiles(r0, q.shape[0], bk, t, causal):
        s = jax.lax.dot_general(q, k_ref[k0:k0 + w, kv_lanes],
                                (((1,), (1,)), ((), ())), precision=precision,
                                preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(diag[:, :w], s, _NEG_INF)
        m_t = jnp.max(s, axis=1, keepdims=True)
        m_new = m_t if m is None else jnp.maximum(m, m_t)
        p = jnp.exp(s - m_new)
        v = v_ref[k0:k0 + w, kv_lanes]
        pv = jnp.dot(p.astype(v.dtype), v, precision=precision,
                     preferred_element_type=jnp.float32)
        l_t = jnp.sum(p, axis=1, keepdims=True)
        if m is None:
            l, acc = l_t, pv
        else:
            alpha = jnp.exp(m - m_new)
            l, acc = alpha * l + l_t, alpha * acc + pv
        m = m_new
    return acc / l


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, g, hd, bq, bk, causal,
                  scale, precision):
    hkv = k_ref.shape[1] // hd
    diag = (jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1))
    attend = functools.partial(_attend, k_ref=k_ref, v_ref=v_ref, diag=diag,
                               bk=bk, causal=causal, precision=precision)
    for lanes, kv_lanes, masks in _head_groups(hkv, g, hd):
        for r0 in range(0, q_ref.shape[0], bq):
            q = q_ref[r0:r0 + bq, lanes].astype(jnp.float32) * scale
            if masks is None:
                out = attend(q.astype(q_ref.dtype), kv_lanes=kv_lanes, r0=r0)
            else:
                out = None
                for mask in masks:
                    o = attend(jnp.where(mask, q, 0.0).astype(q_ref.dtype),
                               kv_lanes=kv_lanes, r0=r0)
                    out = o if out is None else jnp.where(mask, o, out)
            o_ref[r0:r0 + bq, lanes] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "blocks",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, blocks=None,
                    interpret: bool = False):
    """q: (B, S, H, hd); k/v: (B, T, KV, hd) -> (B, S, H, hd).

    Query row i sees key j where j <= i when causal. ``blocks=None``
    picks (hkv, bq, bk) with :func:`attention_blocks`; a tuple pins it.
    """
    b, s, h, hd = q.shape
    check_head_dim(hd, interpret=interpret, kernel="flash_attention")
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    highest = _highest(q.dtype)
    if blocks is None:
        blocks = attention_blocks(s, t, h, kvh, hd, q.dtype, causal,
                                  highest)
        if blocks is None:
            raise ValueError(f"flash_attention: no tiling of q {q.shape} "
                             f"and k {k.shape} fits VMEM")
    hkv, bq, bk = blocks
    kern = functools.partial(
        _flash_kernel, g=g, hd=hd, bq=bq, bk=bk, causal=causal,
        scale=1.0 / float(hd) ** 0.5,
        precision=(jax.lax.Precision.HIGHEST if highest
                   else jax.lax.Precision.DEFAULT))
    q_spec = pl.BlockSpec((None, s, hkv * g * hd), lambda bi, c: (bi, 0, c))
    kv_spec = pl.BlockSpec((None, t, hkv * hd), lambda bi, c: (bi, 0, c))
    out = pl.pallas_call(
        kern,
        grid=(b, kvh // hkv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, h * hd), q.dtype),
        interpret=interpret,
    )(q.reshape(b, s, h * hd), k.reshape(b, t, kvh * hd),
      v.reshape(b, t, kvh * hd))
    return out.reshape(b, s, h, hd)

"""Flash-decoding over a paged KV cache (Pallas TPU + jnp reference).

Decode is single-token attention: one query row per sequence against
everything that sequence has cached. The dense path reads the full
(B, S_max) cache every step -- including the dead tail beyond each
slot's position -- and its HBM traffic is what caps decode tok/s.
This kernel reads K/V as fixed-size *pages* gathered through a
per-slot page table, splits the key axis across a grid dimension, and
reduces with online-softmax partials (acc, m, l) in VMEM scratch:

  * pages whose first position lies beyond the slot's ``pos`` are dead
    for the whole tile -- the ``pl.when`` guard skips their dot entirely
    (flash-decoding's "only read what is resident"),
  * the page gather is a BlockSpec index map over a scalar-prefetched
    page table (``pltpu.PrefetchScalarGridSpec``): the DMA engine fetches
    pool page ``pages[b, p]`` directly, no materialized (B, S, ...)
    contiguous copy of the cache ever exists.

Layout: q (B, H, hd) -- one token per slot; k/v pools
(n_pages, page_size, KV, hd); pages (B, n_live) physical page ids;
pos (B,) each slot's current position. Grid (B, n_live), pages
innermost; each step reads one whole page (all KV heads) and loops over
the heads in VMEM. GQA: the G = H//KV query heads of one KV head share a
tile. ``paged_attention`` is the pallas_call that flash_verify and
flash_prefill share: their windows and chunks are extra query rows.

``paged_attn_ref`` is the pure-jnp oracle (gather + masked softmax) --
also the hot-path implementation on non-TPU backends, where interpret
mode would run the kernel body in Python per grid step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# full-head-dim tiles: the lane axis must fill (or evenly split) the
# 128-wide MXU; the sets below are what the q/k dot supports without
# implicit padding that silently corrupts the accumulation
MXU_HEAD_DIMS = (64, 112, 128, 256)


def check_head_dim(hd: int, *, interpret: bool, kernel: str):
    """Registry-style validation: on TPU an unsupported head dim must be
    a loud error, not silent tile-padding misbehavior. Interpret mode
    (CI parity tests) runs any head dim."""
    if not interpret and hd not in MXU_HEAD_DIMS:
        raise ValueError(
            f"{kernel}: head_dim {hd} is not MXU-aligned; supported head "
            f"dims: {list(MXU_HEAD_DIMS)} (interpret=True lifts this for "
            f"correctness tests)")


def _paged_kernel(pages_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, ps, n_live, g, scale):
    """One (slot, page) grid step for every KV head of the slot.

    q block (1, KV, R, hd): row r of a head's tile is query offset r // g
    (a chunk or window of R // g tokens, G = g query heads each), which
    attends through ``pos + r // g``. The K/V block is one whole page with
    all its heads, (1, ps, KV, hd) -- the chip's tiling refuses a size-1
    slice of the KV axis -- and a static loop walks the heads in VMEM.
    """
    bi, pp = pl.program_id(0), pl.program_id(1)
    kvh, r = q_ref.shape[1], q_ref.shape[2]

    @pl.when(pp == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # a page is live iff the last query row can see it; later pages of the
    # table hold this slot's future (or trash-page entries) -- skipped
    pos0 = pos_ref[bi]

    @pl.when(pp * ps <= pos0 + (r - 1) // g)
    def _():
        q_pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0) // g
        k_pos = pp * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        visible = k_pos <= q_pos                             # (R, ps)
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32) * scale      # (R, hd)
            k = k_ref[0, :, h, :].astype(jnp.float32)        # (ps, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(visible, s, _NEG_INF)
            m_prev = m_ref[h]                                # (R, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(pp == n_live - 1)
    def _():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, pages, pos, *, interpret: bool):
    """The pallas_call the paged kernels share. q: (B, C, H, hd), C query
    tokens per slot with offset c at position ``pos[b] + c``; k/v pools
    (NP, ps, KV, hd); pages (B, n_live) -> (B, C, H, hd).

    Each KV head's tile holds the C*G query rows of its G = H//KV heads.
    Grid (B, n_live), pages innermost, so the online-softmax partials
    (acc, m, l) in VMEM reduce over one slot's pages."""
    b, c, h, hd = q.shape
    _, ps, kvh, _ = k_pages.shape
    g = h // kvh
    n_live = pages.shape[1]
    qr = q.reshape(b, c, kvh, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, kvh, c * g, hd)

    def qmap(bi, pp, pages_ref, pos_ref):
        return (bi, 0, 0, 0)

    def kvmap(bi, pp, pages_ref, pos_ref):
        return (pages_ref[bi, pp], 0, 0, 0)

    kern = functools.partial(_paged_kernel, ps=ps, n_live=n_live, g=g,
                             scale=1.0 / float(hd) ** 0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # pages, pos
        grid=(b, n_live),
        in_specs=[
            pl.BlockSpec((1, kvh, c * g, hd), qmap),
            pl.BlockSpec((1, ps, kvh, hd), kvmap),
            pl.BlockSpec((1, ps, kvh, hd), kvmap),
        ],
        out_specs=pl.BlockSpec((1, kvh, c * g, hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((kvh, c * g, hd), jnp.float32),
            pltpu.VMEM((kvh, c * g, 1), jnp.float32),
            pltpu.VMEM((kvh, c * g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
        interpret=interpret,
    )(pages.astype(jnp.int32), pos.astype(jnp.int32), qr, k_pages, v_pages)
    return out.reshape(b, kvh, c, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, c, h, hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode(q, k_pages, v_pages, pages, pos, *,
                 interpret: bool = False):
    """q: (B, H, hd); k/v pools: (NP, ps, KV, hd); pages: (B, n_live)
    int32 physical page ids; pos: (B,) int32 -> (B, H, hd).

    Positions > pos[b] (this slot's dead tail, unallocated table entries
    pointing at the trash page) are masked out; page n_live*ps .. S_max
    is never read at all.
    """
    check_head_dim(q.shape[-1], interpret=interpret, kernel="flash_decode")
    return paged_attention(q[:, None], k_pages, v_pages, pages, pos,
                           interpret=interpret)[:, 0]


def paged_attn_ref(q, k_pages, v_pages, pages, pos):
    """jnp oracle / non-TPU hot path: gather the live pages back into
    logical order and run masked GQA attention over them. Reads
    n_live * ps keys instead of S_max -- the same dead-tail skip the
    kernel does, expressed as a (bucketed-static) gather."""
    b, h, hd = q.shape
    _, ps, kvh, _ = k_pages.shape
    n_live = pages.shape[1]
    kk = k_pages[pages].reshape(b, n_live * ps, kvh, hd)
    vv = v_pages[pages].reshape(b, n_live * ps, kvh, hd)
    valid = jnp.arange(n_live * ps)[None, :] <= pos[:, None]
    from repro.models.layers import attention
    out = attention(q[:, None], kk, vv, causal=False, kv_mask=valid,
                    chunk=0)
    return out[:, 0]

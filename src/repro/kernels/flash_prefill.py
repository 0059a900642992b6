"""Chunked prefill attention into a paged KV cache (Pallas TPU + jnp
reference) -- the many-token-query sibling of flash_decode/flash_verify.

Chunked prefill admits a prompt C tokens at a time straight into the
slot's reserved pages: chunk offset c of slot b sits at logical position
``pos[b] + c`` and may attend to every cached position ``<= pos[b] + c``
-- the earlier prompt chunks already resident in the pool, plus causal
masking *inside* the chunk. The chunk's own K/V has been scattered into
the slot's pages by the caller before the read (exactly the verify
kernel's contract), so the kernel is pure page reads and no dense B=1
prompt cache ever exists.

The chunk's C*G query rows per KV head stay resident in VMEM through
one page sweep (flash_decode's shared ``paged_attention``): scores
(C*G, page_size) per head and page with per-row causal limits,
online-softmax partials (acc, m, l) sized (C*G, ...) in VMEM scratch.

Layout: q (B, C, H, hd) -- C chunk tokens per slot; k/v pools
(n_pages, page_size, KV, hd); pages (B, n_live) physical page ids;
pos (B,) each slot's chunk-start position. GQA: the G = H//KV query
heads of one KV head share a tile.

``prefill_attn_ref`` is the pure-jnp oracle and the non-TPU hot path;
at C=1 it degenerates to the same math as ``paged_attn_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode import check_head_dim, paged_attention


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_prefill(q, k_pages, v_pages, pages, pos, *,
                  interpret: bool = False):
    """q: (B, C, H, hd); k/v pools: (NP, ps, KV, hd); pages: (B, n_live)
    int32 physical page ids; pos: (B,) int32 -> (B, C, H, hd).

    Chunk offset c of slot b reads positions <= pos[b] + c; everything
    later (the rest of the chunk, the slot's dead tail, trash-page table
    entries) is masked out. The table must cover pos + C - 1 -- the
    admission reservation guarantees the pages exist.
    """
    check_head_dim(q.shape[-1], interpret=interpret, kernel="flash_prefill")
    return paged_attention(q, k_pages, v_pages, pages, pos,
                           interpret=interpret)


def prefill_attn_ref(q, k_pages, v_pages, pages, pos):
    """jnp oracle / non-TPU hot path: gather the live pages into logical
    order and run masked GQA attention with a per-(slot, offset) limit
    ``k_pos <= pos + c`` -- flash_decode's dead-tail skip plus causal
    masking inside the chunk, expressed as one 3-D kv_mask."""
    b, c, h, hd = q.shape
    _, ps, kvh, _ = k_pages.shape
    n_live = pages.shape[1]
    kk = k_pages[pages].reshape(b, n_live * ps, kvh, hd)
    vv = v_pages[pages].reshape(b, n_live * ps, kvh, hd)
    qpos = pos[:, None] + jnp.arange(c)[None, :]             # (B, C)
    valid = jnp.arange(n_live * ps)[None, None, :] <= qpos[:, :, None]
    from repro.models.layers import attention
    return attention(q, kk, vv, causal=False, kv_mask=valid, chunk=0)

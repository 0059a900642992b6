"""Short-window verify attention over a paged KV cache (Pallas TPU +
jnp reference) -- the multi-token-query sibling of flash_decode.

Speculative decoding scores a *window* of W = k+1 candidate tokens per
slot in one dispatch: query offset w of slot b sits at logical position
``pos[b] + w`` and may attend to every cached position ``<= pos[b] + w``
-- the page-table gather of flash-decoding plus causal masking *inside*
the window. The window's own K/V has already been scattered into the
slot's pages by the caller (the verifier overwrites the draft's entries
before reading), so the kernel is pure page reads: no separate in-window
attention pass, and speculation adds zero KV HBM.

Layout: q (B, W, H, hd) -- W candidate tokens per slot; k/v pools
(n_pages, page_size, KV, hd); pages (B, n_live) physical page ids;
pos (B,) each slot's first window position. The window's W*G query rows
per KV head ride flash_decode's shared page sweep
(``paged_attention``), so the online-softmax partials reduce over pages
exactly as flash_decode's do.

``verify_attn_ref`` is the pure-jnp oracle and the non-TPU hot path; at
W=1 it degenerates to the same math as ``paged_attn_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode import check_head_dim, paged_attention


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_verify(q, k_pages, v_pages, pages, pos, *,
                 interpret: bool = False):
    """q: (B, W, H, hd); k/v pools: (NP, ps, KV, hd); pages: (B, n_live)
    int32 physical page ids; pos: (B,) int32 -> (B, W, H, hd).

    Window offset w of slot b reads positions <= pos[b] + w; everything
    later (the rest of the window, the dead tail, trash-page table
    entries) is masked out.
    """
    check_head_dim(q.shape[-1], interpret=interpret, kernel="flash_verify")
    return paged_attention(q, k_pages, v_pages, pages, pos,
                           interpret=interpret)


def verify_attn_ref(q, k_pages, v_pages, pages, pos):
    """jnp oracle / non-TPU hot path: gather the live pages into logical
    order and run masked GQA attention with a per-(slot, offset) limit
    ``k_pos <= pos + w`` -- flash_decode's dead-tail skip plus causal
    masking inside the window, expressed as one 3-D kv_mask."""
    b, w, h, hd = q.shape
    _, ps, kvh, _ = k_pages.shape
    n_live = pages.shape[1]
    kk = k_pages[pages].reshape(b, n_live * ps, kvh, hd)
    vv = v_pages[pages].reshape(b, n_live * ps, kvh, hd)
    qpos = pos[:, None] + jnp.arange(w)[None, :]             # (B, W)
    valid = jnp.arange(n_live * ps)[None, None, :] <= qpos[:, :, None]
    from repro.models.layers import attention
    return attention(q, kk, vv, causal=False, kv_mask=valid, chunk=0)

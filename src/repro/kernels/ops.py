"""jit'd wrappers around the ZO Pallas kernels.

On non-TPU backends (this container) the kernels run in interpret mode,
which executes the kernel body in Python for correctness validation; on
TPU they compile to Mosaic.

Both wrappers accept ``scale=`` (per-output-channel f32 vector) to mark
``w`` as an int8 quantized base: dequantization then fuses into the same
kernel tile pass (see kernels/zo_perturb.py). The matmul wrappers'
``blocks=None`` picks the tiling from the call's shapes, dtypes and dot
precision (``zo_perturb.matmul_blocks``); a (bm, bk, bn) tuple pins it.
"""

from __future__ import annotations

import jax

from repro.kernels import zo_perturb as _k

BACKEND = jax.default_backend()
_INTERPRET = BACKEND != "tpu"


def paged_decode_attn(q, k_pages, v_pages, pages, pos):
    """Single-token attention over a paged KV pool: the Pallas
    flash-decoding kernel on TPU, the jnp gather reference elsewhere
    (decode is a hot loop -- interpret mode's per-grid-step Python body
    would dominate it; the reference is the same math as one XLA graph).
    """
    from repro.kernels import flash_decode as _fd
    if _INTERPRET:
        return _fd.paged_attn_ref(q, k_pages, v_pages, pages, pos)
    return _fd.flash_decode(q, k_pages, v_pages, pages, pos)


def paged_verify_attn(q, k_pages, v_pages, pages, pos):
    """Window attention over a paged KV pool for speculative verify:
    q is (B, W, H, hd) -- W candidate tokens per slot, offset w reading
    positions <= pos + w. Pallas flash-verify kernel on TPU, the jnp
    gather reference elsewhere (same hot-loop rationale as
    :func:`paged_decode_attn`)."""
    from repro.kernels import flash_verify as _fv
    if _INTERPRET:
        return _fv.verify_attn_ref(q, k_pages, v_pages, pages, pos)
    return _fv.flash_verify(q, k_pages, v_pages, pages, pos)


def paged_prefill_attn(q, k_pages, v_pages, pages, pos):
    """Chunk attention over a paged KV pool for chunked prefill: q is
    (B, C, H, hd) -- C prompt tokens per slot, offset c reading
    positions <= pos + c. Pallas flash-prefill kernel on TPU (whole
    chunk resident per page sweep), the jnp gather reference elsewhere
    (same hot-loop rationale as :func:`paged_decode_attn`)."""
    from repro.kernels import flash_prefill as _fp
    if _INTERPRET:
        return _fp.prefill_attn_ref(q, k_pages, v_pages, pages, pos)
    return _fp.flash_prefill(q, k_pages, v_pages, pages, pos)


def flash_attention(q, k, v, *, causal: bool):
    """Self-attention core as the Pallas flash kernel: compiled on TPU,
    interpreted elsewhere. ``layers.attn_apply`` calls it where
    :func:`repro.kernels.flash_attention.takes` finds a TPU."""
    from repro.kernels import flash_attention as _fa
    return _fa.flash_attention(q, k, v, causal=causal,
                               interpret=_INTERPRET)


def zo_add(w, seed, salt: int, coeff, dist: str = "rademacher",
           block=(256, 256), prime_offset: int = 0, prehashed: bool = False,
           scale=None):
    return _k.zo_add(w, seed, salt, coeff, dist=dist, block=block,
                     interpret=_INTERPRET, prime_offset=prime_offset,
                     prehashed=prehashed, scale=scale)


def zo_matmul(x, w, seed, salt: int, coeff, dist: str = "rademacher",
              blocks=None, prime_offset: int = 0,
              prehashed: bool = False, scale=None, layer=None):
    """``x @ (w + coeff*z)``; a stacked (L, K, N) ``w`` is read in place
    at ``layer``."""
    return _k.zo_matmul(x, w, seed, salt, coeff, dist=dist, blocks=blocks,
                        interpret=_INTERPRET, prime_offset=prime_offset,
                        prehashed=prehashed, scale=scale, layer=layer)


def zo_add_users(w, seeds, salt: int, coeffs, dist: str = "rademacher",
                 block=(256, 256), prime_offset: int = 0,
                 prehashed: bool = False):
    """Per-user stacked leaves: ``out[u] = w[u] + coeffs[u]*z(seeds[u])``."""
    return _k.zo_add_users(w, seeds, salt, coeffs, dist=dist, block=block,
                           interpret=_INTERPRET, prime_offset=prime_offset,
                           prehashed=prehashed)


def zo_matmul_users(x, w, seeds, salt: int, coeffs,
                    dist: str = "rademacher", blocks=None,
                    prime_offset: int = 0, prehashed: bool = False,
                    scale=None):
    """B users' perturbed forwards against ONE resident (K, N) base:
    ``y[u] = x[u] @ (w + coeffs[u]*z(seeds[u]))`` in one dispatch."""
    return _k.zo_matmul_users(x, w, seeds, salt, coeffs, dist=dist,
                              blocks=blocks, interpret=_INTERPRET,
                              prime_offset=prime_offset,
                              prehashed=prehashed, scale=scale)

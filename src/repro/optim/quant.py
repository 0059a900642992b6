"""int8 quantization: the quantized-base runtime + shared int8 helpers.

PocketLLM's headline claim is *memory* (RoBERTa-large in ~4GB, OPT-1.3B
in ~6.5GB on a phone). The fused ZO path already removed the transient
perturbed parameter copy; the remaining lever is the resident base
weights themselves. This module provides the quantized-base
representation the whole stack threads through:

* :class:`QuantizedLeaf` -- one parameter leaf as int8 values plus
  per-channel f32 scales (absmax over the contraction axis ``-2``,
  rounded up to a power of two so ``q*scale`` is exact in f32; a
  ``(K, N)`` projection carries an ``(N,)`` scale and a scan-stacked
  ``(L, K, N)`` leaf an ``(L, N)`` one -- the leading layer axis slices
  through ``lax.scan`` exactly like the values). An optional f32
  ``delta`` carries the accumulated ZO update stream: the int8 base
  stays frozen; training writes only the additive side (the
  derivative-free analogue of PAE MobiLLM's additive deltas).
* :func:`quantize_tree` -- one-shot base quantization of a param
  pytree (deterministic round-to-nearest: the quantized base is a pure
  function of the f32 base, so every host/restart agrees bit-for-bit).
* use-site helpers (:func:`deq`, :func:`take_rows`,
  :func:`dequantize_tree`) that pass plain arrays through untouched, so
  the model code has ONE path for quantized and full-precision bases.

Seed-replay contract: a :class:`QuantizedLeaf` is *atomic* for every
salt/path computation (``core.perturb`` flattens with
``is_leaf=is_quantized``), so the z-field of a quantized leaf is
bit-identical to its f32 counterpart's -- replay logs, adapters, and
checkpoints move freely between quantized and full-precision bases.

The per-tensor stochastic-rounding helpers (``int8_quantize`` /
``int8_dequantize``) used by gradient compression
(``optim/compression.py``) and adapter delta compaction
(``serve/adapters.py``) live here as the single copy; deterministic
per-channel quantization (the base) and stochastic per-tensor
quantization (wire/delta compression) are deliberately different codes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rng as zrng

PyTree = Any

#: supported --quant modes ("none" is the f32 passthrough)
QUANT_MODES = ("none", "int8")


def check_quant_mode(mode: str) -> str:
    """Validate a quantization mode name (mirrors the engine's
    estimator/update registry errors)."""
    if mode not in QUANT_MODES:
        raise ValueError(
            f"unknown quantization mode {mode!r}; supported modes: "
            f"{list(QUANT_MODES)}")
    return mode


# ---------------------------------------------------------------------------
# per-tensor stochastic int8 (gradient compression / delta compaction)
# -- moved verbatim from optim/compression.py; that module and
# serve/adapters.py now import the single copy from here.


def int8_quantize(g: jnp.ndarray, seed=jnp.uint32(0x51CA)):
    scale = jnp.max(jnp.abs(g)).astype(jnp.float32) / 127.0 + 1e-30
    x = g.astype(jnp.float32) / scale
    # stochastic rounding via the same hash field used for ZO noise
    u = (zrng._coord_hash(seed, 0xC0DE, g.shape) >> 8).astype(jnp.float32) \
        * (1.0 / 16777216.0)
    q = jnp.clip(jnp.floor(x + u), -127, 127).astype(jnp.int8)
    return q, scale


def int8_dequantize(q: jnp.ndarray, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# the quantized-base leaf


@dataclasses.dataclass(frozen=True)
class QuantizedLeaf:
    """One frozen int8 base leaf (+ optional f32 adapter delta).

    Effective weight: ``q * expand(scale) (+ delta)``. ``scale`` is the
    per-channel power-of-two absmax scale over axis ``-2`` (the
    contraction axis of a matmul weight, see :func:`quantize_leaf`),
    shape ``shape[:-2] + (shape[-1],)``; ``delta`` is
    ``None`` (frozen serving base) or a full-shape f32 array carrying
    the accumulated ZO updates (``core.perturb.add_scaled_z`` writes
    here; the int8 values never change).

    Registered as a pytree whose children are ``(q, scale, delta)`` so
    it flows through jit / scan / checkpoint IO; perturbation-path code
    treats it atomically via ``is_leaf=is_quantized`` so salts bind to
    the *leaf's* pytree path, never to ``.../q``.
    """
    q: Any                          # int8, the original leaf shape
    scale: Any                      # f32, shape[:-2] + (shape[-1],)
    delta: Any = None               # f32 accumulated update, or None
    orig_dtype: Any = jnp.float32   # dtype the f32 path would carry

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self) -> int:
        return self.q.ndim

    @property
    def dtype(self):
        """The *logical* dtype (what a full-precision base would be)."""
        return jnp.dtype(self.orig_dtype)

    @property
    def nbytes(self) -> int:
        n = self.q.nbytes + self.scale.nbytes
        return n + (self.delta.nbytes if self.delta is not None else 0)

    def base_f32(self):
        """The frozen base alone, ``q*scale``, in f32 (exact: int8
        times a power-of-two scale)."""
        return self.q.astype(jnp.float32) * _expand(self.scale)

    def dequantize_f32(self):
        """q*scale (+ delta) in f32 -- the exact arithmetic every use
        site (fused or materialized) shares, so parity is bit-for-bit."""
        w = self.base_f32()
        if self.delta is not None:
            w = w + self.delta.astype(jnp.float32)
        return w

    def dequantize(self):
        """Effective weight in the logical dtype."""
        return self.dequantize_f32().astype(self.dtype)


jax.tree_util.register_pytree_with_keys(
    QuantizedLeaf,
    lambda l: (((jax.tree_util.DictKey("q"), l.q),
                (jax.tree_util.DictKey("scale"), l.scale),
                (jax.tree_util.DictKey("delta"), l.delta)),
               jnp.dtype(l.orig_dtype)),
    lambda dt, c: QuantizedLeaf(q=c[0], scale=c[1], delta=c[2],
                                orig_dtype=dt),
)


def is_quantized(x) -> bool:
    return isinstance(x, QuantizedLeaf)


def _expand(scale):
    """Broadcast a per-channel scale back over the reduced axis -2."""
    return scale[..., None, :]


def quantize_leaf(w, with_delta: bool = False) -> QuantizedLeaf:
    """Deterministic symmetric per-channel int8 quantization of one
    rank>=2 leaf. Round-to-nearest (not stochastic): the base must be a
    reproducible function of the f32 weights. Zero / denormal channels
    get scale 1.0 so they roundtrip to exact zeros instead of NaNs.

    Scales are the per-channel absmax/127 rounded UP to a power of two:
    ``q * scale`` is then *exact* in f32 (int8 times 2^k), which is what
    makes the fused dequant+perturb bit-identical to a materialized
    ``dequant(Wq) + c*z`` under any compiler contraction -- XLA may fuse
    the dequant multiply into an FMA with the perturbation add, and with
    an exact product the contracted and uncontracted roundings agree.
    Cost: quantization error up to 2x the optimal absmax scaling (still
    <= scale/2 for the chosen scale)."""
    wf = jnp.asarray(w).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2)
    pow2 = jnp.exp2(jnp.ceil(jnp.log2(absmax / 127.0)))
    scale = jnp.where((absmax > 0) & (pow2 > 0) & jnp.isfinite(pow2),
                      pow2, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(wf / _expand(scale)), -127, 127).astype(jnp.int8)
    delta = jnp.zeros(wf.shape, jnp.float32) if with_delta else None
    return QuantizedLeaf(q=q, scale=scale, delta=delta,
                         orig_dtype=jnp.dtype(w.dtype))


def default_quantizable(path: str, leaf) -> bool:
    """Which leaves the one-shot base quantization touches.

    Matrix-shaped floating leaves only: rank >= 2 at top level
    (embeddings, heads), rank >= 3 under a scanned stack scope
    (``*blocks``), where every leaf carries a leading layer axis -- a
    stacked ``(L, d)`` leaf is a per-layer *vector* (norm scale, bias),
    and those are both precision-critical and a rounding error of the
    byte budget. MoE router weights stay f32: top-k routing is
    discrete, so router rounding flips expert assignments instead of
    degrading smoothly.
    """
    if is_quantized(leaf):
        return False
    min_rank = 3 if path.split("/", 1)[0].endswith("blocks") else 2
    if getattr(leaf, "ndim", 0) < min_rank:
        return False
    if not jnp.issubdtype(leaf.dtype, jnp.floating):
        return False
    return not path.endswith("router")


def quantize_tree(params: PyTree, mode: str = "int8",
                  with_delta: bool = False, quantizable=None) -> PyTree:
    """One-shot base quantization of a param pytree.

    mode "none" returns the tree untouched (the f32 passthrough the
    trainer's ``--quant none`` resolves to); unknown modes raise the
    registry-style ValueError. ``with_delta=True`` attaches a zero f32
    delta to every quantized leaf -- required for any tree that will be
    *trained* (the update stream lands in the delta; a delta-less base
    is frozen and ``add_scaled_z`` leaves it untouched).
    """
    check_quant_mode(mode)
    if mode == "none":
        return params
    pred = quantizable or default_quantizable
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=is_quantized)
    out = []
    for path, leaf in leaves:
        ps = _path_str(path)
        out.append(quantize_leaf(leaf, with_delta) if pred(ps, leaf)
                   else leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


# ---------------------------------------------------------------------------
# use-site helpers (pass plain arrays through untouched)


def deq(w):
    """Effective weight of ``w`` -- dequantized if quantized, as-is
    otherwise. The single plain-forward entry point for every use site
    (dense matmuls, convs, einsums)."""
    return w.dequantize() if is_quantized(w) else w


def take_rows_f32(table, ids):
    """Row gather in f32 that never materializes a dequantized table:
    O(rows * cols) work for a quantized ``(R, C)`` leaf, exactly like
    the fused path's ``rng.z_rows`` embedding trick. The single copy of
    the quantized-gather arithmetic -- both the plain forward
    (:func:`take_rows`) and the perturbed one (``PerturbCtx.take``)
    build on it, so they cannot drift apart."""
    if not is_quantized(table):
        return jnp.take(table, ids, axis=0).astype(jnp.float32)
    rows = jnp.take(table.q, ids, axis=0).astype(jnp.float32) * table.scale
    if table.delta is not None:
        rows = rows + jnp.take(table.delta, ids, axis=0)
    return rows


def take_rows(table, ids):
    """Row gather in the table's logical dtype (plain-forward use)."""
    if not is_quantized(table):
        return jnp.take(table, ids, axis=0)
    return take_rows_f32(table, ids).astype(table.dtype)


def dequantize_tree(tree: PyTree) -> PyTree:
    """Transient full-precision view of a (sub)tree -- the generic
    fallback for code that consumes stacked leaves in nonstandard ways
    (MoE sort-based dispatch). Plain trees pass through unchanged."""
    return jax.tree_util.tree_map(deq, tree, is_leaf=is_quantized)


def with_delta(tree: PyTree) -> PyTree:
    """Attach zero f32 deltas to any delta-less quantized leaves, making
    the tree update-capable. The delta must exist *before* the first
    ``add_scaled_z``: the update sweep runs under ``lax.scan``, whose
    carry treedef is fixed, so a leaf cannot grow a delta mid-scan."""
    def ensure(leaf):
        if is_quantized(leaf) and leaf.delta is None:
            return dataclasses.replace(
                leaf, delta=jnp.zeros(leaf.shape, jnp.float32))
        return leaf
    return jax.tree_util.tree_map(ensure, tree, is_leaf=is_quantized)


def tree_is_quantized(tree: PyTree) -> bool:
    return any(is_quantized(l) for l in jax.tree_util.tree_leaves(
        tree, is_leaf=is_quantized))


def quantized_bytes(tree: PyTree):
    """(resident_bytes, f32_equivalent_bytes) of a param tree. Resident
    counts int8 values + f32 scales (+ deltas if attached); the f32
    equivalent counts every floating leaf at 4 bytes/element."""
    resident = f32_eq = 0
    for leaf in jax.tree_util.tree_leaves(tree, is_leaf=is_quantized):
        if is_quantized(leaf):
            resident += leaf.nbytes
            f32_eq += 4 * int(np.prod(leaf.shape))
        else:
            resident += leaf.nbytes
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                f32_eq += 4 * int(np.prod(leaf.shape))
            else:
                f32_eq += leaf.nbytes
    return resident, f32_eq

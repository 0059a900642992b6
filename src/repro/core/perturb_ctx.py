"""Perturbed-forward execution context (the fused MeZO path).

The materialized ``vmapdir`` estimator realizes theta ± eps*z with one
transient param-sized copy per side. The fused path removes it: the
*unperturbed* params flow into the forward together with
a :class:`PerturbCtx` carrying ``(seed, coeff, dist)``, and each consumer
applies its leaf's perturbation at the point of use --

  * dense projections (QKV/O, MLP up/down, LM head) compute
    ``X @ (W + coeff*z)`` via the fused Pallas kernel
    ``repro.kernels.ops.zo_matmul`` (z regenerated tile-wise in VMEM,
    zero HBM bytes) or, on non-aligned shapes / without ``use_kernel``,
    via a transient jnp materialization that XLA fuses into the matmul;
  * embedding gathers perturb only the gathered rows
    (``rng.z_rows``: O(tokens*d), never O(vocab*d));
  * small leaves (norm scales, biases) add a transient ``coeff*z``.

Quantized bases (optim/quant.py): every primitive accepts a
``QuantizedLeaf`` in place of an array -- dense projections fuse the
int8 dequant into the same ``zo_matmul`` kernel pass
(``X @ (q*scale + coeff*z)``), embedding gathers dequantize only the
gathered rows, and the jnp fallback computes
``q*scale (+ delta) + coeff*z`` in one transient f32 expression. The
salt is the *leaf's* path (never ``.../q``), so the z-fields match the
f32 base's bit-for-bit.

Bit-compatibility contract: salts are derived from the same pytree path
strings as ``core.perturb._path_str``, and scan-stacked ``(L, ...)``
block leaves are handled by folding the layer index into a pre-hashed
base (``rng.leaf_base`` / ``rng.fold_leading``) with ``prime_offset=1``.
So for every leaf the fused forward sees *exactly* the z-field that
``add_scaled_z`` (and therefore ``spsa_gradient_estimate`` and the
replay-log checkpointer) would apply to the stacked parameter tree.

Stacked weights the kernel can read in place (:meth:`PerturbCtx.in_place`)
reach a layer's body as a :class:`LayerSlice`, not as a slice: the
kernel fetches the layer's tiles from the stack, and every other use
slices the view first.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import rng as zrng
from repro.core.perturb import _path_str, is_perturbable, kernel_aligned
from repro.optim.quant import is_quantized, take_rows, take_rows_f32

PyTree = Any


@dataclasses.dataclass(frozen=True)
class LayerSlice:
    """Layer ``layer`` of a scan-stacked weight, left unsliced in its
    ``stack`` (an (L, K, N) array, or a quantized leaf of one).

    :meth:`PerturbCtx.matmul` hands the fused kernel the stack and the
    index; every other use takes :meth:`get`, the same 2-D leaf a scan
    over the stack would have given."""
    stack: Any
    layer: Any

    def get(self):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, self.layer, 0, False),
            self.stack)


def _resolve(leaf):
    """A :class:`LayerSlice`'s 2-D leaf; any other leaf as it is."""
    return leaf.get() if isinstance(leaf, LayerSlice) else leaf


@dataclasses.dataclass(frozen=True)
class PerturbCtx:
    """theta + coeff * z(seed), applied lazily at each parameter's use site.

    seed/coeff may be traced (they are scan/vmap-carried in the fused MeZO
    step); dist / use_kernel / prefix are trace-time static.

    **User-axis mode**: a (U,) ``seed`` vector (``coeff`` scalar or (U,))
    batches the ctx over a leading user axis -- B users' directions in
    one forward. Input conventions then follow the multi-tenant state
    layout (``core.batching``): activations and plain param leaves carry
    a leading user axis; :class:`~repro.optim.quant.QuantizedLeaf`
    weights keep the single resident int8 base (``q``/``scale`` shared)
    with only the f32 ``delta`` stacked (or absent when frozen). Aligned
    shared-base matmuls dispatch ONE ``kernels.ops.zo_matmul_users``
    call per site -- per-user seeds/coeffs ride SMEM while the base
    tiles are read once -- and every other primitive vmaps the scalar
    path, so each lane is bit-identical to a scalar ctx with that
    user's (seed, coeff).
    """
    seed: Any                        # uint32 step/direction seed; (U,) =>
    #                                  user-axis mode (see class docstring)
    coeff: Any                       # f32 scalar: +eps or -eps
    dist: str = "rademacher"
    use_kernel: bool = False         # route aligned 2-D matmuls via Pallas
    prefix: str = ""                 # pytree path of the current scope
    layer: Optional[Any] = None      # leading (scan) index into stacked leaves

    # -- scope plumbing ----------------------------------------------------

    def scope(self, name: str) -> "PerturbCtx":
        """Descend into a param sub-dict (extends the salt path)."""
        p = f"{self.prefix}/{name}" if self.prefix else name
        return dataclasses.replace(self, prefix=p)

    def at_layer(self, idx) -> "PerturbCtx":
        """Bind the leading scan index of stacked (L, ...) leaves."""
        return dataclasses.replace(self, layer=jnp.asarray(idx, jnp.uint32))

    def _leaf(self, name: str):
        """(full path, pre-hashed base, prime offset) for a named leaf."""
        path = f"{self.prefix}/{name}" if self.prefix else name
        base = zrng.leaf_base(self.seed, zrng.leaf_salt(path))
        off = 0
        if self.layer is not None:
            base = zrng.fold_leading(base, self.layer, dim=0)
            off = 1
        return path, base, off

    def _coeff(self):
        return jnp.asarray(self.coeff, jnp.float32)

    def _kernel_takes(self, w, shape) -> bool:
        """Whether the fused kernel computes the matmul with ``w``, whose
        (K, N) is ``shape``: the kernel path is on, and ``w`` is floating
        (or a delta-less quantized base) and kernel-aligned."""
        return self.use_kernel and kernel_aligned(shape) and \
            jnp.issubdtype(w.dtype, jnp.floating) and \
            not (is_quantized(w) and w.delta is not None)

    def in_place(self, leaf) -> bool:
        """Whether the fused kernel reads this scan-stacked (L, K, N) leaf
        in place, so a layer scan hands it on as a :class:`LayerSlice`."""
        return not self.batched and leaf.ndim == 3 and \
            self._kernel_takes(leaf, leaf.shape[1:])

    # -- user axis ---------------------------------------------------------

    @property
    def batched(self) -> bool:
        """True in user-axis mode ((U,) seed vector)."""
        return jnp.ndim(self.seed) == 1

    def _user_lanes(self):
        """(U,) uint32 seeds and (U,) f32 coeffs (scalar coeff broadcast)."""
        seeds = jnp.asarray(self.seed, jnp.uint32)
        coeffs = jnp.broadcast_to(
            jnp.asarray(self.coeff, jnp.float32), seeds.shape)
        return seeds, coeffs

    def _lane(self, seed, coeff) -> "PerturbCtx":
        return dataclasses.replace(self, seed=seed, coeff=coeff)

    @staticmethod
    def _user_axes(leaf):
        """vmap in_axes for a weight under the user-axis conventions:
        plain leaves stacked on axis 0 unless shared 2-D; quantized
        leaves share the base and stack only a present delta."""
        from repro.optim.quant import QuantizedLeaf
        if is_quantized(leaf):
            return QuantizedLeaf(q=None, scale=None,
                                 delta=None if leaf.delta is None else 0,
                                 orig_dtype=leaf.orig_dtype)
        return 0

    # -- perturbation primitives ------------------------------------------

    def perturb(self, name: str, leaf):
        """leaf + coeff*z, transient (the jnp fallback for any leaf).

        Quantized leaves dequantize into the same transient:
        ``q*scale (+ delta) + coeff*z`` in one f32 expression, with the
        z-field of the *leaf's* path (identical to the f32 base's).

        User-axis mode: ``leaf`` is per-user stacked (quantized: shared
        base, stacked delta); each lane gets its own z-field."""
        leaf = _resolve(leaf)
        if self.batched:
            seeds, coeffs = self._user_lanes()
            return jax.vmap(
                lambda s, c, lf: self._lane(s, c).perturb(name, lf),
                in_axes=(0, 0, self._user_axes(leaf)))(seeds, coeffs, leaf)
        path, base, off = self._leaf(name)
        if not is_perturbable(path) or \
                not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.dequantize() if is_quantized(leaf) else leaf
        z = zrng.z_field(None, 0, leaf.shape, jnp.float32, self.dist,
                         prime_offset=off, base=base)
        lf = leaf.dequantize_f32() if is_quantized(leaf) \
            else leaf.astype(jnp.float32)
        return (lf + self._coeff() * z).astype(leaf.dtype)

    def matmul(self, x, w, name: str = "w"):
        """x @ (w + coeff*z) for x (..., K), w (K, N) or a
        :class:`LayerSlice` of an (L, K, N) stack.

        MXU-aligned weights go through the fused Pallas kernel (z never
        leaves VMEM), a LayerSlice as its whole stack plus the layer index
        (the kernel reads the layer's tiles in place); everything else
        falls back to a transient jnp materialization with identical
        values (ref.zo_matmul_ref semantics, cast back to the weight dtype
        like ``add_scaled_z`` so the f32 path is bit-exact with the
        sequential strategies). Either way the ops run under the device
        scope ``zo_matmul.<projection path>``.
        """
        with jax.named_scope(obs.MATMUL + (self.prefix or name)):
            if self.batched:
                return self._matmul_users(x, w, name)
            return self._matmul_one(x, w, name)

    def _matmul_one(self, x, w, name: str):
        path, base, off = self._leaf(name)
        if isinstance(w, LayerSlice) and is_perturbable(path):
            return self._kernel(x, w.stack, base, off, w.layer)
        w = _resolve(w)
        if not is_perturbable(path) or \
                not jnp.issubdtype(w.dtype, jnp.floating):
            return x @ (w.dequantize() if is_quantized(w) else w)
        if self._kernel_takes(w, w.shape):
            return self._kernel(x, w, base, off)
        return x @ self.perturb(name, w)

    def _kernel(self, x, w, base, off, layer=None):
        """The fused kernel on a 2-D weight, or on a stack at ``layer``; a
        quantized base dequantizes in the same tile pass (X @ (q*scale +
        coeff*z), base resident as int8)."""
        from repro.kernels import ops as kops  # lazy: pallas import
        obs.zo_matmul_weight(obs.W_SLICE if layer is None else obs.W_STACKED)
        q, scale = (w.q, w.scale) if is_quantized(w) else (w, None)
        y = kops.zo_matmul(x.reshape(-1, x.shape[-1]), q, base, 0,
                           self._coeff(), dist=self.dist, prime_offset=off,
                           prehashed=True, scale=scale, layer=layer)
        return y.reshape(*x.shape[:-1], y.shape[-1])

    def _matmul_users(self, x, w, name: str):
        """User-axis matmul: x (U, ..., K). A SHARED 2-D base (plain f32
        or delta-less quantized) on the aligned kernel path dispatches
        one :func:`repro.kernels.ops.zo_matmul_users` -- B users'
        perturbed forwards reading the resident base once; stacked /
        delta-carrying weights vmap the scalar lane (bit-identical to a
        per-user loop either way)."""
        path, base, off = self._leaf(name)   # base: (U,) lane vector
        seeds, coeffs = self._user_lanes()
        shared = (w.delta is None and w.q.ndim == 2) if is_quantized(w) \
            else (w.ndim == 2)
        floating = jnp.issubdtype(w.dtype, jnp.floating)
        wshape = w.q.shape if is_quantized(w) else w.shape
        if shared and floating and is_perturbable(path) and \
                self.use_kernel and kernel_aligned(wshape):
            from repro.kernels import ops as kops  # lazy: pallas import
            u, lead, k = x.shape[0], x.shape[1:-1], x.shape[-1]
            n = wshape[-1]
            if is_quantized(w):
                y = kops.zo_matmul_users(x.reshape(u, -1, k), w.q, base, 0,
                                         coeffs, dist=self.dist,
                                         prime_offset=off, prehashed=True,
                                         scale=w.scale)
            else:
                y = kops.zo_matmul_users(x.reshape(u, -1, k), w, base, 0,
                                         coeffs, dist=self.dist,
                                         prime_offset=off, prehashed=True)
            return y.reshape(u, *lead, n)
        w_ax = None if (shared and not is_quantized(w)) \
            else self._user_axes(w)
        return jax.vmap(
            lambda s, c, xu, wu: self._lane(s, c)._matmul_one(xu, wu, name),
            in_axes=(0, 0, 0, w_ax))(seeds, coeffs, x, w)

    def take(self, name: str, table, ids):
        """take(table + coeff*z, ids, axis=0), perturbing only gathered
        rows. A quantized table dequantizes only the gathered rows too
        (quant.take_rows): still O(tokens*d), never O(vocab*d).

        User-axis mode: ``ids`` carry a leading user axis; the table
        follows the weight conventions (stacked plain / shared base)."""
        table = _resolve(table)
        if self.batched:
            seeds, coeffs = self._user_lanes()
            return jax.vmap(
                lambda s, c, tb, i: self._lane(s, c).take(name, tb, i),
                in_axes=(0, 0, self._user_axes(table), 0))(
                seeds, coeffs, table, ids)
        path, base, off = self._leaf(name)
        if not is_perturbable(path) or \
                not jnp.issubdtype(table.dtype, jnp.floating):
            return take_rows(table, ids)
        rows = take_rows_f32(table, ids)
        z = zrng.z_rows(base, ids, table.shape[1], jnp.float32, self.dist,
                        prime_offset=off)
        return (rows + self._coeff() * z).astype(table.dtype)

    def materialize(self, subtree: PyTree, name: str = "") -> PyTree:
        """Perturb every leaf of a param subtree transiently.

        Generic fallback for components without a per-leaf fused path --
        today only MoE expert sub-dicts (stacked 3/4-D leaves consumed
        inside sort-based dispatch) -- and, scoped at the root, the
        parity oracle the tests evaluate the fused forward against.
        Equivalent to ``add_scaled_z`` restricted to the subtree: one
        transient copy of the subtree.
        """
        ctx = self.scope(name) if name else self
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            subtree, is_leaf=is_quantized)
        out = [ctx.perturb(_path_str(p), leaf) for p, leaf in leaves]
        return jax.tree_util.tree_unflatten(treedef, out)


def sub(ctx: Optional[PerturbCtx], name: str) -> Optional[PerturbCtx]:
    """ctx.scope(name), passing None through (unperturbed forward)."""
    return None if ctx is None else ctx.scope(name)

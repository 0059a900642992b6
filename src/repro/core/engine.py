"""The ZO step: direction estimators × update rules.

PocketLLM's memory claim rests on one invariant: a training step is fully
described by the scalar pair ``(seed, gs)``. The step function is built
from two choices —

* a **DirectionEvaluator** realizes ``L(theta ± eps*z_k)`` for K
  directions and returns the projected gradients ``gs``. Neither writes
  the base point, so the ``(seed, gs)`` replay log reconstructs a step
  bit-exactly:

  - ``fused``   — the path every benchmark cell runs and the default: a
    :class:`~repro.core.perturb_ctx.PerturbCtx` with ``coeff=±eps``
    rides into the forward and dense projections compute
    ``X @ (W + coeff*z)`` via the Pallas ``zo_matmul`` kernel (0 param
    sweeps per direction);
  - ``vmapdir`` — the materialized reference: directions evaluated
    concurrently under ``vmap`` over transient ``theta ± eps*z`` copies
    (pod-shardable);

* an **UpdateRule** turns ``(seed, gs)`` into a parameter update:

  - ``sgd``       — the shared f32 seed-replay tail
    ``theta -= lr * sum_k coeffs_k * gs_k * z_k`` (the default);
  - ``momentum``  — ZO momentum via *truncated seed replay*: classical
    momentum needs a param-sized velocity buffer (exactly the memory MeZO
    exists to avoid), but the ZO velocity is structurally
    ``v_t = sum_i beta^{t-i} g_i z_i``, so a window of M
    ``(seed, gs, coeffs)`` rows represents it in O(M*K) scalars and the
    update replays the window with geometric weights;
  - ``stale-sgd`` — sgd scaled by ``decay ** staleness``, the async
    fleet's rule.

Every estimator×update pairing shares the same f32 update arithmetic
(:func:`_direction_coeffs` / :func:`_apply_direction_updates`), which is
what keeps the ``(seed, gs)`` replay log interchangeable across them.

The engine also owns:

* :class:`TrainState` — the one pytree a step consumes and produces
  (params, step counter, update-rule state). The checkpoint manager
  snapshots/restores it whole, so momentum history and Adam moments
  survive a crash (``checkpoint/manager.py``).
* :func:`build_strategy` — the trainer and CLI resolve
  ``--estimator fused --update momentum`` through it, from two
  module-level tables of estimators and update rules.
* :meth:`ZOStrategy.run_chunk` — a multi-step ``lax.scan`` over a stacked
  batch pytree that amortizes per-step dispatch overhead.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import rng as zrng
from repro.core.perturb import add_scaled_z
from repro.core.perturb_ctx import PerturbCtx

PyTree = Any
# (params, batch) -> scalar; the fused estimator additionally requires a
# ``perturb=`` keyword (models built by repro.models.build_model accept it)
LossFn = Callable[..., jnp.ndarray]


# ---------------------------------------------------------------------------
# configs / aux / state


@dataclasses.dataclass(frozen=True)
class MezoConfig:
    eps: float = 1e-3
    lr: float = 1e-6
    n_directions: int = 1          # K: SPSA directions averaged per step
    dist: str = "rademacher"       # or "gaussian" (MeZO-repo default)
    use_kernel: bool = False       # route 2-D leaves via Pallas zo_add
    momentum: float = 0.0          # ZO momentum via truncated seed replay
    momentum_window: int = 8       # directions of history to replay
    weight_decay: float = 0.0
    staleness_decay: float = 0.8   # async fleet: update scale decay^stale


@dataclasses.dataclass
class MezoAux:
    loss: jnp.ndarray         # mean of (l+ + l-)/2 over directions
    gs: jnp.ndarray           # (K,) projected gradients -- the replay log
    seed: jnp.ndarray         # uint32 step seed -- the replay log
    grad_norm_est: jnp.ndarray


jax.tree_util.register_pytree_node(
    MezoAux,
    lambda a: ((a.loss, a.gs, a.seed, a.grad_norm_est), None),
    lambda _, c: MezoAux(*c),
)


@dataclasses.dataclass(frozen=True)
class TrainState:
    """Everything a training step consumes and produces.

    ``opt`` is the update rule's state: ``{}`` for sgd, the truncated
    seed-replay window for momentum, or an ``optim.adam.AdamState`` for
    the gradient baseline. Snapshotting this pytree whole (instead of bare
    params) is what makes momentum history / Adam moments survive resume.

    ``params`` may be a quantized base (``optim.quant.quantize_tree``
    with deltas attached): the int8 values + scales stay frozen, every
    update rule writes the f32 ``delta`` of each quantized leaf through
    the same ``add_scaled_z`` replay arithmetic, and the replay log is
    byte-identical to an f32 run's -- checkpoints and adapters need no
    format change.
    """
    params: PyTree
    step: jnp.ndarray              # uint32 scalar: completed-step count
    opt: PyTree


jax.tree_util.register_pytree_with_keys(
    TrainState,
    lambda s: (((jax.tree_util.DictKey("params"), s.params),
                (jax.tree_util.DictKey("step"), s.step),
                (jax.tree_util.DictKey("opt"), s.opt)), None),
    lambda _, c: TrainState(*c),
)


# ---------------------------------------------------------------------------
# the shared f32 update tail (identical across every strategy — this is
# what keeps the (seed, gs) replay log interchangeable)


def _direction_coeffs(kk: int, lr, direction_mask):
    """Per-direction update coefficients: ``-lr/K``, or with a straggler
    mask ``-lr * m_k / max(sum(m), 1)`` — an unbiased mean over survivors.

    The unmasked branch multiplies by the f32 reciprocal instead of
    dividing: ``lr`` may now arrive traced (the user-batched engine
    threads per-user lr vectors through jit), and XLA rewrites division
    by a *constant* K into multiply-by-reciprocal while the eager replay
    paths (checkpoint manager, adapter store) would keep true division —
    a last-ulp fork for non-power-of-two K. One explicit multiply keeps
    live jit and eager replay on identical ops, hence bit-identical.
    """
    if direction_mask is None:
        return jnp.full((kk,), -lr * jnp.float32(1.0 / kk), jnp.float32)
    m = jnp.asarray(direction_mask, jnp.float32).reshape(kk)
    return -lr * m / jnp.maximum(m.sum(), 1.0)


def _staleness_coeffs(kk: int, lr, direction_mask, staleness, decay):
    """Per-direction coefficients for an *asynchronously delivered*
    direction set: the synchronous coefficients scaled by
    ``decay ** staleness``, where ``staleness`` counts the updates
    applied between the worker's params snapshot and this apply.

    ZO tolerates this where SGD cannot -- a stale ``gs`` is still an
    unbiased directional sample at a nearby point, so down-weighting
    (rather than discarding) keeps slow workers contributing. The decay
    is one extra f32 multiply on top of :func:`_direction_coeffs`
    (``x * 1.0`` is exact for staleness 0, so a fresh result is
    bit-identical to the synchronous path), and both the live fleet
    coordinator and log replay compute it from the same logged integer
    -- which is what keeps async runs bit-replayable.
    """
    base = _direction_coeffs(kk, lr, direction_mask)
    scale = jnp.float32(decay) ** jnp.asarray(staleness, jnp.float32)
    return base * scale


def _apply_direction_updates(params, seed, gs, coeffs, cfg: MezoConfig):
    """theta += sum_k coeffs[k] * gs[k] * z_k, z_k regenerated per k."""
    k_tot = gs.shape[0]

    def body(p, kg):
        k, g, c = kg
        return add_scaled_z(p, zrng.fold_seed(seed, k), c * g,
                            dist=cfg.dist, use_kernel=cfg.use_kernel), None

    params, _ = jax.lax.scan(
        body, params, (jnp.arange(k_tot, dtype=jnp.uint32), gs, coeffs))
    return params


def _decay(params, wd_coeff):
    if wd_coeff is None:
        return params
    from repro.optim.quant import is_quantized

    def leaf(p):
        if is_quantized(p):
            # decay the effective weight (q*scale + delta) by folding it
            # entirely into the f32 delta: (q*s + d)(1-c) = q*s +
            # (d*(1-c) - c*q*s). The int8 values AND the power-of-two
            # scales stay frozen -- mutating the scale would break the
            # exact-product property the atol=0 fused-vs-materialized
            # parity rests on. Delta-less leaves are frozen (same
            # semantics as add_scaled_z) and pass through.
            if p.delta is None:
                return p
            wd = jnp.asarray(wd_coeff, jnp.float32)
            return dataclasses.replace(
                p, delta=p.delta * (1.0 - wd) - wd * p.base_f32())
        return ((p * (1.0 - wd_coeff)).astype(p.dtype)
                if jnp.issubdtype(p.dtype, jnp.floating) else p)

    return jax.tree.map(leaf, params, is_leaf=is_quantized)


# ---------------------------------------------------------------------------
# direction evaluators


@dataclasses.dataclass(frozen=True)
class DirectionEvaluator:
    """How ``theta ± eps*z`` is realized for the 2K loss evaluations.

    eval_fn: (loss_fn, params, batch, seed, cfg, eps=None) -> (gs, ls).
    ``params`` is read, never written. ``eps`` optionally overrides
    ``cfg.eps`` with a traced f32 scalar — the jitted steps always pass
    it so the projected gradient ``(l+ - l-) / (2 eps)`` is a true
    division for constant and traced eps alike (XLA rewrites division by
    a *baked* constant into multiply-by-reciprocal, which would fork the
    last ulp between the sequential and user-batched paths).

    donate: the step jit may donate the input TrainState's buffers.
    """
    name: str
    eval_fn: Callable[..., Tuple[jnp.ndarray, jnp.ndarray]]
    donate: bool


def _f32(value, default: float):
    """Traced-or-config f32 scalar (``None`` -> the config constant)."""
    return jnp.float32(default) if value is None \
        else jnp.asarray(value, jnp.float32)


def _eval_vmapdir(loss_fn: LossFn, params: PyTree, batch: Any, seed,
                  cfg: MezoConfig, eps=None):
    """Direction-parallel evaluation: the K-way vmap axis is what the
    launcher shards over the ``pod`` mesh axis; the only cross-pod
    exchange is the (K,) vector ``gs``."""
    eps = _f32(eps, cfg.eps)

    def eval_dir(k):
        s = zrng.fold_seed(seed, k)
        pp = add_scaled_z(params, s, eps, dist=cfg.dist)
        with jax.named_scope(obs.FORWARD):
            lp = loss_fn(pp, batch)
        pm = add_scaled_z(params, s, -eps, dist=cfg.dist)
        with jax.named_scope(obs.FORWARD):
            lm = loss_fn(pm, batch)
        return (lp - lm) / (2.0 * eps), 0.5 * (lp + lm)

    return jax.vmap(eval_dir)(jnp.arange(cfg.n_directions, dtype=jnp.uint32))


def _eval_fused(loss_fn: LossFn, params: PyTree, batch: Any, seed,
                cfg: MezoConfig, eps=None):
    """Fused perturbed forward: 0 param sweeps per direction. ``loss_fn``
    must accept a ``perturb=`` keyword; both sides of each direction see
    the exact z-fields ``add_scaled_z`` would apply, so losses match
    ``vmapdir`` bit-for-bit on the jnp path in f32."""
    eps = _f32(eps, cfg.eps)

    def one_dir(_, k):
        s = zrng.fold_seed(seed, k)
        ctx = PerturbCtx(seed=s, coeff=eps, dist=cfg.dist,
                         use_kernel=cfg.use_kernel)
        with jax.named_scope(obs.FORWARD):
            lp = loss_fn(params, batch, perturb=ctx)
        with jax.named_scope(obs.FORWARD):
            lm = loss_fn(params, batch,
                         perturb=dataclasses.replace(ctx, coeff=-eps))
        return None, ((lp - lm) / (2.0 * eps), 0.5 * (lp + lm))

    _, (gs, ls) = jax.lax.scan(one_dir, None,
                               jnp.arange(cfg.n_directions, dtype=jnp.uint32))
    return gs, ls


# ---------------------------------------------------------------------------
# update rules


@dataclasses.dataclass(frozen=True)
class UpdateRule:
    """How (seed, gs) becomes a parameter update.

    init_fn:   cfg -> opt state pytree (shapes only depend on cfg).
    update_fn: (params, opt, seed, gs, direction_mask, cfg, lr=None)
               -> (params, opt). Consumes only scalars beyond params —
               this same function is the checkpoint manager's replay
               primitive (zero forward passes on recovery). ``lr``
               optionally overrides ``cfg.lr`` with a traced f32 scalar
               (the user-batched engine threads per-user lr vectors).
    """
    name: str
    init_fn: Callable[[MezoConfig], PyTree]
    update_fn: Callable[..., Tuple[PyTree, PyTree]]


def _sgd_init(cfg: MezoConfig) -> PyTree:
    return {}


def _sgd_update(params, opt, seed, gs, direction_mask, cfg: MezoConfig,
                lr=None):
    seed = jnp.asarray(seed, jnp.uint32)
    gs = jnp.asarray(gs, jnp.float32).reshape(-1)
    lr = _f32(lr, cfg.lr)
    coeffs = _direction_coeffs(gs.shape[0], lr, direction_mask)
    if cfg.weight_decay:
        params = _decay(params, lr * cfg.weight_decay)
    return _apply_direction_updates(params, seed, gs, coeffs, cfg), opt


def _stale_sgd_update(params, opt, seed, gs, direction_mask,
                      cfg: MezoConfig, lr=None, staleness=None):
    """sgd with staleness decay: the async fleet's update rule.

    ``staleness=None``/``0`` degenerates to :func:`_sgd_update`
    bit-exactly (the decay multiply is by exactly 1.0), so the
    checkpoint manager can replay a stale-sgd log tail through the
    standard ``update_fn(params, opt, seed, gs, mask, cfg)`` call and a
    mixed log (sync steps + async steps) stays coherent.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    gs = jnp.asarray(gs, jnp.float32).reshape(-1)
    lr = _f32(lr, cfg.lr)
    coeffs = _staleness_coeffs(gs.shape[0], lr, direction_mask,
                               0 if staleness is None else staleness,
                               cfg.staleness_decay)
    if cfg.weight_decay:
        params = _decay(params, lr * cfg.weight_decay)
    return _apply_direction_updates(params, seed, gs, coeffs, cfg), opt


def momentum_history_init(cfg: MezoConfig) -> PyTree:
    """Empty truncated-replay window: M rows of (seed, gs, coeffs).
    Zero rows are exact no-ops (g=0 ⇒ 0*z added)."""
    m, k = cfg.momentum_window, cfg.n_directions
    return {"seeds": jnp.zeros((m,), jnp.uint32),
            "gs": jnp.zeros((m, k), jnp.float32),
            "coeffs": jnp.zeros((m, k), jnp.float32)}


def _momentum_update(params, opt, seed, gs, direction_mask,
                     cfg: MezoConfig, lr=None):
    """ZO momentum via truncated seed replay (paper Sec 6.2 asks for
    faster derivative-free methods).

    The window stores each step's own f32 coefficients (its lr and
    straggler-mask renormalization), so replaying an entry reproduces
    exactly the sgd update that step would have applied, scaled by the
    geometric weight ``(1-beta) * beta^age``. Memory: M*(2K+1) scalars.
    Compute: M extra z-regeneration sweeps per step (bandwidth-bound,
    no forwards).
    """
    seed = jnp.asarray(seed, jnp.uint32)
    gs = jnp.asarray(gs, jnp.float32).reshape(-1)
    lr = _f32(lr, cfg.lr)
    kk = gs.shape[0]
    beta = jnp.float32(cfg.momentum)
    coeffs = _direction_coeffs(kk, lr, direction_mask)

    # roll the window: newest last
    seeds_h = jnp.concatenate([opt["seeds"][1:], seed[None]])
    gs_h = jnp.concatenate([opt["gs"][1:], gs[None]])
    cf_h = jnp.concatenate([opt["coeffs"][1:], coeffs[None]])

    m = seeds_h.shape[0]
    ages = jnp.arange(m - 1, -1, -1, dtype=jnp.float32)
    weights = ((1.0 - beta) * beta ** ages if cfg.momentum
               else jnp.where(ages == 0, 1.0, 0.0))

    if cfg.weight_decay:
        params = _decay(params, lr * cfg.weight_decay)

    def entry(p, inp):
        s_j, g_j, c_j, w_j = inp

        def dir_body(pp, kgc):
            k, g, c = kgc
            return add_scaled_z(pp, zrng.fold_seed(s_j, k), w_j * c * g,
                                dist=cfg.dist,
                                use_kernel=cfg.use_kernel), None

        p, _ = jax.lax.scan(
            dir_body, p, (jnp.arange(kk, dtype=jnp.uint32), g_j, c_j))
        return p, None

    params, _ = jax.lax.scan(entry, params, (seeds_h, gs_h, cf_h, weights))
    return params, {"seeds": seeds_h, "gs": gs_h, "coeffs": cf_h}


# ---------------------------------------------------------------------------
# the composed strategy


def _step_body(strategy: "ZOStrategy", loss_fn: LossFn, state: TrainState,
               batch: Any, seed, cfg: MezoConfig, direction_mask,
               eps=None, lr=None):
    seed = jnp.asarray(seed, jnp.uint32)
    gs, ls = strategy.estimator.eval_fn(
        loss_fn, state.params, batch, seed, cfg, eps=eps)
    with jax.named_scope(obs.UPDATE):
        params, opt = strategy.update.update_fn(
            state.params, state.opt, seed, gs, direction_mask, cfg, lr=lr)
    aux = MezoAux(loss=ls.mean(), gs=gs, seed=seed,
                  grad_norm_est=jnp.abs(gs).mean())
    return TrainState(params=params, step=state.step + jnp.uint32(1),
                      opt=opt), aux


# eps/lr ride into every jitted step as *traced* operands (not cfg
# constants baked into the trace): a step's arithmetic is then identical
# whether eps/lr come from the config, a replay record, or a per-user
# vector sliced by vmap — which is what makes the user-batched step
# bit-exact against the sequential one.
@partial(jax.jit, static_argnames=("strategy", "loss_fn", "cfg"))
def _jit_step(strategy, loss_fn, state, batch, seed, cfg,
              direction_mask=None, eps=None, lr=None):
    return _step_body(strategy, loss_fn, state, batch, seed, cfg,
                      direction_mask, eps, lr)


@partial(jax.jit, static_argnames=("strategy", "loss_fn", "cfg"),
         donate_argnums=(2,))
def _jit_step_donate(strategy, loss_fn, state, batch, seed, cfg,
                     direction_mask=None, eps=None, lr=None):
    return _step_body(strategy, loss_fn, state, batch, seed, cfg,
                      direction_mask, eps, lr)


@partial(jax.jit, static_argnames=("strategy", "loss_fn", "cfg"),
         donate_argnums=(2,))
def _jit_chunk(strategy, loss_fn, state, batches, base_seed, cfg,
               eps=None, lr=None):
    base = jnp.asarray(base_seed, jnp.uint32)

    def body(st, batch):
        return _step_body(strategy, loss_fn, st, batch,
                          zrng.fold_seed(base, st.step), cfg, None,
                          eps, lr)

    return jax.lax.scan(body, state, batches)


@partial(jax.jit, static_argnames=("strategy", "loss_fn", "cfg",
                                   "state_axes"),
         donate_argnums=(2,))
def _jit_step_users(strategy, loss_fn, state, batch, seeds, cfg,
                    active, eps, lr, state_axes):
    """One dispatch advances every slot of a user-stacked TrainState.

    ``state`` carries a leading user axis on every per-user leaf (params
    deltas / f32 weights, the step counter, opt state) while quantized
    leaves keep ONE resident int8 base (``q``/``scale`` unbatched —
    ``state_axes`` maps them to ``None``). Each lane runs the exact
    sequential ``_step_body`` with its own (seed, eps, lr), then inactive
    lanes are masked back to their previous state (ragged admission /
    early finishers), so an active lane's trajectory is bit-identical to
    a lone sequential run and an inactive lane is bit-frozen.
    """
    from repro.core.batching import masked_merge

    def lane(st, b, seed, e, l):
        return _step_body(strategy, loss_fn, st, b, seed, cfg, None, e, l)

    axes = state_axes.unflatten()
    new_state, aux = jax.vmap(
        lane, in_axes=(axes, 0, 0, 0, 0), out_axes=(axes, 0))(
        state, batch, seeds, eps, lr)
    return masked_merge(state, new_state, active, axis=0), aux


@dataclasses.dataclass(frozen=True)
class ZOStrategy:
    """One estimator×update pairing, jit-cached per (loss_fn, cfg)."""
    estimator: DirectionEvaluator
    update: UpdateRule

    @property
    def name(self) -> str:
        return f"{self.estimator.name}+{self.update.name}"

    def init_state(self, params: PyTree, cfg: MezoConfig,
                   step: int = 0) -> TrainState:
        return TrainState(params=params, step=jnp.uint32(step),
                          opt=self.update.init_fn(cfg))

    def step(self, loss_fn: LossFn, state: TrainState, batch: Any, seed,
             cfg: MezoConfig, direction_mask=None, step: Optional[int] = None
             ) -> Tuple[TrainState, MezoAux]:
        """One step; ``step`` (the caller's step number) only labels the
        host span."""
        fn = _jit_step_donate if self.estimator.donate else _jit_step
        with obs.span("zo.step", step=step):
            return fn(self, loss_fn, state, batch,
                      jnp.asarray(seed, jnp.uint32), cfg, direction_mask,
                      jnp.float32(cfg.eps), jnp.float32(cfg.lr))

    def lower(self, loss_fn: LossFn, state: TrainState, batch: Any, seed,
              cfg: MezoConfig, direction_mask=None):
        """AOT-lower one step (HLO inspection / cost analysis)."""
        fn = _jit_step_donate if self.estimator.donate else _jit_step
        return fn.lower(self, loss_fn, state, batch,
                        jnp.asarray(seed, jnp.uint32), cfg, direction_mask,
                        jnp.float32(cfg.eps), jnp.float32(cfg.lr))

    def run_chunk(self, loss_fn: LossFn, state: TrainState, batches: Any,
                  base_seed, cfg: MezoConfig
                  ) -> Tuple[TrainState, MezoAux]:
        """Run N steps in one ``lax.scan`` dispatch.

        ``batches`` is a pytree whose leaves are stacked on a leading N
        axis (step i consumes slice i). Per-step seeds are derived inside
        the scan as ``fold_seed(base_seed, state.step)`` — identical to
        the Trainer's per-step derivation, so a chunked run is
        seed-compatible (and replay-log-compatible) with a stepwise one.
        Returns the final state and a stacked MezoAux (leaves gain a
        leading N axis).
        """
        return _jit_chunk(self, loss_fn, state, batches,
                          jnp.asarray(base_seed, jnp.uint32), cfg,
                          jnp.float32(cfg.eps), jnp.float32(cfg.lr))

    def step_users(self, loss_fn: LossFn, state: TrainState, batch: Any,
                   seeds, cfg: MezoConfig, active, eps=None, lr=None
                   ) -> Tuple[TrainState, MezoAux]:
        """Advance U users' slots in ONE dispatch (the multi-tenant step).

        ``state`` is a user-stacked TrainState (``core.batching``): every
        per-user leaf carries a leading U axis, quantized leaves share
        the single resident int8 base. ``batch`` leaves are stacked on a
        leading U axis; ``seeds`` / ``eps`` / ``lr`` are per-user
        vectors; ``active`` is the (U,) slot-occupancy mask — inactive
        lanes come back bit-identical (masked merge), active lanes
        bit-identical to a lone sequential :meth:`step` with the same
        (seed, eps, lr).
        """
        from repro.core.batching import AxesSpec, user_state_axes
        u = seeds.shape[0]
        eps = jnp.broadcast_to(_f32(eps, cfg.eps), (u,))
        lr = jnp.broadcast_to(_f32(lr, cfg.lr), (u,))
        return _jit_step_users(
            self, loss_fn, state, batch, jnp.asarray(seeds, jnp.uint32),
            cfg, jnp.asarray(active, bool), eps, lr,
            AxesSpec(user_state_axes(state)))


# ---------------------------------------------------------------------------
# the estimator and update-rule tables


SGD = UpdateRule(name="sgd", init_fn=_sgd_init, update_fn=_sgd_update)
STALE_SGD = UpdateRule(name="stale-sgd", init_fn=_sgd_init,
                       update_fn=_stale_sgd_update)

_ESTIMATORS: Dict[str, DirectionEvaluator] = {e.name: e for e in (
    DirectionEvaluator(name="fused", eval_fn=_eval_fused, donate=True),
    DirectionEvaluator(name="vmapdir", eval_fn=_eval_vmapdir, donate=False),
)}
_UPDATE_RULES: Dict[str, UpdateRule] = {u.name: u for u in (
    SGD, STALE_SGD,
    UpdateRule(name="momentum", init_fn=momentum_history_init,
               update_fn=_momentum_update),
)}
_STRATEGY_CACHE: Dict[Tuple[str, str], ZOStrategy] = {}


def estimator_names():
    return sorted(_ESTIMATORS)


def update_rule_names():
    return sorted(_UPDATE_RULES)


def build_strategy(estimator: str = "fused", update: str = "sgd"
                   ) -> ZOStrategy:
    """Compose an estimator×update pairing by name (cached singletons,
    so jit caches keyed on the strategy stay warm)."""
    if estimator not in _ESTIMATORS:
        raise ValueError(
            f"unknown direction estimator {estimator!r}; "
            f"registered: {estimator_names()}")
    if update not in _UPDATE_RULES:
        raise ValueError(
            f"unknown update rule {update!r}; "
            f"registered: {update_rule_names()}")
    key = (estimator, update)
    if key not in _STRATEGY_CACHE:
        _STRATEGY_CACHE[key] = ZOStrategy(
            estimator=_ESTIMATORS[estimator], update=_UPDATE_RULES[update])
    return _STRATEGY_CACHE[key]

"""Fault-tolerant training loop for ZO (MeZO) and gradient (Adam) arms.

Responsibilities: build model + shardings, resolve the training strategy
from the engine registry, auto-resume (TrainState snapshot + replay log),
per-step straggler masks, metrics, periodic checkpointing. The loop is
deliberately dumb -- all cleverness lives in core/ and checkpoint/ -- so
its failure behavior is auditable: any crash between two ``on_step``
calls loses at most the step in flight.

Strategy resolution: ``TrainerConfig.optimizer`` is "mezo" (the ZO
arm) or "adam" (the gradient baseline). The ZO arm's step is
``build_strategy(estimator, update)``, by default ``fused`` + ``sgd``:
the step every benchmark cell runs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.checkpoint.manager import CheckpointManager
from repro.core import rng as zrng
from repro.core.engine import TrainState, build_strategy
from repro.core.mezo import MezoConfig
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.optim.adam import AdamConfig, adam_init, grad_train_step
from repro.optim.quant import (check_quant_mode, quantize_tree,
                               tree_is_quantized)
from repro.runtime.stragglers import StragglerPolicy

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    optimizer: str = "mezo"          # mezo (ZO) | adam
    estimator: str = "fused"         # ZO only: fused | vmapdir
    update: str = "sgd"              # ZO only: sgd | momentum | stale-sgd
    mezo: MezoConfig = MezoConfig()
    adam: AdamConfig = AdamConfig()
    quant: str = "none"              # base-weight quantization: none | int8
    n_steps: int = 100
    seed: int = 0
    ckpt_dir: Optional[str] = None
    snapshot_every: int = 100
    log_every: int = 10
    straggler_redundancy: int = 0


class Trainer:
    def __init__(self, model_cfg: ModelConfig, train_cfg: TrainerConfig,
                 batches: Iterator[Any], mesh=None,
                 log_fn: Callable[[str], None] = print):
        self.strategy = None
        check_quant_mode(train_cfg.quant)
        if train_cfg.quant != "none" and train_cfg.optimizer == "adam":
            raise ValueError(
                "quantized bases require a ZO strategy: the gradient "
                "baseline differentiates through the weights, but an "
                "int8 base is frozen (updates live in the f32 delta, "
                "written by seed replay)")
        if train_cfg.optimizer == "mezo":
            self.strategy = build_strategy(train_cfg.estimator,
                                           train_cfg.update)
        elif train_cfg.optimizer != "adam":
            raise ValueError(
                f"unknown TrainerConfig.optimizer {train_cfg.optimizer!r}; "
                f"expected one of ['mezo', 'adam']")
        elif (train_cfg.estimator, train_cfg.update) != ("fused", "sgd"):
            raise ValueError(
                "TrainerConfig.estimator/.update compose the ZO step and "
                "cannot be combined with optimizer='adam' (the gradient "
                "baseline has no estimator×update axes)")

        self.mcfg = model_cfg
        self.tcfg = train_cfg
        self.model = build_model(model_cfg)
        self.batches = batches
        self.mesh = mesh
        self.log = log_fn
        self.losses: list = []
        self._pending: list = []     # device loss scalars awaiting host sync
        self._straggler = (StragglerPolicy(
            train_cfg.mezo.n_directions,
            train_cfg.straggler_redundancy)
            if train_cfg.straggler_redundancy else None)

        self.ckpt = (CheckpointManager(
            train_cfg.ckpt_dir,
            mezo_cfg=(self._mezo_cfg() if self.strategy else None),
            snapshot_every=train_cfg.snapshot_every,
            update_rule=(self.strategy.update if self.strategy else None))
            if train_cfg.ckpt_dir else None)

    # -- setup ------------------------------------------------------------
    def init_params(self) -> PyTree:
        return self.model.init(jax.random.PRNGKey(self.tcfg.seed))

    def _maybe_quantize(self, params: PyTree) -> PyTree:
        """One-shot base quantization (TrainerConfig.quant). Deltas are
        attached so every update rule can write the f32 stream; a tree
        that arrives already quantized passes through."""
        if self.tcfg.quant == "none" or tree_is_quantized(params):
            return params
        return quantize_tree(params, self.tcfg.quant, with_delta=True)

    def _mezo_cfg(self) -> MezoConfig:
        c = self.tcfg.mezo
        if self._straggler:
            c = dataclasses.replace(
                c, n_directions=self._straggler.total)
        return c

    def _init_state(self, params: PyTree, mcfg: MezoConfig) -> TrainState:
        if self.strategy is not None:
            return self.strategy.init_state(params, mcfg)
        return TrainState(params=params, step=jnp.uint32(0),
                          opt=adam_init(params))

    def _sync_losses(self):
        """Host-sync the buffered device scalars (one transfer per batch
        of steps instead of one per step)."""
        if self._pending:
            self.losses.extend(float(x) for x in self._pending)
            self._pending.clear()

    # -- main loop --------------------------------------------------------
    def run_step(self, state: TrainState, step: int):
        """The loop body: next batch, straggler mask, the strategy's
        step (or Adam's), replay-log append and snapshot. Returns
        ``(state, aux)``; ``aux`` is None for Adam. Dispatches without
        waiting on the device, except where the replay log reads gs."""
        with obs.span("train.batch"):
            batch = {k: jnp.asarray(v) for k, v in next(self.batches).items()}
            seed = zrng.fold_seed(jnp.uint32(self.tcfg.seed), step)
        mask = None
        if self.strategy is None:
            p, opt, loss = grad_train_step(
                self.model.loss, state.params, batch, state.opt,
                self.tcfg.adam)
            state = TrainState(params=p, step=jnp.uint32(step + 1), opt=opt)
            aux = None
            self._pending.append(loss)
        else:
            if self._straggler:
                mask = jnp.asarray(self._straggler.mask())
            state, aux = self.strategy.step(
                self.model.loss, state, batch, seed, self._mezo_cfg(), mask,
                step=step)
            self._pending.append(aux.loss)
        if self.ckpt:
            self.ckpt.on_step(step, state, aux, direction_mask=mask)
        return state, aux

    def train(self, params: Optional[PyTree] = None,
              fail_at: Optional[int] = None) -> PyTree:
        """Runs to n_steps with auto-resume. ``fail_at`` raises at that
        step (fault-injection for tests)."""
        start = 0
        mcfg = self._mezo_cfg()
        resume = params is None
        if params is None:
            params = self.init_params()
        params = self._maybe_quantize(params)
        state = self._init_state(params, mcfg)
        if resume and self.ckpt:
            restored, start = self.ckpt.restore(state)
            if restored is not None:
                state = restored
                self.log(f"[trainer] resumed at step {start}")

        t0 = time.perf_counter()
        for step in range(start, self.tcfg.n_steps):
            if fail_at is not None and step == fail_at:
                raise RuntimeError(f"injected failure at step {step}")
            state, _ = self.run_step(state, step)
            if step % self.tcfg.log_every == 0:
                self._sync_losses()
                dt = time.perf_counter() - t0
                self.log(f"[trainer] step={step} loss={self.losses[-1]:.4f} "
                         f"({dt:.1f}s)")
        self._sync_losses()
        return state.params


def train_multi_tenant(model_cfg: ModelConfig, jobs, *, n_slots: int = 4,
                       estimator: str = "fused", update: str = "sgd",
                       seed: int = 0, mezo_cfg: Optional[MezoConfig] = None,
                       quant: str = "none", store=None,
                       log_dir: Optional[str] = None,
                       log_fn: Callable[[str], None] = print):
    """One-call multi-tenant path: run ``jobs`` (TrainJob sequence)
    through a batched :class:`repro.train.TrainEngine` over one shared
    base -- each job's trajectory bit-identical to a lone
    :class:`Trainer` with ``seed=derive_user_seed(seed, job.user)``.

    ``quant="int8"`` quantizes the freshly initialized base before the
    store adopts it (ignored when an explicit ``store`` brings its own
    base). Returns ``(engine, results)``: the engine for its stats and
    store, results jid-sorted.
    """
    from repro.serve.adapters import AdapterStore
    from repro.train import TrainEngine

    check_quant_mode(quant)
    if store is None:
        params = build_model(model_cfg).init(jax.random.PRNGKey(seed))
        if quant != "none":
            params = quantize_tree(params, quant, with_delta=True)
        store = AdapterStore(params, mezo_cfg=mezo_cfg or MezoConfig(),
                             update_rule=build_strategy(
                                 estimator, update).update)
    engine = TrainEngine(model_cfg, store, n_slots=n_slots,
                         estimator=estimator, update=update, seed=seed,
                         mezo_cfg=mezo_cfg, log_dir=log_dir)
    for job in jobs:
        engine.submit(job)
    results = engine.run()
    s = engine.stats
    log_fn(f"[fleet] {s.finished} jobs, {s.user_steps} user-steps in "
           f"{s.dispatches} dispatches ({s.user_steps_per_s:.2f} "
           f"user-steps/s)")
    return engine, results

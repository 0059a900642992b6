"""Checkpoint manager: snapshots + replay log + auto-resume.

Policy: full *train-state* snapshot every ``snapshot_every`` steps
(expensive, rare), replay-log append every step (cheap, always).
``restore()`` finds the newest snapshot, replays the log tail, and
reports the step to resume from -- giving per-step restart granularity at
snapshot-level IO cost.

What gets snapshotted is the engine's whole :class:`TrainState` pytree
(params, step counter, update-rule state), not bare params -- so momentum
history and Adam moments survive a crash instead of silently resetting.
Replay of the log tail goes through the strategy's *update rule*
(``rule.update_fn``), which consumes only the logged ``(seed, gs)``
scalars: sgd replay is the classic seed-replay sweep, momentum replay
additionally rolls the truncated history window forward, so the restored
state is step-for-step what the live run had.

For the Adam baseline (no replay log possible -- gradients depend on
data) it degrades to snapshot-only recovery, losing the steps since the
last snapshot.

Bare-params pytrees (no TrainState) are still accepted when the caller
passes one as ``restore(like=...)``; they replay through
``repro.core.mezo.replay_update`` as before. Note the snapshot *format*
follows the ``like`` structure: a directory written with bare params
cannot be restored as a TrainState (or vice versa) — the Trainer always
snapshots TrainStates.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import store
from repro.checkpoint.replay_log import ReplayLog, replay_into
from repro.core.engine import SGD, TrainState, UpdateRule

PyTree = Any


class CheckpointManager:
    def __init__(self, ckpt_dir: str, mezo_cfg=None,
                 snapshot_every: int = 100, keep: int = 2,
                 update_rule: Optional[UpdateRule] = None):
        self.dir = ckpt_dir
        self.cfg = mezo_cfg
        self.snapshot_every = snapshot_every
        self.keep = keep
        self.rule = update_rule
        self.log = (ReplayLog(os.path.join(ckpt_dir, "replay.jsonl"))
                    if mezo_cfg is not None else None)

    # ---- save -----------------------------------------------------------
    def on_step(self, step: int, state: PyTree, aux=None,
                direction_mask=None):
        """``state`` is the full TrainState (or a bare params pytree);
        ``direction_mask`` is the step's straggler mask, logged so replay
        renormalizes over the same survivors."""
        if self.log is not None and aux is not None:
            with obs.span("ckpt.append", step=step):    # waits on gs
                self.log.append(step, aux.seed, aux.gs, self.cfg.lr,
                                self.cfg.eps, mask=direction_mask)
        if step % self.snapshot_every == 0:
            with obs.span("ckpt.snapshot", step=step):
                store.save_params(self.dir, step, state)
                self._gc()

    def _gc(self):
        steps = sorted(int(d.split("_")[1]) for d in os.listdir(self.dir)
                       if d.startswith("step_"))
        for s in steps[:-self.keep]:
            import shutil
            shutil.rmtree(os.path.join(self.dir, f"step_{s:08d}"))

    # ---- restore --------------------------------------------------------
    def restore(self, like: PyTree, shardings=None
                ) -> Tuple[Optional[PyTree], int]:
        """Returns (state, next_step) or (None, 0) when nothing saved.

        ``like`` fixes the structure/shapes: a TrainState restores the
        full state (opt state included) and replays the log tail through
        the update rule; a bare params pytree keeps the legacy
        params-only behavior.
        """
        snap = store.latest_step(self.dir)
        if snap is None:
            return None, 0
        obj = store.load_params(self.dir, snap, like, shardings)
        if self.log is None:
            if isinstance(obj, TrainState):
                obj = dataclasses.replace(obj, step=jnp.uint32(snap + 1))
            return obj, snap + 1
        records = ReplayLog.read(os.path.join(self.dir, "replay.jsonl"),
                                 after_step=snap)
        if isinstance(obj, TrainState):
            state, last = self._replay_state(obj, records)
            nxt = max(snap, last) + 1
            return dataclasses.replace(state, step=jnp.uint32(nxt)), nxt
        params, last = replay_into(obj, records, self.cfg)
        return params, max(snap, last) + 1

    def _replay_state(self, state: TrainState, records
                      ) -> Tuple[TrainState, int]:
        """Replay logged (seed, gs) records through the update rule --
        zero forward passes; momentum history rolls forward exactly as
        the live steps would have rolled it."""
        rule = self.rule
        if rule is None:
            if jax.tree_util.tree_leaves(state.opt):
                raise ValueError(
                    "restoring a TrainState with non-empty update-rule "
                    "state requires the update_rule= the run was trained "
                    "with; silently replaying the log tail with sgd would "
                    "leave the optimizer state stale")
            rule = SGD
        params, opt, last = state.params, state.opt, -1
        for rec in records:
            c = dataclasses.replace(self.cfg, lr=rec["lr"], eps=rec["eps"])
            mask = rec.get("mask")
            params, opt = rule.update_fn(
                params, opt, np.uint32(rec["seed"]),
                np.asarray(rec["gs"], np.float32),
                None if mask is None else np.asarray(mask, np.float32), c)
            last = rec["step"]
        return dataclasses.replace(state, params=params, opt=opt), last

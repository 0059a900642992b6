"""ZO adapters: a user's entire fine-tune as a few-KB replay log.

A MeZO trajectory is fully determined by ``(theta_base, [(seed_t, gs_t,
lr_t, eps_t)])`` -- so a *personalized* model is not a parameter tree but
a scalar log replayable onto shared base weights with zero forward
passes (``checkpoint/replay_log.py``). That makes the replay log a
derivative-free analogue of the side-tuning adapters of MobiLLM
(arXiv 2502.20421) and the additive deltas of PAE MobiLLM
(arXiv 2507.01216): per-user state is ~KB, and one device can hold
thousands of users' fine-tunes next to a single copy of the base model.

:class:`AdapterStore` is the serving-side registry:

* ``put`` / ``import_checkpoint`` / ``save`` / ``load`` -- adapters move
  as replay-log JSONL (the exact CheckpointManager on-disk format);
* ``materialize(user)`` -- ``base + replay`` on demand, LRU-cached with
  a byte budget so hot users pay zero replays and cold users evict;
* ``export_delta`` / ``put_delta`` -- a compact int8 additive-delta form
  (via ``optim/compression.py``) for adapters whose logs grew long
  enough that replay latency matters more than bit-exactness.

Materializing from records is bit-identical to
``CheckpointManager.restore``; the int8 delta form is lossy by one
quantization roundtrip per leaf.

The shared base may itself be an int8 *quantized* base
(``optim.quant.quantize_tree``): replay then writes each quantized
leaf's f32 delta while the int8 values stay frozen and shared, so a
device serves thousands of users over a ~1 byte/param base -- the
memory story of the paper's Table 1, composed with personalization.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.replay_log import ReplayLog
from repro.core.engine import SGD, UpdateRule
from repro.core.mezo import MezoConfig
from repro.optim.quant import (int8_dequantize, int8_quantize, is_quantized,
                               tree_is_quantized, with_delta)

PyTree = Any

#: adapter id meaning "no adapter" -- materializes the shared base tree.
BASE_USER = "__base__"


@dataclasses.dataclass(frozen=True)
class ZOAdapter:
    """One user's fine-tune: step-ordered replay-log records."""
    user: str
    records: Tuple[dict, ...]

    @property
    def n_steps(self) -> int:
        return len(self.records)

    @property
    def nbytes(self) -> int:
        """Wire size of the adapter itself (the scalars, not the tree)."""
        return len(json.dumps(list(self.records)).encode())


def tree_bytes(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


class AdapterStore:
    """Per-user ZO adapters over one shared base tree.

    ``mezo_cfg`` must carry the ``dist`` / ``weight_decay`` the users
    trained with (lr / eps travel inside each record; K is the logged
    ``gs`` length) -- a mismatched ``dist`` silently materializes a
    different model, exactly like replaying a log with the wrong RNG.
    Runs trained with a stateful update rule (momentum) must pass the
    same ``update_rule`` (and matching ``n_directions`` /
    ``momentum_window`` in ``mezo_cfg``): the whole log replays through
    ``rule.update_fn`` from a fresh state, reproducing the live
    trajectory exactly as ``CheckpointManager._replay_state`` does.
    """

    def __init__(self, base_params: PyTree, mezo_cfg: Optional[MezoConfig]
                 = None, cache_bytes: Optional[int] = None,
                 update_rule: Optional[UpdateRule] = None):
        self.base = base_params
        self.cfg = mezo_cfg or MezoConfig()
        self.cache_bytes = cache_bytes
        self.rule = update_rule or SGD
        self._adapters: Dict[str, ZOAdapter] = {}
        self._deltas: Dict[str, list] = {}
        self._cache: "OrderedDict[str, PyTree]" = OrderedDict()
        self.stats = {"hits": 0, "misses": 0, "evictions": 0,
                      "materialize_s": 0.0, "last_materialize_s": 0.0}

    # ---- registration ----------------------------------------------------
    def put(self, user: str, records: List[dict]) -> ZOAdapter:
        if user == BASE_USER:
            raise ValueError(f"{BASE_USER!r} is reserved for the base tree")
        ad = ZOAdapter(user=user, records=tuple(records))
        self._adapters[user] = ad
        self._cache.pop(user, None)      # re-registered => stale cache entry
        return ad

    def import_checkpoint(self, user: str, ckpt_dir: str) -> ZOAdapter:
        """Adopt a CheckpointManager run's replay log as this user's
        adapter (the whole log: base_params must be the run's theta_0)."""
        path = os.path.join(ckpt_dir, "replay.jsonl")
        records = ReplayLog.read(path)
        if not records:
            raise FileNotFoundError(f"no replay records under {ckpt_dir}")
        return self.put(user, records)

    def save(self, user: str, path: str) -> int:
        """Write the adapter as replay-log JSONL; returns bytes written."""
        ad = self._adapters[user]
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            for rec in ad.records:
                f.write(json.dumps(rec) + "\n")
        return os.path.getsize(path)

    def load(self, user: str, path: str) -> ZOAdapter:
        records = ReplayLog.read(path)
        if not records:
            # an empty adapter would silently serve the base model
            raise FileNotFoundError(f"no replay records in {path}")
        return self.put(user, records)

    def users(self) -> List[str]:
        return sorted(set(self._adapters) | set(self._deltas))

    def records(self, user: Optional[str]) -> Tuple[dict, ...]:
        """The user's stored replay records, step-ordered (empty for the
        base id and for users never ``put`` -- a fresh user resumes from
        nothing). This is the TrainEngine's resume source."""
        if user is None or user == BASE_USER:
            return ()
        ad = self._adapters.get(user)
        return ad.records if ad is not None else ()

    # ---- materialization -------------------------------------------------
    def materialize(self, user: Optional[str]) -> PyTree:
        """``base + replay(user)`` (or base + int8 delta), LRU-cached."""
        if user is None or user == BASE_USER:
            return self.base
        if user in self._cache:
            self.stats["hits"] += 1
            self._cache.move_to_end(user)
            return self._cache[user]
        t0 = time.perf_counter()
        if user in self._adapters:
            params = self._replay(self._adapters[user].records)
        elif user in self._deltas:
            params = self._apply_delta(self._deltas[user])
        else:
            raise KeyError(f"unknown adapter {user!r}; have {self.users()}")
        jax.block_until_ready(params)
        dt = time.perf_counter() - t0
        self.stats["misses"] += 1
        self.stats["materialize_s"] += dt
        self.stats["last_materialize_s"] = dt
        self._cache[user] = params
        self._evict()
        return params

    def _replay_records(self, records) -> Tuple[PyTree, PyTree]:
        """Replay a log through the update rule from a fresh state --
        identical arithmetic to the live steps (sgd: the classic
        seed-replay sweep; momentum: the history window rolls forward
        from empty exactly as training rolled it). Returns the full
        ``(params, opt)`` pair so a trainer can resume mid-log with the
        rule's state intact, not just serve the weights.

        A quantized base (optim/quant.py) works unchanged: the replay
        writes each quantized leaf's f32 delta while the int8 values
        stay frozen and shared across every user -- the resident cost of
        N personalized models is one int8 base plus N delta sets. A
        frozen (delta-less) base gains zero deltas here first."""
        params, opt = self.base, self.rule.init_fn(self.cfg)
        if tree_is_quantized(params):
            params = with_delta(params)
        for rec in records:
            c = dataclasses.replace(self.cfg, lr=rec["lr"], eps=rec["eps"])
            mask = rec.get("mask")
            params, opt = self.rule.update_fn(
                params, opt, np.uint32(rec["seed"]),
                np.asarray(rec["gs"], np.float32),
                None if mask is None else np.asarray(mask, np.float32), c)
        return params, opt

    def _replay(self, records) -> PyTree:
        return self._replay_records(records)[0]

    def materialize_state(self, user: Optional[str]
                          ) -> Tuple[PyTree, PyTree, int]:
        """Resume point for a fine-tune job: ``(params, opt,
        n_replayed)`` after replaying the user's stored records from the
        base. ``None`` / ``BASE_USER`` / a never-seen user start fresh
        (zero records); a user known only by a compact int8 delta raises
        -- deltas are lossy, so resuming training from one would fork
        the trajectory from its own replay log."""
        if (user is not None and user != BASE_USER
                and user in self._deltas and user not in self._adapters):
            raise ValueError(
                f"adapter {user!r} exists only as a lossy int8 delta; "
                f"training resume needs the exact replay log")
        recs = self.records(user)
        params, opt = self._replay_records(recs)
        return params, opt, len(recs)

    def cached_bytes(self) -> int:
        """Bytes the cache actually adds on top of the shared base.

        Quantized leaves in a materialized tree alias the base's int8
        values and scales by reference (replay only writes the f32
        delta), so counting them per cached user would evict hot users
        over phantom bytes -- only the per-user delta is charged."""
        total = 0
        for t in self._cache.values():
            for leaf in jax.tree_util.tree_leaves(t, is_leaf=is_quantized):
                if is_quantized(leaf):
                    total += (leaf.delta.nbytes
                              if leaf.delta is not None else 0)
                else:
                    total += tree_bytes(leaf)
        return total

    def _evict(self):
        """Drop least-recently-used materialized trees past the byte
        budget -- always keeping the most recent one so the caller's
        working tree is never evicted under it."""
        if self.cache_bytes is None:
            return
        while len(self._cache) > 1 and self.cached_bytes() > self.cache_bytes:
            self._cache.popitem(last=False)
            self.stats["evictions"] += 1

    # ---- compact int8 delta form ----------------------------------------
    @staticmethod
    def _eff(leaf):
        """Effective f32 value of a (possibly quantized) leaf."""
        return (leaf.dequantize_f32() if is_quantized(leaf)
                else jnp.asarray(leaf, jnp.float32))

    def export_delta(self, user: str) -> list:
        """Compact the adapter into per-leaf int8 ``(q, scale)`` deltas
        against base -- O(params) bytes/8 instead of O(steps) replay work.
        Lossy (one int8 roundtrip); leaf order is ``jax.tree.leaves``
        (quantized leaves atomic: the delta is over effective weights)."""
        mat = self.materialize(user)
        out = []
        for b, m in zip(
                jax.tree.leaves(self.base, is_leaf=is_quantized),
                jax.tree.leaves(mat, is_leaf=is_quantized)):
            d = self._eff(m) - self._eff(b)
            q, s = int8_quantize(d)
            out.append((np.asarray(q), float(np.asarray(s))))
        return out

    def put_delta(self, user: str, delta: list):
        if user == BASE_USER:
            raise ValueError(f"{BASE_USER!r} is reserved for the base tree")
        self._deltas[user] = delta
        self._cache.pop(user, None)

    def _apply_delta(self, delta: list) -> PyTree:
        leaves, treedef = jax.tree_util.tree_flatten(
            self.base, is_leaf=is_quantized)
        if len(delta) != len(leaves):
            raise ValueError(f"delta has {len(delta)} leaves, base has "
                             f"{len(leaves)}")
        new = []
        for b, (q, s) in zip(leaves, delta):
            d = int8_dequantize(jnp.asarray(q), s)
            if is_quantized(b):
                # keep the int8 base resident; the delta stays additive
                prev = b.delta if b.delta is not None else 0.0
                new.append(dataclasses.replace(b, delta=prev + d))
            else:
                new.append((jnp.asarray(b, jnp.float32) + d).astype(b.dtype))
        return jax.tree_util.tree_unflatten(treedef, new)

    def save_delta(self, user: str, path: str) -> int:
        if not path.endswith(".npz"):      # np.savez appends it silently
            path += ".npz"
        arrays = {}
        for i, (q, s) in enumerate(self._deltas.get(user)
                                   or self.export_delta(user)):
            arrays[f"q_{i}"] = q
            arrays[f"s_{i}"] = np.float32(s)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        np.savez(path, **arrays)
        return os.path.getsize(path)

    def load_delta(self, user: str, path: str):
        if not path.endswith(".npz"):
            path += ".npz"
        data = np.load(path)
        n = len([k for k in data.files if k.startswith("q_")])
        self.put_delta(user, [(data[f"q_{i}"], float(data[f"s_{i}"]))
                              for i in range(n)])

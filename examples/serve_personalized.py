"""End-to-end personalized-LLM flow (the paper's motivating scenario),
multi-user edition:

  1. fine-tune TWO "users" on their own (synthetic) private data with
     MeZO -- same shared base weights, different data,
  2. export each user's fine-tune as a ZO adapter: the replay log alone,
     a few KB of (seed, gs) scalars instead of a parameter tree,
  3. serve interleaved per-user requests from ONE engine instance --
     adapters materialized on demand (base + replay), fused prefill,
     continuous-batching decode.

  PYTHONPATH=src python examples/serve_personalized.py
"""

import shutil

import jax
import numpy as np

from repro.configs import get_config
from repro.core import MezoConfig
from repro.data.synthetic import lm_batches
from repro.models import build_model
from repro.runtime import Trainer, TrainerConfig
from repro.serve import AdapterStore, Request, ServeEngine, tree_bytes

MZ = MezoConfig(eps=1e-2, lr=5e-3, n_directions=4)
USERS = {"alice": 11, "bob": 23}          # user -> private-data seed


def finetune(cfg, user: str, data_seed: int, ckpt: str):
    shutil.rmtree(ckpt, ignore_errors=True)
    # the replay log is a bit-exact reconstruction of the fine-tune: the
    # estimator never writes the base point
    tc = TrainerConfig(estimator="vmapdir", mezo=MZ, n_steps=30,
                       ckpt_dir=ckpt, snapshot_every=15, log_every=10,
                       seed=0)
    tr = Trainer(cfg, tc, lm_batches(8, 32, cfg.vocab, seed=data_seed))
    tr.train()
    print(f"[{user}] fine-tuned on private data: "
          f"loss {tr.losses[0]:.3f} -> {tr.losses[-1]:.3f}")


def main():
    cfg = get_config("gemma-2b").reduced()
    ckpts = {u: f"/tmp/pocketllm_personalized_{u}" for u in USERS}
    for user, seed in USERS.items():
        finetune(cfg, user, seed, ckpts[user])

    # fresh "serving process": shared base weights + per-user scalar logs
    base = build_model(cfg).init(jax.random.PRNGKey(0))   # Trainer's seed=0
    store = AdapterStore(base, MZ)
    for user in USERS:
        ad = store.import_checkpoint(user, ckpts[user])
        print(f"[{user}] adapter: {ad.n_steps} steps, {ad.nbytes} B "
              f"(base tree: {tree_bytes(base)} B)")
    deltas = {u: np.max(np.abs(np.asarray(jax.tree.leaves(
        store.materialize(u))[0], np.float32)
        - np.asarray(jax.tree.leaves(base)[0], np.float32)))
        for u in USERS}
    assert all(d > 0 for d in deltas.values()), deltas   # really fine-tuned

    engine = ServeEngine(cfg, store, n_slots=2, max_len=32, seed=0)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (6, 8), dtype=np.int32)
    users = [u for _, u in zip(range(6), 3 * list(USERS))]
    rids = {engine.submit(Request(prompt=prompts[i], max_new=6, user=u)): u
            for i, u in enumerate(users)}
    completions = engine.run()          # 6 requests through 2 slots:
    served = {}                         # admission happens mid-flight
    for c in completions:
        assert c.tokens.shape == (6,) and rids[c.rid] == c.user
        served.setdefault(c.user, []).append(c.rid)
        print(f"[serve] rid={c.rid} user={c.user}: {c.tokens.tolist()}")
    assert set(served) == set(USERS), served
    st = engine.stats
    print(f"[serve] interleaved {len(completions)} requests from "
          f"{len(served)} adapters in one engine | prefill "
          f"{st.prefill_tps:.0f} tok/s | decode {st.decode_tps:.0f} tok/s | "
          f"adapter cache: {store.stats['misses']} materializations, "
          f"{store.stats['hits']} hits")
    print("OK: fine-tune x2 -> export ZO adapters -> serve interleaved")


if __name__ == "__main__":
    main()

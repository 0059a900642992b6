"""Serving launcher: thin CLI over the personalized serving subsystem.

The engine lives in :mod:`repro.serve` (AdapterStore + fused prefill +
continuous-batching decode); this module keeps (a) ``serve()``, the
reference per-token generation loop the parity tests pin the engine
against, and (b) a CLI that builds an engine, loads per-user ZO adapters
from replay logs, and serves a synthetic request mix:

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
      --requests 4 --prompt-len 16 --gen 8 \
      --adapter alice=/tmp/ckpt_alice --adapter bob=/tmp/ckpt_bob
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.configs import ALL_ARCHS, get_config
from repro.core import MezoConfig
from repro.launch import compile_cache
from repro.models import build_model
from repro.serve import (AdapterStore, Request, ServeEngine, sample_topk,
                         step_keys)


def serve(cfg, params, prompts: np.ndarray, gen: int, greedy: bool = True,
          topk: int = 8, seed: int = 0):
    """Reference per-token loop: prefill token-by-token through the
    decode cell, then decode. Kept as the parity oracle for the fused
    prefill path (tests/test_serve.py) and as the simplest possible
    serving implementation.

    prompts: (B, P) int32. Returns (B, gen) generated tokens. Sampling
    is seeded: one key split per step, folded per slot -- runs with
    different ``seed`` values draw independent streams.
    """
    model = build_model(cfg)
    bsz, plen = prompts.shape
    cache = model.init_cache(bsz, plen + gen)
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    key = jax.random.PRNGKey(seed)

    toks = jnp.asarray(prompts)
    out = []
    last = None
    for t in range(plen + gen - 1):
        if t < plen:
            cur = toks[:, t:t + 1]
        else:
            cur = last
            out.append(np.asarray(cur))
        logits, cache = step(params, cache, cur, jnp.int32(t))
        if greedy:
            last = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        else:
            key, slot_keys = step_keys(key, bsz)
            last = sample_topk(slot_keys, logits[:, -1, :], topk)[:, None]
    out.append(np.asarray(last))
    return np.concatenate(out, axis=1)[:, :gen]


# one representative arch per decode-capable family -- the smoke path for
# "does family X serve end-to-end?" (--family encdec exercises the
# enc-dec fused prefill the block-registry runtime added)
FAMILY_ARCHS = {
    "dense": "gemma-2b",
    "moe": "granite-moe-1b-a400m",
    "hybrid": "jamba-v0.1-52b",
    "ssm": "rwkv6-7b",
    "encdec": "whisper-base",
}


def main(argv=None):
    """Run the CLI on ``argv`` (default ``sys.argv[1:]``); returns the
    engine and its rid-sorted completions."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=ALL_ARCHS)
    ap.add_argument("--family", default=None, choices=sorted(FAMILY_ARCHS),
                    help="serve this family's representative arch "
                         "(overrides --arch): " + ", ".join(
                             f"{f}={a}" for f, a in FAMILY_ARCHS.items()))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None,
                    help="load BASE params from this checkpoint dir")
    ap.add_argument("--adapter", action="append", default=[],
                    metavar="USER=CKPT_DIR",
                    help="register USER's replay log as a ZO adapter "
                         "(repeatable); requests round-robin over users")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--sample", action="store_true",
                    help="seeded top-k sampling instead of greedy")
    ap.add_argument("--topk", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dist", default="rademacher",
                    choices=("rademacher", "gaussian"),
                    help="perturbation dist the adapters were trained with")
    ap.add_argument("--weight-decay", type=float, default=0.0,
                    help="weight decay the adapters were trained with "
                         "(replay must apply the same decay coefficient)")
    ap.add_argument("--cache-mb", type=float, default=None,
                    help="adapter-store byte budget for materialized trees")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: attention K/V in a shared page "
                         "pool with per-slot page tables (decode reads "
                         "only live pages via the flash-decoding kernel)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="total pool pages incl. the trash page "
                         "(default: slots x ceil(max_len/page_size) + 1, "
                         "i.e. dense capacity)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="self-speculative decoding (needs --paged): the "
                         "frozen base drafts up to K tokens per round into "
                         "the slot's shared KV pages, base+delta verifies "
                         "them in one batched window call; greedy output "
                         "is bit-identical to plain decoding")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    metavar="N",
                    help="chunked prefill (needs --paged): admissions "
                         "advance at most N prompt tokens per engine step, "
                         "written straight into the slot's reserved KV "
                         "pages, while decoding slots keep stepping -- no "
                         "whole-prompt admission stall; greedy output is "
                         "bit-identical to whole-prompt prefill and "
                         "composes with --spec-k")
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.family:
        args.arch = FAMILY_ARCHS[args.family]
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        step = store.latest_step(args.ckpt_dir)
        if step is not None:
            params = store.load_params(args.ckpt_dir, step, params)
            print(f"[serve] loaded base checkpoint step {step}")

    adapters = AdapterStore(
        params, MezoConfig(dist=args.dist, weight_decay=args.weight_decay),
        cache_bytes=(int(args.cache_mb * 2**20) if args.cache_mb else None))
    users = []
    for spec in args.adapter:
        user, _, ckpt = spec.partition("=")
        if not ckpt:
            raise SystemExit(f"--adapter wants USER=CKPT_DIR, got {spec!r}")
        ad = adapters.import_checkpoint(user, ckpt)
        users.append(user)
        print(f"[serve] adapter {user!r}: {ad.n_steps} steps, "
              f"{ad.nbytes} bytes")
    if not users:
        users = [None]                     # base weights only

    engine = ServeEngine(cfg, adapters, n_slots=args.slots,
                         max_len=args.prompt_len + args.gen,
                         seed=args.seed, paged=args.paged,
                         page_size=args.page_size,
                         pool_pages=args.pool_pages,
                         spec_k=args.spec_k,
                         prefill_chunk=args.prefill_chunk)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab, (args.requests, args.prompt_len),
                           dtype=np.int32)
    for i in range(args.requests):
        engine.submit(Request(prompt=prompts[i], max_new=args.gen,
                              user=users[i % len(users)],
                              greedy=not args.sample, topk=args.topk,
                              temperature=args.temperature))
    t0 = time.perf_counter()
    completions = engine.run()
    dt = time.perf_counter() - t0
    for c in completions:
        tag = c.user if c.user is not None else "base"
        print(f"[serve] rid={c.rid} user={tag}: {c.tokens.tolist()}")
    st = engine.stats
    paged_note = (f" | paged: {engine.pool_pages} pages x "
                  f"{engine.page_size} tok, peak in use "
                  f"{st.peak_pages_in_use}" if engine.paged else "")
    if engine.spec_k:
        paged_note += (f" | spec k={engine.spec_k}: accepted "
                       f"{st.spec_accepted}/{st.spec_drafted} drafts "
                       f"({st.spec_accept_rate:.0%}) in "
                       f"{st.decode_steps} rounds")
    if engine.prefill_chunk:
        paged_note += f" | chunked prefill C={engine.prefill_chunk}"
    n_done = max(len(completions), 1)
    gaps = [b - a for c in completions
            for a, b in zip(c.token_ts, c.token_ts[1:])]
    lat_note = (f" | ttft avg {st.ttft_s / n_done * 1e3:.0f}ms "
                f"(queue {st.queue_wait_s / n_done * 1e3:.0f}ms), token "
                f"gap avg {sum(gaps) / max(len(gaps), 1) * 1e3:.1f}ms | "
                f"decode stall {st.decode_stall_s:.2f} slot-s")
    print(f"[serve] {args.requests} reqs x ({args.prompt_len} prompt + "
          f"{args.gen} gen) in {dt:.2f}s | prefill {st.prefill_tps:.0f} "
          f"tok/s | decode {st.decode_tps:.0f} tok/s | "
          f"adapter materializations: {adapters.stats['misses']} "
          f"(hits {adapters.stats['hits']})" + lat_note + paged_note)
    return engine, completions


if __name__ == "__main__":
    main()

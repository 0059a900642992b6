"""Serve cells: personalized serving through ``serve.ServeEngine`` (paged
KV, chunked prefill, speculative decode) under open-loop arrivals.

Set-up makes the base weights from the seed, registers each user's
adapter as replay-log records and materializes it through the engine's
``AdapterStore`` (replay), builds the engine, and warms every program
shape the window's requests will use: draft and verify at each live-page
bucket, the commit, and each (chunk, live-page) pair of chunked prefill
that the window's prompt lengths produce. The warm calls point every
page-table row at the trash page, so they leave the pool as it was.

The window submits each request when it falls due, with ``submit_ts``
set to its due time, and drives ``ServeEngine.step``; after each step
the benchmark notes, on its own clock, how many tokens each request has.
Time to first token runs from a request's due time; a request with no
first token when the window closes counts the time it has waited.

After the window the engine is freed and the reference
(``bench/reference``) replays the sampled requests' adapters from the
same records and scores prompt + served tokens in one float32 forward.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Any, Dict, List

import numpy as np

from bench.harness import traffic as T
from bench.harness.check import Check
from bench.harness.weights import make_params


def model_config(config: Dict[str, Any]):
    from repro.models.config import ModelConfig
    return ModelConfig(**config["model"])


def pow2_bucket(n: int, cap: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def chunk_plan(plen: int, chunk: int) -> List[int]:
    """The chunk sizes chunked prefill runs a prompt in: ``chunk`` while
    it fits, then the tail in powers of two."""
    out, off = [], 0
    while off < plen:
        c = min(plen - off, chunk)
        if c < chunk:
            c = 1 << (c.bit_length() - 1)
        out.append(c)
        off += c
    return out


def warm(engine, schedule, users) -> int:
    """Call each program the window will use once, at each shape it
    will see; returns the number of shapes."""
    import jax
    import jax.numpy as jnp

    fns, n, k = engine._fns, engine.n_slots, engine.spec_k
    sp = engine.slot_pages
    i32 = lambda *s: jnp.zeros(s, jnp.int32)                # noqa: E731
    buckets = sorted({pow2_bucket(p, sp) for p in range(1, sp + 1)})
    base = engine.store.materialize(None)
    tuned = engine.store.materialize(users[0])
    count = 0
    for nl in buckets:
        pages = i32(n, nl)
        _, engine.cache = fns["draft_spec"](base, engine.cache, i32(n),
                                            i32(n), pages, i32(n), k)
        lg, vstate = fns["verify_spec"](tuned, engine.cache, i32(n, k + 1),
                                        i32(n), pages,
                                        jnp.zeros((n, k + 1), bool))
        np.asarray(lg, np.float32)
        engine.cache = fns["commit_spec"](engine.cache, vstate, i32(n),
                                          jnp.zeros((n,), bool))
        count += 2
    count += 1
    pairs = set()
    for a in schedule:
        nl = pow2_bucket(-(-a.prompt.size // engine.page_size), sp)
        for c in chunk_plan(a.prompt.size, engine.prefill_chunk):
            pairs.add((c, nl))
    for c, nl in sorted(pairs):
        lg, engine.cache = fns["prefill_chunk"](
            tuned, engine.cache, i32(1, c), i32(1), i32(1, nl),
            jnp.int32(0))
        np.asarray(lg[:, -1, :], np.float32)
        count += 1
    # the eager ops the engine's host code runs between dispatches
    jnp.asarray([0], np.int32)
    key, sub = jax.random.split(engine.key)
    jax.random.fold_in(sub, 0)
    jax.block_until_ready(engine.cache)
    return count


class Tracker:
    """Counts, from outside the engine, the attention work of each
    paged-attention dispatch (traced runs only), and puts a host span
    around each dispatch."""

    def __init__(self, ctx, engine):
        self.ctx, self.engine = ctx, engine
        self.q_keys = 0.0          # sum over query rows of keys seen
        self.slot_keys = 0.0       # sum over (call, slot) of keys read
        self.calls = {"draft": 0, "verify": 0, "commit": 0, "prefill": 0}
        fns = engine._fns
        self._orig = dict(fns)
        fns["draft_spec"] = self._wrap("draft", self._draft)
        fns["verify_spec"] = self._wrap("verify", self._verify)
        fns["commit_spec"] = self._wrap("commit", None)
        fns["prefill_chunk"] = self._wrap("prefill", self._prefill)

    def restore(self):
        self.engine._fns.update(self._orig)

    def _wrap(self, name, count):
        orig = self._orig[{"draft": "draft_spec", "verify": "verify_spec",
                           "commit": "commit_spec",
                           "prefill": "prefill_chunk"}[name]]

        def call(*args):
            if count is not None:
                count(*args)
            self.calls[name] += 1
            with self.ctx.span(f"serve.{name}"):
                return orig(*args)
        return call

    def _add(self, starts: np.ndarray, widths: np.ndarray) -> None:
        """Slots whose queries sit at positions start .. start+width-1."""
        for s, w in zip(starts.tolist(), widths.tolist()):
            if w > 0:
                self.q_keys += w * s + w * (w + 1) / 2
                self.slot_keys += s + w

    def _draft(self, params, cache, last, pos, pages, draft_len, k):
        pos, d = np.asarray(pos), np.asarray(draft_len)
        for i in range(k):                   # k chained decode steps
            self._add(pos + i, (d > i).astype(np.int64))

    def _verify(self, params, cache, toks, pos, pages, wmask):
        self._add(np.asarray(pos), np.asarray(wmask).sum(axis=1))

    def _prefill(self, params, cache, toks, pos, pages, slot):
        self._add(np.asarray(pos), np.array([toks.shape[1]]))


def run(ctx) -> Dict[str, Any]:
    import jax

    from repro.core.engine import MezoConfig
    from repro.models import build_model
    from repro.serve.adapters import AdapterStore
    from repro.serve.engine import Request, ServeEngine

    cfg = model_config(ctx.config)
    tr = ctx.cell["traffic"]
    eng = tr["engine"]
    users = [u["name"] for u in tr["users"]]
    key32 = T.seed32(ctx.seed, 0)
    records = {u: T.adapter_records(tr["adapter"], ctx.seed, i)
               for i, u in enumerate(users)}

    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    store = AdapterStore(make_params(shapes, key32),
                         mezo_cfg=MezoConfig(lr=tr["adapter"]["lr"],
                                             eps=tr["adapter"]["eps"]))
    for u in users:
        store.put(u, records[u])
    engine = ServeEngine(cfg, store, n_slots=eng["slots"],
                         max_len=eng["max_len"], seed=T.seed32(ctx.seed, 4),
                         paged=True, page_size=eng["page_size"],
                         pool_pages=eng["pool_pages"], spec_k=eng["spec_k"],
                         prefill_chunk=eng["prefill_chunk"])
    for u in users:
        store.materialize(u)
    replay_s = store.stats["materialize_s"]
    schedule = T.arrivals(tr, cfg.vocab, ctx.seed, ctx.seconds,
                          rate=ctx.rate)
    n_shapes = warm(engine, schedule, users)
    setup_s = ctx.elapsed()
    ctx.log(f"set-up {setup_s!r}s (adapter replay {replay_s!r}s, "
            f"{n_shapes} program shapes warmed); {len(schedule)} requests "
            f"offered at {ctx.rate or tr['rate_rps']} req/s")

    tracker = Tracker(ctx, engine) if ctx.trace else None
    stats0 = (engine.stats.spec_drafted, engine.stats.spec_accepted)
    c0 = ctx.clock.snapshot()
    tracer = None
    if ctx.trace:
        from bench.harness.context import profile_options
        tracer = jax.profiler.trace(ctx.work("trace"),
                                    profiler_options=profile_options())
        tracer.__enter__()

    due: Dict[int, float] = {}
    arrival: Dict[int, Any] = {}
    first: Dict[int, float] = {}
    last: Dict[int, float] = {}
    ntok: Dict[int, int] = {}
    done: Dict[int, Any] = {}
    late: List[float] = []
    failed = 0
    step_s = 0.0
    nxt = 0
    ctx.log("window opens")
    with ctx.span("serve.window"):
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= ctx.seconds:
                break
            while nxt < len(schedule) and schedule[nxt].due_s <= now:
                a = schedule[nxt]
                nxt += 1
                with ctx.span("serve.submit"):
                    try:
                        rid = engine.submit(Request(
                            prompt=a.prompt, max_new=a.max_new, user=a.user,
                            greedy=True, submit_ts=t0 + a.due_s))
                    except ValueError:
                        failed += 1
                        continue
                due[rid], arrival[rid] = a.due_s, a
                late.append(now - a.due_s)
            busy = (engine.queue or engine._active.any()
                    or engine._prefill_slot is not None)
            if not busy:
                wait = (schedule[nxt].due_s if nxt < len(schedule)
                        else ctx.seconds) - now
                with ctx.span("serve.idle_wait"):
                    time.sleep(max(0.0, min(wait, ctx.seconds - now)))
                continue
            s0 = time.perf_counter()
            with ctx.span("serve.step"):
                engine.step()
            t = time.perf_counter()
            step_s += t - s0
            t -= t0
            counts = {}
            for c in engine.drain_finished():
                done[c.rid] = c
                counts[c.rid] = int(c.tokens.size)
            for slot in np.flatnonzero(engine._active):
                counts[engine._req[slot].rid] = len(engine._out[slot])
            for rid, n in counts.items():
                if n > ntok.get(rid, 0):
                    first.setdefault(rid, t)
                    last[rid] = t
                    ntok[rid] = n
        t_end = time.perf_counter() - t0
    ctx.log("window closed")
    if tracer is not None:
        tracer.__exit__(None, None, None)
        tracker.restore()
    c1 = ctx.clock.snapshot()
    backlog = len(engine.queue) + int(engine._active.sum()) + (
        engine._prefill_slot is not None)
    drafted = engine.stats.spec_drafted - stats0[0]
    accepted = engine.stats.spec_accepted - stats0[1]
    prefill_done = {rid: arrival[rid].prompt.size for rid in first}
    if engine._prefill_slot is not None:
        r = engine._req[engine._prefill_slot]
        prefill_done[r.rid] = engine._prefill_off

    ttft = [(first[rid] if rid in first else t_end) - d
            for rid, d in due.items()]
    itl = [(last[rid] - first[rid]) / (ntok[rid] - 1)
           for rid in first if ntok[rid] >= 2]

    from bench.harness.device import peak_bytes
    peak = peak_bytes(ctx.devs)
    finished = [done[r] for r in sorted(done)]
    del engine, store
    gc.collect()

    check = Check(ctx.cell["check"]["limits"])
    sample = check_sample(finished, ctx.cell["check"]["requests"], ctx.seed)
    gap = reference_gap(cfg, shapes, key32, records, sample,
                        eng["max_len"])
    check.add("logit_gap", gap)
    ctx.log(f"served {len(finished)} of {len(due)} requests, {backlog} "
            f"queued or in flight at the close; checked "
            f"{len(sample)} ({sum(c.tokens.size for c in sample)} tokens)")

    q90 = lambda xs: float(np.percentile(xs, 90)) if xs else math.nan  # noqa
    record = {
        "e2e": {"setup_s": setup_s,
                "ttft_p90_ms": 1e3 * q90(ttft),
                "itl_p90_ms": 1e3 * q90(itl),
                "peak_hbm_gib": peak / 2 ** 30},
        "peak_bytes": peak,
        "attempted": len(due) + failed,
        "failed": failed,
        "check": check,
        "window_s": t_end,
        "compiles_in_window": [c1[0] - c0[0], c1[1] - c0[1]],
        "serve": {
            "requests": len(due), "finished": len(finished),
            "backlog": backlog,
            "queue_wait_s": [c.queue_wait_s for c in finished],
            "spec_drafted": drafted, "spec_accepted": accepted,
            "step_s": step_s,
            "prefill_tokens": prefill_done,
            "prompt_len": {rid: int(arrival[rid].prompt.size)
                           for rid in first},
            "generated": dict(ntok),
            "late_p90_ms": 1e3 * q90(late),
            "attention": (None if tracker is None else
                          {"q_keys": tracker.q_keys,
                           "slot_keys": tracker.slot_keys,
                           "calls": tracker.calls}),
        },
        "trace_dir": ctx.work("trace") if ctx.trace else None,
        "sample": sample,
    }
    ctx.log(f"ttft_p90_ms {record['e2e']['ttft_p90_ms']!r} itl_p90_ms "
            f"{record['e2e']['itl_p90_ms']!r}; generator late p90 "
            f"{record['serve']['late_p90_ms']!r} ms; spec accepted "
            f"{accepted}/{drafted}; compiles/traces in window "
            f"{record['compiles_in_window']}")
    return record


def control(ctx, record) -> Dict[str, float]:
    """The control of the output check: at each position of the same
    sampled prompts and served tokens, the gap of the token that the
    reference with matrix products one precision below the model's
    (float8 for bf16) puts first."""
    import jax

    from bench.reference import forward
    from repro.models import build_model

    cfg = model_config(ctx.config)
    tr = ctx.cell["traffic"]
    records = {u["name"]: T.adapter_records(tr["adapter"], ctx.seed, i)
               for i, u in enumerate(tr["users"])}
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    return {"logit_gap": reference_gap(
        cfg, shapes, T.seed32(ctx.seed, 0), records, record["sample"],
        tr["engine"]["max_len"], mm=forward.control_mm(cfg.dtype))}


def check_sample(finished, n: int, seed: int):
    """``n`` finished requests drawn from the seed, the longest among
    them."""
    if not finished:
        return []
    longest = max(finished, key=lambda c: c.prompt.size + c.tokens.size)
    rest = [c for c in finished if c is not longest]
    g = T.rng(seed, 5)
    pick = g.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gap(cfg, shapes, key32, records, sample, max_len: int,
                  mm=None) -> float:
    """Widest gap by which a served token's logit lies below the
    reference's best at its position, over every sampled request.

    With ``mm`` (the control's matrix product), the served tokens are
    replaced by what that precision puts first at each position."""
    import jax
    import jax.numpy as jnp

    from bench.reference import forward, zo

    if not sample:
        return math.inf

    @jax.jit
    def logits_f32(params, toks):
        return forward.lm_logits(params, toks, n_heads=cfg.n_heads)[0]

    logits_ctl = None
    if mm is not None:
        @jax.jit
        def logits_ctl(params, toks):
            return forward.lm_logits(params, toks, n_heads=cfg.n_heads,
                                     mm=mm)[0]

    worst = 0.0
    by_user: Dict[str, list] = {}
    for c in sample:
        by_user.setdefault(c.user, []).append(c)
    for user, reqs in by_user.items():
        params = zo.replay(make_params(shapes, key32), records[user])
        for c in reqs:
            plen, n = c.prompt.size, c.tokens.size
            seq = np.zeros((1, max_len), np.int32)
            seq[0, :plen] = c.prompt
            seq[0, plen:plen + n - 1] = c.tokens[:-1]
            lg = logits_f32(params, jnp.asarray(seq))[plen - 1:plen - 1 + n]
            if logits_ctl is None:
                served = jnp.asarray(c.tokens)
            else:
                served = jnp.argmax(logits_ctl(params, jnp.asarray(seq))
                                    [plen - 1:plen - 1 + n], axis=-1)
            gold = jnp.take_along_axis(lg, served[:, None], axis=1)[:, 0]
            worst = max(worst, float(jnp.max(jnp.max(lg, axis=1) - gold)))
        del params
    return worst

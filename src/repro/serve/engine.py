"""Continuous-batching decode engine over per-user ZO adapters.

A fixed table of ``n_slots`` sequence slots shares one batched decode
cache. Requests queue up; whenever a slot is free the next request is
admitted *mid-flight*: its adapter is materialized through the
:class:`~repro.serve.adapters.AdapterStore`, its prompt is prefilled in
one fused call (``model.prefill`` -- wired for every decode-capable
family, enc-dec included; a per-token fallback remains as a safety net
for models built without one), and the cache rows are scattered into
the slot.
Finished sequences free their slot on the spot -- the engine never
drains the whole batch to admit new work.

Every decode step advances ALL active slots one token, each at its own
position (``decode_step`` takes a per-slot ``pos`` vector). Slots served
by different adapters are handled with one decode dispatch per distinct
active adapter, masked-merged into the shared cache -- compute cost per
step scales with the number of *distinct* adapters in flight, the
classic multi-model batching tradeoff (cf. S-LoRA-style adapter
batching), except here an "adapter" is a replayed scalar log, not extra
weights in the batch.

Paged KV (``paged=True``): instead of every slot pre-allocating a dense
(max_len, KV, hd) strip per layer, attention K/V lives in a shared pool
of fixed-size pages with a per-slot page table. Pages are *reserved* at
admission (the request's worst-case ``ceil((plen+max_new)/page_size)``,
so mid-flight growth can never dead-lock) but only *allocated* as the
sequence actually reaches them, and freed the moment the slot finishes.
Slot count is then bounded by tokens resident, not ``slots x max_len``:
a pool sized for 4 dense max-len slots holds every short request that
fits, concurrently. Decode reads only live pages -- the flash-decoding
kernel (TPU) / gather reference skips each slot's dead tail -- with the
live page count bucketed to powers of two so the step stays a handful
of compiled shapes. Physical page 0 is the trash page: freed slots'
table rows and masked-out adapter lanes scatter there, which keeps the
multi-adapter merge a leaf-name split (pool leaves: take new; dense
recurrent leaves: masked lane select) instead of a page-level scatter.

Families without pageable state (rwkv6: O(1) recurrent state per slot)
run ``paged=True`` as the dense layout -- same admission, same tokens.

Chunked prefill (``prefill_chunk=C``, paged mode only): whole-prompt
admission is an *admission stall* -- every resident decode slot freezes
for the full prompt's prefill, and the prompt transits a throwaway
dense B=1 cache that is then scattered page-by-page into the pool
(``install_paged``). Chunked mode instead runs at most one admission at
a time and advances it at most ``C`` prompt tokens per engine step,
each chunk written *straight into the slot's reserved pages* by
``model.prefill_chunk`` (flash-prefill kernel on TPU) -- no dense
intermediate, no install scatter -- while every decoding slot still
advances one token per step (Sarathi-style mixed batching). The
admission reservation already covers every chunk's pages, so chunking
cannot deadlock. Tail chunks decompose into powers of two (a 13-token
tail runs as 8+4+1) so the chunk dispatch stays a handful of compiled
shapes without padding -- padded tokens would corrupt recurrent
(mamba/rwkv) state, which advances dense through the chunk at the
slot's lane. While a chunked prefill is in flight, decode always takes
the masked dispatch: the prefilling slot's page-table row points at
real pages and its recurrent lane is mid-advance, so an unmasked
all-slots decode would write garbage through both. Greedy output is
bit-identical to whole-prompt admission; the per-admission key split
happens once in both modes.

The engine is family-agnostic: the block-registry runtime's unified
StateCache puts every dense leaf at (n_layers, B, ...) -- batch on axis
1 for every family -- so slot scatter/merge is one ``jax.tree.map``,
with no per-family axis table. Jitted serving entry points are cached
per Model (see ``_serving_fns``): constructing an engine re-uses the
compiled decode/prefill/install executables instead of re-tracing them,
which -- together with keeping the sampler's key-split off the
greedy-only hot path -- is where the pre-paging decode baseline lost
most of its step budget.

Speculative decoding (``spec_k``, paged mode only): the engine's own
frozen base weights (``store.materialize(None)`` -- the int8 base when
quantized) act as the draft model, so speculation adds ZERO extra weight
bytes. Each round the base drafts up to ``k`` tokens greedily, writing
its K/V into the slot's already-reserved pages; one batched
``verify_window`` call then scores all k+1 window positions with the
target (base+delta) model, *overwriting* the window's K/V with the
target's own -- so the pool afterwards holds exactly what a sequential
target decode would have cached and verification is exact. The longest
draft prefix matching the target's greedy choices is accepted plus the
target's correction/bonus token; greedy output is bit-identical to the
non-speculative engine. Rejected positions need no data rollback: reads
mask ``k_pos <= pos`` and the next round overwrites stale entries before
they are read. Recurrent leaves (hybrid families) cannot be overwritten
in place, so verify stacks one state snapshot per window offset and the
commit selects each slot's accepted offset -- the recurrent analogue of
the page-table rollback. Sampled slots use speculative rejection
sampling against the greedy draft (accept token x w.p. p(x); resample
from the residual on rejection), which preserves the target's top-k
sampling distribution. The draft's worst-case write position ``pos +
k`` never outgrows the admission reservation because the per-slot draft
length is capped at ``remaining``. MoE verify windows share expert
capacity across window offsets, so spec parity is only pinned for dense
and hybrid families.

MoE caveat: expert capacity is contended across the whole slot batch, so
a slot's logits can depend on what its neighbors decode -- inherent to
capacity-bounded MoE serving, not to this engine.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.batching import masked_merge
from repro.models import build_model
from repro.serve import sampling
from repro.serve.adapters import AdapterStore

PyTree = Any


@dataclasses.dataclass
class Request:
    """One generation request, tagged with the adapter that serves it."""
    prompt: np.ndarray            # (P,) int32 token ids
    max_new: int
    user: Optional[str] = None    # adapter id; None -> base weights
    greedy: bool = True
    topk: int = 0                 # used when greedy=False
    temperature: float = 1.0
    rid: int = -1                 # assigned by submit()
    submit_ts: Optional[float] = None     # stamped by submit()


@dataclasses.dataclass
class Completion:
    rid: int
    user: Optional[str]
    prompt: np.ndarray
    tokens: np.ndarray            # (n_generated,) int32
    accept_rate: Optional[float] = None   # draft acceptance (spec mode)
    queue_wait_s: float = 0.0     # submit -> admission start
    ttft_s: float = 0.0           # submit -> first token picked
    # host perf_counter time at which each token was committed:
    # ttft_s is token_ts[0] - submit_ts, the gaps between tokens follow
    token_ts: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class EngineStats:
    prefill_tokens: int = 0
    prefill_s: float = 0.0
    decode_tokens: int = 0
    decode_s: float = 0.0
    decode_steps: int = 0
    admitted: int = 0
    finished: int = 0
    peak_active_slots: int = 0
    peak_pages_in_use: int = 0    # paged mode only (excludes trash page)
    spec_drafted: int = 0         # draft tokens proposed (spec mode)
    spec_accepted: int = 0        # draft tokens accepted and committed
    # slot-seconds active decode slots sat idle while admission prefill
    # work ran. Whole-prompt admission accrues the full prompt's prefill
    # per resident decoder in one burst; chunked admission accrues one
    # chunk at a time, so nearly-finished slots drain instead of
    # freezing behind a long prompt.
    decode_stall_s: float = 0.0
    queue_wait_s: float = 0.0     # summed over admissions
    ttft_s: float = 0.0           # summed over admissions

    @staticmethod
    def _rate(num: float, den: float) -> float:
        return num / den if den > 0 else 0.0

    @property
    def prefill_tps(self) -> float:
        return self._rate(self.prefill_tokens, self.prefill_s)

    @property
    def decode_tps(self) -> float:
        return self._rate(self.decode_tokens, self.decode_s)

    @property
    def spec_accept_rate(self) -> float:
        return self._rate(self.spec_accepted, self.spec_drafted)


def _merge_paged(cache, new, mask):
    """Multi-adapter merge for a paged cache: pool leaves were written
    through the page table (masked lanes scattered into the trash page),
    so the new pool is already correct for every slot; dense (L, B, ...)
    leaves lane-select like the unpaged engine."""
    mask = jnp.asarray(mask, bool)

    def pick(path, o, n):
        if str(getattr(path[-1], "key", path[-1])).endswith("_pages"):
            return n
        return jnp.where(jnp.reshape(mask, (1, -1) + (1,) * (o.ndim - 2)),
                         n, o)

    return jax.tree_util.tree_map_with_path(pick, cache, new)


# per-Model jitted serving entry points. build_model memoizes Model on
# the config, so every engine over the same config shares ONE set of
# compiled executables -- engine construction costs no re-trace.
_SERVING_FNS: Dict[int, Dict[str, Any]] = {}


def _serving_fns(model) -> Dict[str, Any]:
    fns = _SERVING_FNS.get(id(model))
    if fns is not None:
        return fns
    decode_step = model.decode_step

    # the slot-table cache is donated on every hot-path call: decode
    # updates it in place instead of copying the full (n_slots,
    # max_len) KV per token (the reference serve() loop donates too)
    @partial(jax.jit, donate_argnums=(1,))
    def decode_all(params, cache, toks, pos):
        return decode_step(params, cache, toks, pos)

    @partial(jax.jit, donate_argnums=(1,))
    def decode_masked(params, cache, toks, pos, mask):
        logits, new = decode_step(params, cache, toks, pos)
        # every StateCache leaf batches on axis 1 (same ragged-slot
        # helper the TrainEngine uses on its axis-0 user stack)
        return logits, masked_merge(cache, new, mask, axis=1)

    @partial(jax.jit, donate_argnums=(1,))
    def decode_all_paged(params, cache, toks, pos, pages):
        return decode_step(params, cache, toks, pos, pages=pages)

    @partial(jax.jit, donate_argnums=(1,))
    def decode_masked_paged(params, cache, toks, pos, pages, mask):
        logits, new = decode_step(params, cache, toks, pos, pages=pages,
                                  write_mask=mask)
        return logits, _merge_paged(cache, new, mask)

    @partial(jax.jit, donate_argnums=(0,))
    def install(cache, prefill_cache, slot):
        """Scatter a B=1 prefilled cache into slot row ``slot``. Rows
        may be shorter than the slot cache along trailing axes (the
        admission buckets ``fresh_len`` to a power of two): the update
        writes the row-sized prefix and the dead tail past ``pos`` is
        never read."""

        def put(c, row):
            return jax.lax.dynamic_update_slice(
                c, row.astype(c.dtype), (0, slot) + (0,) * (c.ndim - 2))

        return jax.tree.map(put, cache, prefill_cache)

    @partial(jax.jit, donate_argnums=(0,))
    def install_paged(cache, prefill_cache, phys, slot):
        """Scatter a B=1 prefilled *dense* cache into the paged slot:
        pool leaves (``X_pages``) page their dense twin ``X`` into the
        slot's physical pages; dense leaves install into row ``slot``."""
        fresh = {jax.tree_util.keystr(p): v for p, v in
                 jax.tree_util.tree_leaves_with_path(prefill_cache)}
        npg = phys.shape[0]

        def put(path, c):
            ks = jax.tree_util.keystr(path)
            name = str(getattr(path[-1], "key", path[-1]))
            if name.endswith("_pages"):
                row = fresh[ks.replace(name, name[:-len("_pages")])]
                ps = c.shape[2]
                src = row[:, 0, :npg * ps].reshape(
                    (row.shape[0], npg, ps) + row.shape[3:])
                return c.at[:, phys].set(src.astype(c.dtype))
            return c.at[:, slot].set(
                jnp.take(fresh[ks], 0, axis=1).astype(c.dtype))

        return jax.tree_util.tree_map_with_path(put, cache)

    def _pool_or(path, old, new):
        """Leaf-name split shared by the speculative fns: pool leaves
        (written through the page table) take the new buffer, everything
        else keeps ``old``."""
        if str(getattr(path[-1], "key", path[-1])).endswith("_pages"):
            return new
        return old

    draft_spec = verify_spec = commit_spec = None
    verify_window = model.verify_window
    if verify_window is not None:
        @partial(jax.jit, static_argnums=(6,), donate_argnums=(1,))
        def draft_spec(params, cache, last, pos, pages, draft_len, k):
            """Greedy-draft ``k`` tokens per slot with the (base) params:
            k chained decode steps inside one dispatch. Slots draft only
            ``draft_len`` tokens (excess writes land in the trash page and
            the proposed token freezes). The draft's K/V goes into the
            shared pages -- verify overwrites it -- while its dense
            recurrent-state advance is discarded (the target's verify
            scan re-derives it exactly)."""
            def step(carry, i):
                toks, c = carry
                lg, c = decode_step(params, c, toks[:, None], pos + i,
                                    pages=pages, write_mask=i < draft_len)
                nxt = jnp.argmax(lg[:, -1, :], axis=-1).astype(toks.dtype)
                toks = jnp.where(i < draft_len, nxt, toks)
                return (toks, c), toks

            (_, newc), drafts = jax.lax.scan(
                step, (last, cache), jnp.arange(k, dtype=jnp.int32))
            return drafts, jax.tree_util.tree_map_with_path(
                _pool_or, cache, newc)

        @jax.jit
        def verify_spec(params, cache, toks, pos, pages, wmask):
            """Score the (B, W) window with the target params. NOT
            donated: the commit's lane-select needs the pre-verify dense
            leaves for masked-out slots."""
            return verify_window(params, cache, toks, pos, pages=pages,
                                 write_mask=wmask)

        @partial(jax.jit, donate_argnums=(0, 1))
        def commit_spec(cache, vcache, acc, mask):
            """Fold a verify result into the cache: pool leaves are
            already correct for every slot (masked writes went to
            trash); stacked recurrent leaves (L, W, B, ...) select each
            slot's accepted window offset ``acc``; read-only leaves
            (same ndim, never stacked) stay."""
            def pick(path, o, n):
                if str(getattr(path[-1], "key",
                               path[-1])).endswith("_pages"):
                    return n
                if n.ndim == o.ndim:
                    return o
                sel = n[:, acc, jnp.arange(o.shape[1])]
                m = jnp.reshape(mask, (1, -1) + (1,) * (o.ndim - 2))
                return jnp.where(m, sel, o)

            return jax.tree_util.tree_map_with_path(pick, cache, vcache)

    prefill_chunk = None
    chunk_entry = model.prefill_chunk
    if chunk_entry is not None:
        @partial(jax.jit, donate_argnums=(1,))
        def prefill_chunk(params, cache, toks, pos, pages, slot):
            """Advance slot ``slot`` by one B=1 prompt chunk, written
            straight into the shared page pool. Pool leaves pass through
            whole (the chunk scatters via the page table; other slots'
            pages are untouched); dense (L, B, ...) leaves -- recurrent
            state for hybrid families -- slice the slot's lane, advance
            at B=1 through the chunk, and scatter back. ``slot`` is
            traced, so one compile serves every slot per (C, n_live)
            bucket."""
            def take(path, c):
                if str(getattr(path[-1], "key", path[-1])).endswith("_pages"):
                    return c
                return jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1)

            sub = jax.tree_util.tree_map_with_path(take, cache)
            logits, new = chunk_entry(params, sub, toks, pos, pages=pages)

            def put(path, c, n):
                if str(getattr(path[-1], "key", path[-1])).endswith("_pages"):
                    return n
                return jax.lax.dynamic_update_slice_in_dim(
                    c, n.astype(c.dtype), slot, axis=1)

            return logits, jax.tree_util.tree_map_with_path(put, cache, new)

    fns = {
        "decode_all": decode_all,
        "decode_masked": decode_masked,
        "decode_all_paged": decode_all_paged,
        "decode_masked_paged": decode_masked_paged,
        "draft_spec": draft_spec,
        "verify_spec": verify_spec,
        "commit_spec": commit_spec,
        "install": install,
        "install_paged": install_paged,
        "prefill_chunk": prefill_chunk,
        "prefill": (jax.jit(model.prefill, donate_argnums=(1,))
                    if model.prefill is not None else None),
        "decode_one": jax.jit(decode_step,   # per-token prefill fallback
                              donate_argnums=(1,)),
    }
    _SERVING_FNS[id(model)] = fns
    return fns


class ServeEngine:
    def __init__(self, cfg, store: AdapterStore, n_slots: int = 4,
                 max_len: Optional[int] = None, seed: int = 0,
                 paged: bool = False, page_size: int = 16,
                 pool_pages: Optional[int] = None,
                 spec_k: Optional[int] = None,
                 prefill_chunk: Optional[int] = None):
        self.cfg = cfg
        self.model = build_model(cfg)
        if self.model.decode_step is None:
            raise ValueError(f"family {cfg.family!r} has no decode path")
        if spec_k is not None and spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if spec_k is not None and not paged:
            raise ValueError(
                "spec_k requires paged=True: the draft writes into (and "
                "the verifier overwrites) the slot's shared KV pages")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if prefill_chunk is not None and not paged:
            raise ValueError(
                "prefill_chunk requires paged=True: prompt chunks write "
                "straight into the slot's reserved KV pages")
        self.store = store
        self.n_slots = n_slots
        self.max_len = max_len or cfg.max_seq
        self.key = jax.random.PRNGKey(seed)
        self.stats = EngineStats()

        # families without pageable state serve the dense layout even
        # under paged=True (nothing to page; admission is identical)
        self.paged = bool(paged and self.model.init_paged_cache is not None)
        if spec_k is not None and not self.paged:
            raise ValueError(
                f"family {cfg.family!r} has no pageable state; speculative "
                f"decoding needs a paged KV cache to share between draft "
                f"and verifier")
        if prefill_chunk is not None and not self.paged:
            raise ValueError(
                f"family {cfg.family!r} has no pageable state; chunked "
                f"prefill needs a paged KV cache to write prompt chunks "
                f"into")
        self.spec_k = int(spec_k or 0)
        self.prefill_chunk = int(prefill_chunk or 0)
        self.page_size = page_size
        if self.paged:
            self.slot_pages = -(-self.max_len // page_size)  # per-slot max
            if pool_pages is None:       # default: dense capacity + trash
                pool_pages = n_slots * self.slot_pages + 1
            if pool_pages < 2:
                raise ValueError("pool_pages must be >= 2 (trash + 1)")
            self.pool_pages = pool_pages
            self.cache = self.model.init_paged_cache(
                n_slots, pool_pages, page_size, max_len=self.max_len)
            self._free_pages = list(range(pool_pages - 1, 0, -1))
            self._reserved = 0                     # pages promised, total
            self._slot_alloc: List[List[int]] = [[] for _ in range(n_slots)]
            self._slot_reserve = np.zeros(n_slots, np.int64)
            self._table = np.zeros((n_slots, self.slot_pages), np.int32)
        else:
            self.cache = self.model.init_cache(n_slots, self.max_len)

        self.queue: deque = deque()
        self._next_rid = 0
        self._req: List[Optional[Request]] = [None] * n_slots
        self._active = np.zeros(n_slots, bool)
        self._pos = np.zeros(n_slots, np.int32)
        self._remaining = np.zeros(n_slots, np.int32)
        self._last = np.zeros(n_slots, np.int32)
        self._out: List[List[int]] = [[] for _ in range(n_slots)]
        self._ts: List[List[float]] = [[] for _ in range(n_slots)]
        self._slot_drafted = np.zeros(n_slots, np.int64)
        self._slot_accepted = np.zeros(n_slots, np.int64)
        self._queue_wait = np.zeros(n_slots)
        self._ttft = np.zeros(n_slots)
        self._prefill_slot: Optional[int] = None   # chunked: slot mid-prefill
        self._prefill_off = 0                      # prompt tokens done so far
        self._finished: List[Completion] = []
        self._fns = _serving_fns(self.model)

    # ---- page pool -------------------------------------------------------
    def _pages_needed(self, tokens: int) -> int:
        return -(-tokens // self.page_size)

    def _alloc_prompt_pages(self, slot: int, plen: int) -> None:
        with obs.span("serve.pages"):
            for _ in range(self._pages_needed(plen)):
                self._alloc_page(slot)

    def _alloc_page(self, slot: int) -> None:
        page = self._free_pages.pop()
        lp = len(self._slot_alloc[slot])
        self._slot_alloc[slot].append(page)
        self._table[slot, lp] = page
        in_use = self.pool_pages - 1 - len(self._free_pages)
        self.stats.peak_pages_in_use = max(self.stats.peak_pages_in_use,
                                           in_use)

    def _release_slot_pages(self, slot: int) -> None:
        self._free_pages.extend(reversed(self._slot_alloc[slot]))
        self._reserved -= int(self._slot_reserve[slot])
        self._slot_reserve[slot] = 0
        self._slot_alloc[slot] = []
        self._table[slot] = 0                      # -> trash page

    # ---- request lifecycle ----------------------------------------------
    def submit(self, req: Request) -> int:
        plen = int(np.asarray(req.prompt).size)
        if plen + req.max_new > self.max_len:
            raise ValueError(f"prompt({plen}) + max_new({req.max_new}) "
                             f"exceeds max_len({self.max_len})")
        if req.max_new < 1:
            raise ValueError("max_new must be >= 1")
        if self.paged:
            need = self._pages_needed(plen + req.max_new)
            if need > self.pool_pages - 1:
                raise ValueError(
                    f"request needs {need} pages "
                    f"({plen}+{req.max_new} tokens @ page_size "
                    f"{self.page_size}); pool holds {self.pool_pages - 1}")
        req.rid = self._next_rid
        self._next_rid += 1
        if req.submit_ts is None:
            req.submit_ts = time.perf_counter()
        self.queue.append(req)
        return req.rid

    def _params(self, user: Optional[str]):
        """The adapter's weights (replayed on a store miss)."""
        with obs.span("serve.materialize", user=lambda: str(user)):
            return self.store.materialize(user)

    def _free_slots(self) -> List[int]:
        return [i for i in range(self.n_slots) if not self._active[i]]

    def _admit(self):
        """Prefill queued requests into free slots (mid-flight). Paged
        mode additionally requires the request's worst-case page count
        to fit in the unreserved pool -- admission is the only gate, so
        growth during decode can never fail. FIFO: a head request that
        does not fit blocks the queue until slots/pages free up.

        Whole-prompt admission blocks every resident decode slot for the
        full prefill (accrued in ``decode_stall_s``); ``prefill_chunk``
        mode delegates to :meth:`_admit_chunked`, which spreads the
        prompt over engine steps."""
        if self.prefill_chunk:
            return self._admit_chunked()
        for slot in self._free_slots():
            if not self.queue:
                return
            req = self.queue[0]
            plen = int(np.asarray(req.prompt).size)
            if self.paged:
                need = self._pages_needed(plen + req.max_new)
                if self._reserved + need > self.pool_pages - 1:
                    return                       # wait for pages to free
            self.queue.popleft()
            params = self._params(req.user)
            prompt = np.asarray(req.prompt, np.int32).reshape(1, -1)
            t0 = time.perf_counter()
            self._queue_wait[slot] = (
                t0 - req.submit_ts if req.submit_ts is not None else 0.0)
            if self.paged:
                self._reserved += need
                self._slot_reserve[slot] = need
                self._alloc_prompt_pages(slot, plen)
                fresh_len = self._pages_needed(plen) * self.page_size
            else:
                # bucket the throwaway prefill cache to the next power
                # of two >= plen instead of a full max_len strip: short
                # prompts stop paying max_len HBM and the prefill jit
                # compiles once per bucket (mirroring _live_pages)
                fresh_len = min(1 << max(plen - 1, 0).bit_length(),
                                self.max_len)
            with obs.span("serve.admit", prompt=plen, chunk=plen):
                fresh = self.model.init_cache(1, fresh_len)
                if self._fns["prefill"] is not None:
                    logits, fresh = self._fns["prefill"](
                        params, fresh, jnp.asarray(prompt))
                else:
                    toks = jnp.asarray(prompt)
                    for t in range(plen):
                        logits, fresh = self._fns["decode_one"](
                            params, fresh, toks[:, t:t + 1], jnp.int32(t))
                if self.paged:
                    phys = jnp.asarray(
                        np.asarray(self._slot_alloc[slot], np.int32))
                    self.cache = self._fns["install_paged"](
                        self.cache, fresh, phys, slot)
                else:
                    self.cache = self._fns["install"](self.cache, fresh,
                                                      slot)
                jax.block_until_ready(self.cache)
            elapsed = time.perf_counter() - t0
            self.stats.prefill_s += elapsed
            self.stats.decode_stall_s += elapsed * int(self._active.sum())
            self.stats.prefill_tokens += plen
            self.stats.admitted += 1
            self._activate(slot, req,
                           np.asarray(logits[:, -1, :], np.float32)[0], plen)

    def _admit_chunked(self):
        """Chunked admission: at most one prompt in flight, advanced at
        most ``prefill_chunk`` tokens per engine step straight into the
        slot's reserved pages -- no dense B=1 cache, no install scatter,
        and decoding slots keep stepping between chunks. All prompt
        pages are allocated up front (the reservation covers them), so
        every chunk's writes land in live pages. The tail decomposes
        into powers of two (no padding: padded tokens would corrupt the
        dense recurrent state advancing through the chunk)."""
        if self._prefill_slot is None:
            free = self._free_slots()
            if free and self.queue:
                req = self.queue[0]
                plen = int(np.asarray(req.prompt).size)
                need = self._pages_needed(plen + req.max_new)
                if self._reserved + need <= self.pool_pages - 1:
                    self.queue.popleft()
                    slot = free[0]
                    now = time.perf_counter()
                    self._queue_wait[slot] = (
                        now - req.submit_ts if req.submit_ts is not None
                        else 0.0)
                    self._reserved += need
                    self._slot_reserve[slot] = need
                    self._alloc_prompt_pages(slot, plen)
                    self._req[slot] = req
                    self._prefill_slot = slot
                    self._prefill_off = 0
                    self.stats.admitted += 1
        if self._prefill_slot is None:
            return
        slot = self._prefill_slot
        req = self._req[slot]
        prompt = np.asarray(req.prompt, np.int32)
        plen = prompt.size
        params = self._params(req.user)
        n_live = 1
        while n_live < len(self._slot_alloc[slot]):
            n_live *= 2
        n_live = min(n_live, self.slot_pages)
        pages = jnp.asarray(self._table[slot:slot + 1, :n_live])
        budget = self.prefill_chunk
        t0 = time.perf_counter()
        done = 0
        logits = None
        with obs.span("serve.admit", prompt=plen,
                      chunk=min(budget, plen - self._prefill_off)):
            while budget > 0 and self._prefill_off < plen:
                c = min(plen - self._prefill_off, budget)
                if c < self.prefill_chunk:   # pow2 tail pieces: bounded
                    c = 1 << (c.bit_length() - 1)
                end = self._prefill_off + c
                logits, self.cache = self._fns["prefill_chunk"](
                    params, self.cache,
                    jnp.asarray(prompt[None, self._prefill_off:end]),
                    jnp.asarray([self._prefill_off], np.int32), pages,
                    jnp.int32(slot))
                self._prefill_off = end
                budget -= c
                done += c
            jax.block_until_ready(self.cache)
        elapsed = time.perf_counter() - t0
        self.stats.prefill_s += elapsed
        self.stats.decode_stall_s += elapsed * int(self._active.sum())
        self.stats.prefill_tokens += done
        if self._prefill_off < plen:
            return                       # more chunks next step
        self._prefill_slot = None
        self._activate(slot, req,
                       np.asarray(logits[:, -1, :], np.float32)[0], plen)

    def _activate(self, slot: int, req: Request, logits_row: np.ndarray,
                  plen: int):
        """Hand a fully prefilled slot to decode: pick the first token,
        mark the slot active, record time-to-first-token. One key split
        per admission in both admission modes keeps greedy (and the
        per-admission sampling key) bit-identical between them."""
        self.key, sub = jax.random.split(self.key)
        tok = self._pick(req, jax.random.fold_in(sub, slot), logits_row)
        now = time.perf_counter()
        self._ts[slot] = [now]
        self._ttft[slot] = (now - req.submit_ts
                            if req.submit_ts is not None else 0.0)
        self.stats.queue_wait_s += float(self._queue_wait[slot])
        self.stats.ttft_s += float(self._ttft[slot])
        self._req[slot] = req
        self._active[slot] = True
        self._pos[slot] = plen
        self._remaining[slot] = req.max_new - 1
        self._last[slot] = tok
        self._out[slot] = [tok]
        self._slot_drafted[slot] = 0
        self._slot_accepted[slot] = 0
        self.stats.peak_active_slots = max(self.stats.peak_active_slots,
                                           int(self._active.sum()))
        if self._remaining[slot] == 0:
            self._finish(slot)

    def _pick(self, req: Request, key, logits_row: np.ndarray) -> int:
        if req.greedy:
            return int(logits_row.argmax())
        tok = sampling.sample_topk(key[None], jnp.asarray(logits_row)[None],
                                   req.topk or logits_row.size,
                                   req.temperature)
        return int(np.asarray(tok)[0])

    def _finish(self, slot: int):
        with obs.span("serve.finish"):
            req = self._req[slot]
            drafted = int(self._slot_drafted[slot])
            self._finished.append(Completion(
                rid=req.rid, user=req.user, prompt=np.asarray(req.prompt),
                tokens=np.asarray(self._out[slot], np.int32),
                accept_rate=(int(self._slot_accepted[slot]) / drafted
                             if drafted else None),
                queue_wait_s=float(self._queue_wait[slot]),
                ttft_s=float(self._ttft[slot]), token_ts=self._ts[slot]))
            self._ts[slot] = []
            self._active[slot] = False
            self._req[slot] = None
            if self.paged:
                self._release_slot_pages(slot)
            self.stats.finished += 1

    # ---- decode ---------------------------------------------------------
    def _live_pages(self, cover: np.ndarray):
        """Grow page tables to cover this step's highest write position
        per slot (plain decode: ``pos``; speculative rounds: ``pos +
        draft_len``, which the admission reservation still covers), then
        return the (n_slots, n_live) table slice spanning every live
        page -- n_live bucketed to powers of two so the decode dispatch
        compiles once per bucket, not once per length."""
        with obs.span("serve.pages"):
            for slot in np.flatnonzero(self._active):
                while (len(self._slot_alloc[slot])
                       <= cover[slot] // self.page_size):
                    self._alloc_page(slot)      # reservation guarantees one
            maxp = 1 + int(cover[self._active].max()) // self.page_size
            n_live = 1
            while n_live < maxp:
                n_live *= 2
            n_live = min(n_live, self.slot_pages)
            return jnp.asarray(self._table[:, :n_live])

    def _spec_step(self):
        """One speculative round: base drafts up to ``spec_k`` tokens per
        slot into the shared pages, target verifies the whole window in
        one batched call, the longest accepted prefix (plus the target's
        correction/bonus token) is committed. Greedy slots accept by
        exact argmax prefix match -- output is bit-identical to the
        plain engine; sampled slots run speculative rejection sampling
        (:func:`repro.serve.sampling.spec_accept`). Returns the number of
        distinct adapters the round verified with."""
        self._admit()
        if not self._active.any():
            return 0
        t0 = time.perf_counter()
        k = self.spec_k
        act = self._active.copy()
        d = np.where(act, np.minimum(k, self._remaining), 0).astype(np.int32)
        pos_np = np.minimum(self._pos, self.max_len - 1)
        pages = self._live_pages(pos_np + d)
        with obs.span("serve.draft"):
            drafts, self.cache = self._fns["draft_spec"](
                self._params(None), self.cache,
                jnp.asarray(self._last), jnp.asarray(pos_np), pages,
                jnp.asarray(d), k)
            drafts = np.asarray(drafts)                 # (k, n_slots)
        win = np.concatenate([self._last.reshape(-1, 1), drafts.T],
                             axis=1).astype(np.int32)   # (n_slots, k+1)
        win_len = d + 1
        # snapshot slot->user before any commit can finish (and null) a
        # slot's request mid-round; masks stay disjoint across users
        slot_user = {i: self._req[i].user for i in np.flatnonzero(act)}
        users = set(slot_user.values())
        keys = None
        if any(not self._req[i].greedy for i in np.flatnonzero(act)):
            self.key, keys = sampling.step_keys(self.key, self.n_slots)
            keys = np.asarray(keys)
        n_committed = 0
        for u in users:
            mask = np.array([i in slot_user and slot_user[i] == u
                             for i in range(self.n_slots)])
            wmask = mask[:, None] & (np.arange(k + 1)[None, :]
                                     < win_len[:, None])
            params = self._params(u)
            with obs.span("serve.verify", slots=lambda: int(mask.sum())):
                lg, vstate = self._fns["verify_spec"](
                    params, self.cache, jnp.asarray(win),
                    jnp.asarray(pos_np), pages, jnp.asarray(wmask))
            with obs.span("serve.accept"):
                committed = self._accept(mask, d, drafts, lg, keys)
            with obs.span("serve.commit"):
                acc = np.zeros(self.n_slots, np.int32)
                for slot, toks in committed.items():
                    acc[slot] = len(toks) - 1  # state after consuming
                    #                            window offsets [0, len)
                self.cache = self._fns["commit_spec"](
                    self.cache, vstate, jnp.asarray(acc), jnp.asarray(mask))
                now = time.perf_counter()
                for slot, toks in committed.items():
                    self._out[slot].extend(toks)
                    self._ts[slot].extend([now] * len(toks))
                    self._last[slot] = toks[-1]
                    self._pos[slot] += len(toks)
                    self._remaining[slot] -= len(toks)
                    n_committed += len(toks)
                    if (self._remaining[slot] == 0
                            or self._pos[slot] >= self.max_len - 1):
                        self._finish(slot)
        self.stats.decode_s += time.perf_counter() - t0
        self.stats.decode_tokens += n_committed
        self.stats.decode_steps += 1
        return len(users)

    def _accept(self, mask, d, drafts, lg, keys) -> Dict[int, List[int]]:
        """The verify logits to the host, then per masked slot the
        tokens to commit: the accepted draft prefix plus the target's
        correction or bonus token."""
        lg = np.asarray(lg, np.float32)                 # (n_slots, k+1, V)
        committed: Dict[int, List[int]] = {}
        for slot in np.flatnonzero(mask):
            req = self._req[slot]
            ds = int(d[slot])
            rem = int(self._remaining[slot])        # >= 1 while active
            if req.greedy:
                tgt = lg[slot, :ds + 1].argmax(axis=1).astype(np.int32)
                a = 0
                while a < ds and drafts[a, slot] == tgt[a]:
                    a += 1
                toks = tgt[:min(a + 1, rem)].tolist()
            else:
                n_acc, nxt = sampling.spec_accept(
                    jnp.asarray(keys[slot]),
                    jnp.asarray(drafts[:ds, slot]),
                    jnp.asarray(lg[slot, :ds + 1]),
                    req.topk or self.cfg.vocab, req.temperature)
                a = int(n_acc)
                toks = (drafts[:a, slot].tolist()
                        + [int(np.asarray(nxt))])[:min(a + 1, rem)]
            committed[slot] = toks
            self._slot_drafted[slot] += ds
            self._slot_accepted[slot] += min(a, len(toks))
            self.stats.spec_drafted += ds
            self.stats.spec_accepted += min(a, len(toks))
        return committed

    def step(self):
        """Admit whatever fits, then advance every active slot one token
        (or one speculative window when ``spec_k`` is set). Its span
        carries the distinct adapters the round ran."""
        with obs.span("serve.step", active=lambda: int(self._active.sum()),
                      queued=lambda: len(self.queue)) as sp:
            n = self._spec_step() if self.spec_k else self._decode_step()
            sp.set_metadata(adapters=n)

    def _decode_step(self) -> int:
        """One plain decode step; returns the distinct adapters run."""
        self._admit()
        if not self._active.any():
            return 0
        t0 = time.perf_counter()
        toks = jnp.asarray(self._last.reshape(self.n_slots, 1))
        pos_np = np.minimum(self._pos, self.max_len - 1)
        pos = jnp.asarray(pos_np)
        pages = self._live_pages(pos_np) if self.paged else None
        users = {self._req[i].user for i in range(self.n_slots)
                 if self._active[i]}
        merged = np.zeros((self.n_slots, self.cfg.vocab), np.float32)
        # while a chunked prefill is in flight its slot must not see
        # unmasked decode writes: the slot's table row points at real
        # pages (not trash) and its dense recurrent lane is mid-advance,
        # so the all-slots fast path would corrupt both
        if len(users) == 1 and self._prefill_slot is None:
            params = self._params(next(iter(users)))
            if self.paged:
                lg, self.cache = self._fns["decode_all_paged"](
                    params, self.cache, toks, pos, pages)
            else:
                lg, self.cache = self._fns["decode_all"](
                    params, self.cache, toks, pos)
            merged[:] = np.asarray(lg[:, -1, :], np.float32)
        else:
            for u in users:
                mask = np.array([self._active[i]
                                 and self._req[i].user == u
                                 for i in range(self.n_slots)])
                params = self._params(u)
                if self.paged:
                    lg, self.cache = self._fns["decode_masked_paged"](
                        params, self.cache, toks, pos, pages,
                        jnp.asarray(mask))
                else:
                    lg, self.cache = self._fns["decode_masked"](
                        params, self.cache, toks, pos, jnp.asarray(mask))
                merged[mask] = np.asarray(lg[:, -1, :], np.float32)[mask]

        n_active = int(self._active.sum())
        picked: Dict[int, int] = {}
        groups: Dict[tuple, List[int]] = {}   # (topk, temp) -> slots
        for slot in np.flatnonzero(self._active):
            req = self._req[slot]
            if req.greedy:
                picked[slot] = int(merged[slot].argmax())
            else:
                groups.setdefault((req.topk or self.cfg.vocab,
                                   req.temperature), []).append(int(slot))
        if groups:          # key split only when someone actually samples
            self.key, keys = sampling.step_keys(self.key, self.n_slots)
            keys = np.asarray(keys)
        for (k, temp), slots in groups.items():   # one dispatch per combo
            toks_s = sampling.sample_topk(keys[np.asarray(slots)],
                                          jnp.asarray(merged[slots]), k, temp)
            picked.update(zip(slots, np.asarray(toks_s).tolist()))
        now = time.perf_counter()
        for slot, tok in picked.items():
            self._out[slot].append(tok)
            self._ts[slot].append(now)
            self._last[slot] = tok
            self._pos[slot] += 1
            self._remaining[slot] -= 1
            if (self._remaining[slot] == 0
                    or self._pos[slot] >= self.max_len - 1):
                self._finish(slot)
        self.stats.decode_s += time.perf_counter() - t0
        self.stats.decode_tokens += n_active
        self.stats.decode_steps += 1
        return len(users)

    def drain_finished(self) -> List[Completion]:
        out, self._finished = self._finished, []
        return out

    def run(self) -> List[Completion]:
        """Serve until queue and slots are empty; completions rid-sorted."""
        out: List[Completion] = []
        while (self.queue or self._active.any()
               or self._prefill_slot is not None):
            self.step()
            out.extend(self.drain_finished())
        return sorted(out, key=lambda c: c.rid)

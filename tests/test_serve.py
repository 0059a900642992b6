"""Serving subsystem: fused-prefill/decode parity, adapter store
semantics, and continuous-batching engine behavior."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import MezoConfig
from repro.launch.serve import serve
from repro.models import build_model
from repro.serve import AdapterStore, Request, ServeEngine, tree_bytes


def _synthetic_records(n, k=2, seed=0, lr=5e-2, eps=1e-2):
    rng = np.random.default_rng(seed)
    return [{"step": i, "seed": int(rng.integers(2**31)),
             "gs": rng.normal(size=k).astype(np.float32).tolist(),
             "lr": lr, "eps": eps} for i in range(n)]


# ---------------------------------------------------------------------------
# prefill / decode parity (satellite: transformer + one non-transformer)


@pytest.mark.parametrize("arch", ["gemma-2b", "rwkv6-7b", "whisper-base"])
def test_engine_matches_per_token_loop(arch):
    """Fused prefill + batched decode must emit the same greedy tokens as
    the reference per-token loop (the old serve()). whisper-base pins the
    enc-dec prefill the runtime refactor added (cross K/V read from the
    StateCache, zeros for token-only serving -- same as the loop)."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, P, G = 2, 9, 6
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (B, P),
                                            0, cfg.vocab), np.int32)
    ref = serve(cfg, params, prompts, gen=G)

    engine = ServeEngine(cfg, AdapterStore(params), n_slots=B,
                         max_len=P + G, seed=0)
    rids = [engine.submit(Request(prompt=prompts[i], max_new=G))
            for i in range(B)]
    outs = {c.rid: c.tokens for c in engine.run()}
    got = np.stack([outs[r] for r in rids])
    np.testing.assert_array_equal(got, ref)


def test_engine_staggered_lengths_match_individual_serves():
    """Continuous batching with per-slot positions: requests of different
    prompt lengths, admitted mid-flight through 2 slots, must each decode
    exactly what a dedicated single-request loop would."""
    cfg = get_config("gemma-2b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    G = 5
    plens = [5, 9, 7]
    prompts = [np.asarray(jax.random.randint(jax.random.PRNGKey(10 + i),
                                             (p,), 0, cfg.vocab), np.int32)
               for i, p in enumerate(plens)]
    refs = [serve(cfg, params, pr[None], gen=G)[0] for pr in prompts]

    engine = ServeEngine(cfg, AdapterStore(params), n_slots=2,
                         max_len=max(plens) + G, seed=0)
    rids = [engine.submit(Request(prompt=pr, max_new=G)) for pr in prompts]
    outs = {c.rid: c.tokens for c in engine.run()}
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(outs[rid], ref)


def test_hybrid_prefill_matches_decode_loop():
    """Direct model-layer parity for the mamba-hybrid family: fused
    prefill logits and cache == P decode_step calls."""
    cfg = get_config("jamba-v0.1-52b").reduced()
    # capacity semantics differ between T=B*S and T=B token batches; use
    # generous capacity so routing drops nothing either way (the same
    # caveat as test_decode_matches_forward)
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    B, P = 2, 7
    toks = jnp.asarray(np.asarray(
        jax.random.randint(jax.random.PRNGKey(2), (B, P), 0, cfg.vocab),
        np.int32))
    cache = model.init_cache(B, P + 4)
    lg = None
    for t in range(P):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1],
                                      jnp.int32(t))
    pf_lg, pf_cache = model.prefill(params, model.init_cache(B, P + 4), toks)
    np.testing.assert_allclose(np.asarray(pf_lg, np.float32),
                               np.asarray(lg, np.float32),
                               rtol=2e-3, atol=2e-3)
    for (ka, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(cache),
            jax.tree_util.tree_leaves_with_path(pf_cache)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=jax.tree_util.keystr(ka))


def test_decode_step_vector_pos_matches_scalar():
    cfg = get_config("qwen3-4b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B = 3
    tok = jnp.zeros((B, 1), jnp.int32)
    cs, cv = model.init_cache(B, 8), model.init_cache(B, 8)
    for t in range(3):
        lg_s, cs = model.decode_step(params, cs, tok, jnp.int32(t))
        lg_v, cv = model.decode_step(params, cv, tok,
                                     jnp.full((B,), t, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_s, np.float32),
                               np.asarray(lg_v, np.float32),
                               rtol=1e-5, atol=1e-6)
    for (ka, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(cs),
            jax.tree_util.tree_leaves_with_path(cv)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(ka))


# ---------------------------------------------------------------------------
# adapter store


def _tiny_params(key=0):
    k = jax.random.PRNGKey(key)
    return {"a": {"w": jax.random.normal(k, (8, 16))},
            "b": jnp.arange(5, dtype=jnp.float32)}


def test_adapter_materialize_matches_checkpoint_restore(tmp_path, zo_step):
    """AdapterStore.materialize (full-log replay from base) must be
    bit-identical to CheckpointManager.restore (snapshot + tail replay)."""
    params = _tiny_params(1)

    def loss_fn(p, _):
        return jnp.sum(p["a"]["w"] ** 2) * 1e-3 + jnp.sum(p["b"] ** 2) * 1e-3

    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=2)
    mgr = CheckpointManager(str(tmp_path), mezo_cfg=cfg, snapshot_every=4)
    p = jax.tree.map(jnp.copy, params)
    for step in range(9):
        p, aux = zo_step(loss_fn, p, None, jnp.uint32(step), cfg,
                         estimator="vmapdir")
        mgr.on_step(step, p, aux)

    restored, nxt = CheckpointManager(str(tmp_path), mezo_cfg=cfg,
                                      snapshot_every=4).restore(params)
    assert nxt == 9
    store = AdapterStore(params, cfg)
    store.import_checkpoint("u", str(tmp_path))
    mat = store.materialize("u")
    for a, b, live in zip(jax.tree.leaves(mat), jax.tree.leaves(restored),
                          jax.tree.leaves(p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(live))


def test_adapter_momentum_rule_replay_matches_live():
    """A momentum-trained run's adapter must materialize through the
    same update rule: full-log replay from a fresh history window equals
    the live trajectory bit-for-bit."""
    from repro.core import build_strategy
    params = _tiny_params(2)

    def loss_fn(p, _):
        return jnp.sum(p["a"]["w"] ** 2) * 1e-3 + jnp.sum(p["b"] ** 2) * 1e-3

    cfg = MezoConfig(eps=1e-3, lr=1e-2, n_directions=2, momentum=0.9,
                     momentum_window=4)
    strat = build_strategy("vmapdir", "momentum")
    state = strat.init_state(jax.tree.map(jnp.copy, params), cfg)
    records = []
    for step in range(6):
        state, aux = strat.step(loss_fn, state, None, jnp.uint32(step), cfg)
        records.append({"step": step, "seed": int(np.asarray(aux.seed)),
                        "gs": np.asarray(aux.gs, np.float32).tolist(),
                        "lr": 1e-2, "eps": 1e-3})

    store = AdapterStore(params, cfg, update_rule=strat.update)
    store.put("u", records)
    for a, b in zip(jax.tree.leaves(store.materialize("u")),
                    jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    sgd_store = AdapterStore(params, cfg)      # wrong rule: must differ
    sgd_store.put("u", records)
    diff = max(np.max(np.abs(np.asarray(a, np.float32)
                             - np.asarray(b, np.float32)))
               for a, b in zip(jax.tree.leaves(sgd_store.materialize("u")),
                               jax.tree.leaves(state.params)))
    assert diff > 0


def test_adapter_lru_eviction_and_hits():
    base = _tiny_params()
    budget = 2 * tree_bytes(base) + 16     # room for ~2 materialized trees
    store = AdapterStore(base, MezoConfig(n_directions=2),
                         cache_bytes=budget)
    for i, u in enumerate(("u0", "u1", "u2")):
        store.put(u, _synthetic_records(3, seed=i))
        store.materialize(u)
    assert store.stats["misses"] == 3
    assert store.stats["evictions"] >= 1
    assert store.cached_bytes() <= budget
    store.materialize("u2")                       # most recent: still hot
    assert store.stats["hits"] == 1
    store.materialize("u0")                       # evicted: replays again
    assert store.stats["misses"] == 4


def test_adapter_save_load_roundtrip(tmp_path):
    base = _tiny_params()
    store = AdapterStore(base, MezoConfig(n_directions=2))
    store.put("u", _synthetic_records(4))
    mat = store.materialize("u")
    store.save("u", str(tmp_path / "u.jsonl"))

    other = AdapterStore(base, MezoConfig(n_directions=2))
    other.load("u", str(tmp_path / "u.jsonl"))
    for a, b in zip(jax.tree.leaves(mat),
                    jax.tree.leaves(other.materialize("u"))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_adapter_int8_delta_form(tmp_path):
    base = _tiny_params()
    store = AdapterStore(base, MezoConfig(n_directions=2))
    store.put("u", _synthetic_records(4))
    mat = store.materialize("u")
    store.save_delta("u", str(tmp_path / "u_delta.npz"))

    compact = AdapterStore(base, MezoConfig(n_directions=2))
    compact.load_delta("u", str(tmp_path / "u_delta.npz"))
    approx = compact.materialize("u")
    for a, b, bb in zip(jax.tree.leaves(mat), jax.tree.leaves(approx),
                        jax.tree.leaves(base)):
        d = np.abs(np.asarray(a, np.float32) - np.asarray(bb, np.float32))
        tol = d.max() / 127.0 + 1e-7      # one int8 roundtrip per leaf
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32), atol=tol)


def test_adapter_unknown_user_raises():
    store = AdapterStore(_tiny_params())
    with pytest.raises(KeyError):
        store.materialize("nobody")
    assert store.materialize(None) is store.base


# ---------------------------------------------------------------------------
# engine: multi-adapter interleaving + seeded sampling


def test_engine_interleaves_two_adapters_and_seeds_sampling():
    cfg = get_config("gemma-2b").reduced()
    model = build_model(cfg)
    base = model.init(jax.random.PRNGKey(0))
    store = AdapterStore(base, MezoConfig(n_directions=2))
    store.put("alice", _synthetic_records(6, seed=1))
    store.put("bob", _synthetic_records(6, seed=2))
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (6,),
                                           0, cfg.vocab), np.int32)

    def run(seed, greedy):
        eng = ServeEngine(cfg, store, n_slots=2, max_len=16, seed=seed)
        rids = [eng.submit(Request(prompt=prompt, max_new=4, user=u,
                                   greedy=greedy, topk=8))
                for u in ("alice", "bob", "alice")]   # 3 reqs, 2 slots
        outs = {c.rid: c for c in eng.run()}
        assert [outs[r].user for r in rids] == ["alice", "bob", "alice"]
        return [outs[r].tokens.tolist() for r in rids]

    g = run(0, greedy=True)
    assert g == run(7, greedy=True)        # greedy ignores the seed
    assert g[0] == g[2]                    # same adapter, same prompt
    s0, s0b, s1 = run(0, False), run(0, False), run(1, False)
    assert s0 == s0b                       # seeded sampling is reproducible
    assert s0 != s1 or s0[0] != g[0]       # and actually samples


def test_engine_rejects_oversized_request():
    cfg = get_config("gemma-2b").reduced()
    model = build_model(cfg)
    eng = ServeEngine(cfg, AdapterStore(model.init(jax.random.PRNGKey(0))),
                      n_slots=1, max_len=8)
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=np.zeros(6, np.int32), max_new=4))
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=np.zeros(2, np.int32), max_new=0))


# ---------------------------------------------------------------------------
# chunked prefill: staggered arrivals, observability


def _staggered_serve(cfg, store, prefill_chunk=None):
    """Two adapters decoding, then a long-prompt base request arriving
    mid-flight -- the admission-stall scenario chunked prefill exists
    for. Returns {rid: tokens} plus the engine for stats assertions."""
    eng = ServeEngine(cfg, store, n_slots=3, max_len=40, seed=0,
                      paged=True, page_size=4,
                      prefill_chunk=prefill_chunk)
    rng = np.random.default_rng(7)
    mk = lambda p, u: Request(
        prompt=rng.integers(0, cfg.vocab, p).astype(np.int32),
        max_new=6, user=u)
    eng.submit(mk(5, "alice"))
    eng.submit(mk(7, "bob"))
    out = []
    for _ in range(3):                    # both slots mid-decode
        eng.step()
        out.extend(eng.drain_finished())
    eng.submit(mk(23, None))              # long prompt arrives
    eng.submit(mk(6, "alice"))
    while eng.queue or eng._active.any() or eng._prefill_slot is not None:
        eng.step()
        out.extend(eng.drain_finished())
    return {c.rid: c.tokens.tolist() for c in out}, eng, out


def test_chunked_prefill_staggered_multi_adapter_parity():
    """Greedy tokens bit-identical chunked vs whole-prompt admission
    when a long prompt lands mid-decode across two resident adapters,
    for chunk sizes that leave the admission in flight over several
    engine steps."""
    cfg = get_config("gemma-2b").reduced()
    store = AdapterStore(build_model(cfg).init(jax.random.PRNGKey(0)))
    store.put("alice", _synthetic_records(4, seed=1))
    store.put("bob", _synthetic_records(4, seed=2))
    whole, _, _ = _staggered_serve(cfg, store)
    for chunk in (2, 5):
        got, eng, _ = _staggered_serve(cfg, store, prefill_chunk=chunk)
        assert got == whole
        assert eng.stats.prefill_tokens == 5 + 7 + 23 + 6


def test_engine_latency_observability():
    """queue_wait_s / ttft_s per completion (submit -> admission start /
    first token) and the decode_stall_s counter: present, ordered, and
    consistent with the stats totals."""
    cfg = get_config("gemma-2b").reduced()
    store = AdapterStore(build_model(cfg).init(jax.random.PRNGKey(0)))
    store.put("alice", _synthetic_records(4, seed=1))
    store.put("bob", _synthetic_records(4, seed=2))
    _, eng, comps = _staggered_serve(cfg, store)
    assert len(comps) == 4
    for c in comps:
        assert 0.0 <= c.queue_wait_s <= c.ttft_s
    assert eng.stats.ttft_s == pytest.approx(sum(c.ttft_s for c in comps))
    assert eng.stats.queue_wait_s == pytest.approx(
        sum(c.queue_wait_s for c in comps))
    # three slots decoded while the 23-token prompt prefilled whole: the
    # admission stall must be visible (chunked admission shrinks it)
    assert eng.stats.decode_stall_s > 0.0


def test_completion_token_timestamps():
    """token_ts: one host time per committed token, never decreasing,
    from at or after submit; ttft_s is its first entry less submit_ts --
    in plain and in speculative rounds."""
    cfg = get_config("gemma-2b").reduced()
    store = AdapterStore(build_model(cfg).init(jax.random.PRNGKey(0)))
    store.put("alice", _synthetic_records(4, seed=1))
    rng = np.random.default_rng(3)
    for spec_k in (None, 2):
        eng = ServeEngine(cfg, store, n_slots=2, max_len=24, seed=0,
                          paged=True, page_size=4, spec_k=spec_k)
        reqs = [Request(prompt=rng.integers(0, cfg.vocab, p).astype(
            np.int32), max_new=g, user=u)
            for p, g, u in ((5, 6, "alice"), (7, 4, None), (3, 5, "alice"))]
        for r in reqs:
            eng.submit(r)
        comps = {c.rid: c for c in eng.run()}
        for r in reqs:
            c = comps[r.rid]
            assert len(c.token_ts) == c.tokens.size == r.max_new
            assert c.token_ts == sorted(c.token_ts)
            assert c.token_ts[0] >= r.submit_ts
            assert c.ttft_s == c.token_ts[0] - r.submit_ts


def test_traced_round_verifies_once_per_adapter(tmp_path):
    """A speculative round's repro.serve.step span carries the distinct
    adapters it ran, and holds that many repro.serve.verify spans."""
    import glob

    from jax.profiler import ProfileData
    cfg = get_config("gemma-2b").reduced()
    store = AdapterStore(build_model(cfg).init(jax.random.PRNGKey(0)))
    store.put("alice", _synthetic_records(4, seed=1))
    store.put("bob", _synthetic_records(4, seed=2))
    eng = ServeEngine(cfg, store, n_slots=2, max_len=24, seed=0,
                      paged=True, page_size=4, spec_k=2)
    rng = np.random.default_rng(4)
    for u in ("alice", "bob"):
        eng.submit(Request(prompt=rng.integers(0, cfg.vocab, 6).astype(
            np.int32), max_new=5, user=u))
    eng.step()                       # admission and the first round compile
    with jax.profiler.trace(str(tmp_path)):
        eng.step()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats or ()))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro.serve.")]
    (_, s0, s1, args), = [s for s in spans if s[0] == "repro.serve.step"]
    verifies = [s for s in spans if s[0] == "repro.serve.verify"
                and s0 <= s[1] and s[2] <= s1]
    assert int(args["adapters"]) == len(verifies) == 2
    assert sorted(int(v[3]["slots"]) for v in verifies) == [1, 1]
    names = {s[0] for s in spans}
    assert {"repro.serve.draft", "repro.serve.accept", "repro.serve.commit",
            "repro.serve.pages", "repro.serve.materialize"} <= names

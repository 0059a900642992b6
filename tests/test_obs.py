"""The program's tracing layer (repro.obs): spans cost one check with no
trace recording, appear with their args on the host plane when one
records, compiles are counted by function name, and the fused step's
device ops carry the program's scopes."""

import contextlib
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core.engine import MezoConfig, build_strategy
from repro.models import build_model
from repro.models.config import ModelConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _host_events(trace_dir, prefix="repro."):
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((ev.name, dict(ev.stats or ()))
                           for ev in line.events
                           if ev.name.startswith(prefix))
    return out


def test_span_is_the_shared_null_context_without_a_trace():
    called = []
    sp = obs.span("x", cost=lambda: called.append(1))
    assert sp is obs.span("y") is obs._NULL
    with sp as entered:
        entered.set_metadata(adapters=3)
    assert called == []                  # a callable arg is never called


def test_spans_and_args_on_the_host_plane(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("serve.step", active=2, queued=lambda: 5,
                      user=None) as sp:
            sp.set_metadata(adapters=2)
            with obs.span("serve.verify", slots=1):
                pass
    evs = dict(_host_events(str(tmp_path)))
    assert {k: int(v) for k, v in evs["repro.serve.step"].items()} == {
        "active": 2, "queued": 5, "adapters": 2}   # a None arg left out
    assert int(evs["repro.serve.verify"]["slots"]) == 1


def test_compile_counter_names_a_fresh_jit(tmp_path):
    @jax.jit
    def fresh_fn_for_the_counter(x):
        return x * 3 + 1

    before = obs.compiles().get("jit(fresh_fn_for_the_counter)", (0, 0.0))
    with jax.profiler.trace(str(tmp_path)):
        fresh_fn_for_the_counter(jnp.ones(7)).block_until_ready()
    n, secs = obs.compiles()["jit(fresh_fn_for_the_counter)"]
    assert n == before[0] + 1 and secs > before[1]
    funs = [a.get("fun") for name, a in _host_events(str(tmp_path))
            if name == "repro.jit.compile"]
    assert "jit(fresh_fn_for_the_counter)" in funs


@pytest.fixture(scope="module")
def fused_step_hlo():
    """Compiled HLO text of the fused step (zo_matmul kernel on) at the
    reduced opt-1.3b shapes."""
    with open(os.path.join(ROOT, "bench", "configs", "opt-1.3b.json")) as f:
        cfg = ModelConfig(**json.load(f)["model"]).reduced()
    model = build_model(cfg)
    strategy = build_strategy("fused", "sgd")
    mezo = MezoConfig(lr=1e-6, eps=1e-3, use_kernel=True)
    state = strategy.init_state(model.init(jax.random.PRNGKey(0)), mezo)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "targets": jnp.zeros((2, 16), jnp.int32)}
    params = jax.tree_util.tree_flatten_with_path(state.params)[0]
    return (strategy.lower(model.loss, state, batch, jnp.uint32(1), mezo)
            .compile().as_text(), params)


def test_fused_step_carries_the_program_scopes(fused_step_hlo):
    text, params = fused_step_hlo
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in (obs.FORWARD, obs.UPDATE, obs.LM_HEAD, obs.LOSS):
        assert any(f"/{scope}/" in n for n in names), scope
    # every 2-D projection weight: its matmul runs under its own scope
    projections = {jax.tree_util.keystr(p)[2:-2].replace("']['", "/")
                   .rsplit("/", 1)[0] for p, leaf in params
                   if jax.tree_util.keystr(p).endswith("['w']")}
    assert projections == {"blocks/attn/wq", "blocks/attn/wk",
                           "blocks/attn/wv", "blocks/attn/wo",
                           "blocks/mlp/w_in", "blocks/mlp/w_out", "lm_head"}
    for proj in projections:
        assert any(f"/{obs.MATMUL}{proj}/" in n for n in names), proj


# ---------------------------------------------------------------------------
# the attention core and the classification head, in both train models

SMALL_STEP = {"opt-1.3b": {"targets": jnp.zeros((2, 16), jnp.int32)},
              "roberta-large": {"label": jnp.array([0, 1], jnp.int32)}}


def _small_step(arch, scoped=True):
    """(compiled HLO text, loss, gs) of one fused step (zo_matmul kernel
    on) of the benchmark's ``arch`` at reduced shapes; ``scoped=False``
    takes the attention and head scopes out (each call traces the step
    afresh: its loss function is new)."""
    with open(os.path.join(ROOT, "bench", "configs", f"{arch}.json")) as f:
        cfg = ModelConfig(**json.load(f)["model"]).reduced()
    model = build_model(cfg)
    strategy = build_strategy("fused", "sgd")
    mezo = MezoConfig(lr=1e-6, eps=1e-3, use_kernel=True)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                          cfg.vocab), **SMALL_STEP[arch]}
    real = jax.named_scope
    taken_out = (obs.ATTENTION, obs.CLS_HEAD)
    jax.named_scope = (real if scoped else lambda name: (
        contextlib.nullcontext() if name in taken_out else real(name)))
    loss_fn = lambda *a, **k: model.loss(*a, **k)     # noqa: E731
    try:
        state = strategy.init_state(model.init(jax.random.PRNGKey(0)), mezo)
        text = strategy.lower(loss_fn, state, batch, jnp.uint32(1),
                              mezo).compile().as_text()
        _, aux = strategy.step(loss_fn, state, batch, jnp.uint32(1), mezo)
        return text, np.asarray(aux.loss), np.asarray(aux.gs)
    finally:
        jax.named_scope = real


@pytest.fixture(scope="module", params=sorted(SMALL_STEP))
def small_steps(request):
    return (request.param, _small_step(request.param),
            _small_step(request.param, scoped=False))


def test_attention_core_and_head_carry_their_scopes(small_steps):
    arch, (text, _, _), (bare, _, _) = small_steps
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(f"/{obs.ATTENTION}/" in n for n in names)
    # the projections keep their own scopes, outside the attention core
    assert not any(obs.ATTENTION in n and obs.MATMUL in n for n in names)
    head = any(f"/{obs.CLS_HEAD}/" in n for n in names)
    assert head == (arch == "roberta-large")
    assert obs.ATTENTION not in bare and obs.CLS_HEAD not in bare


def test_attention_and_head_scopes_leave_the_step_bit_identical(
        small_steps):
    _, (_, loss, gs), (_, loss_bare, gs_bare) = small_steps
    assert loss.tobytes() == loss_bare.tobytes()
    assert gs.tobytes() == gs_bare.tobytes()


@pytest.mark.parametrize("backend, path", [("cpu", obs.ATTN_JNP),
                                           ("tpu", obs.ATTN_KERNEL)])
def test_attention_core_counter_counts_each_path_once_per_trace(
        backend, path, monkeypatch):
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import layers as L

    cfg = get_config("opt-1.3b").reduced(d_model=128, n_heads=2,
                                         n_kv_heads=2, max_seq=128)
    p = L.attn_init(cfg, jax.random.PRNGKey(0))
    x = jnp.zeros((2, 128, cfg.d_model), jnp.float32)
    monkeypatch.setattr(ops, "BACKEND", backend)
    other = obs.ATTN_JNP if path == obs.ATTN_KERNEL else obs.ATTN_KERNEL
    for n in (1, 2):
        before = obs.attention_cores()
        jax.jit(lambda p, x: L.attn_apply(cfg, p, x)).lower(p, x)
        after = obs.attention_cores()
        assert after.get(path, 0) - before.get(path, 0) == 1
        assert after.get(other, 0) == before.get(other, 0)

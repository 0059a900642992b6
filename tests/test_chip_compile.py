"""Every main-path Pallas kernel compiles for a described TPU v5e at
opt-1.3b widths (d_model 2048, d_ff 8192, 32 heads of 64, pages of 16),
and the fused ZO matmul and the attention core also at roberta-large's
f32 widths; the fused ZO matmul also on a layer-stacked weight, and the
fused step of a 2-layer model at roberta-large widths hands it the stack
without a slice copy.

Nothing runs: the TPU compiler, installed here, compiles for a chip that
is described and not attached, and refuses what the chip would refuse
(block shapes off the (8, 128) tiling, casts Mosaic lacks, tiles that
overflow the scoped VMEM) -- which interpret mode never checks. The
topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and pytest-xdist workers
import every test file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.kernels import zo_perturb as zp
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.flash_verify import flash_verify

NL = 24                              # layers of both train models
D, F = 2048, 8192                    # opt-1.3b d_model, d_ff
RD, RF = 1024, 4096                  # roberta-large d_model, d_ff
H, KV, HD, PS = 32, 32, 64, 16       # heads, kv heads, head_dim, page size
SLOTS, N_LIVE = 4, 18                # 4 slots of 288 tokens
N_PAGES = SLOTS * N_LIVE + 1
BF, F32, I8, I32, U32 = (jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32,
                         jnp.uint32)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _zo_matmul(x, w, s, c):
    return zp.zo_matmul(x, w, s, 0, c)


def _zo_matmul_highest(x, w, s, c):
    """As roberta-large runs it: f32 products at HIGHEST."""
    with jax.default_matmul_precision("highest"):
        return zp.zo_matmul(x, w, s, 0, c)


def _zo_matmul_q(x, w, sc, s, c):
    return zp.zo_matmul(x, w, s, 0, c, scale=sc)


def _zo_matmul_stacked(x, w, s, c, layer):
    return zp.zo_matmul(x, w, s, 0, c, layer=layer)


def _zo_matmul_stacked_highest(x, w, s, c, layer):
    with jax.default_matmul_precision("highest"):
        return zp.zo_matmul(x, w, s, 0, c, layer=layer)


def _zo_matmul_stacked_q(x, w, sc, s, c, layer):
    return zp.zo_matmul(x, w, s, 0, c, scale=sc, layer=layer)


def _zo_matmul_users_q(x, w, sc, s, c):
    return zp.zo_matmul_users(x, w, s, 0, c, scale=sc)


def _flash_causal(q, k, v):
    return flash_attention(q, k, v, causal=True)


def _flash_bidir_highest(q, k, v):
    """As roberta-large runs it: f32 operands at HIGHEST, no mask."""
    with jax.default_matmul_precision("highest"):
        return flash_attention(q, k, v, causal=False)


def _zo_add(w, s, c):
    return zp.zo_add(w, s, 0, c)


def _zo_add_q(w, sc, s, c):
    return zp.zo_add(w, s, 0, c, scale=sc)


# name -> (kernel, argument (shape, dtype) list)
CASES = {
    "zo_matmul_f32": (_zo_matmul, [((512, D), F32), ((D, F), F32),
                                   ((), U32), ((), F32)]),
    "zo_matmul_bf16": (_zo_matmul, [((4096, D), BF), ((D, F), BF),
                                    ((), U32), ((), F32)]),
    "zo_matmul_bf16_m200": (_zo_matmul, [((200, D), BF), ((D, F), BF),
                                         ((), U32), ((), F32)]),
    # the train cells' projections at their picked tiles: opt-1.3b in
    # bf16 (8 x 512 rows), roberta-large in f32 (64 x 128 rows), at the
    # default precision and at HIGHEST
    "zo_matmul_bf16_attn": (_zo_matmul, [((4096, D), BF), ((D, D), BF),
                                         ((), U32), ((), F32)]),
    "zo_matmul_bf16_w_out": (_zo_matmul, [((4096, F), BF), ((F, D), BF),
                                          ((), U32), ((), F32)]),
    "zo_matmul_f32_rl_attn": (_zo_matmul, [((8192, RD), F32),
                                           ((RD, RD), F32), ((), U32),
                                           ((), F32)]),
    "zo_matmul_f32_rl_w_in": (_zo_matmul, [((8192, RD), F32),
                                           ((RD, RF), F32), ((), U32),
                                           ((), F32)]),
    "zo_matmul_f32_rl_w_out": (_zo_matmul, [((8192, RF), F32),
                                            ((RF, RD), F32), ((), U32),
                                            ((), F32)]),
    "zo_matmul_f32_highest_rl_attn": (_zo_matmul_highest, [
        ((8192, RD), F32), ((RD, RD), F32), ((), U32), ((), F32)]),
    "zo_matmul_f32_highest_rl_w_in": (_zo_matmul_highest, [
        ((8192, RD), F32), ((RD, RF), F32), ((), U32), ((), F32)]),
    "zo_matmul_f32_highest_rl_w_out": (_zo_matmul_highest, [
        ((8192, RF), F32), ((RF, RD), F32), ((), U32), ((), F32)]),
    "zo_matmul_int8": (_zo_matmul_q, [((512, D), BF), ((D, F), I8),
                                      ((F,), F32), ((), U32), ((), F32)]),
    # a layer of the (24, K, N) stack, read in place: opt-1.3b's w_in in
    # bf16, roberta-large's w_out in f32 at HIGHEST, an int8 stack
    "zo_matmul_bf16_stacked_w_in": (_zo_matmul_stacked, [
        ((4096, D), BF), ((NL, D, F), BF), ((), U32), ((), F32), ((), U32)]),
    "zo_matmul_f32_highest_rl_stacked_w_out": (_zo_matmul_stacked_highest, [
        ((8192, RF), F32), ((NL, RF, RD), F32), ((), U32), ((), F32),
        ((), U32)]),
    "zo_matmul_int8_stacked": (_zo_matmul_stacked_q, [
        ((512, D), BF), ((NL, D, F), I8), ((NL, F), F32), ((), U32),
        ((), F32), ((), U32)]),
    "zo_matmul_users_int8": (_zo_matmul_users_q, [
        ((4, 512, D), BF), ((D, F), I8), ((F,), F32), ((4,), U32),
        ((4,), F32)]),
    "zo_add_f32": (_zo_add, [((D, F), F32), ((), U32), ((), F32)]),
    "zo_add_int8": (_zo_add_q, [((D, F), I8), ((F,), F32), ((), U32),
                                ((), F32)]),
    # the train cells' attention cores: opt-1.3b (batch 8 x seq 512,
    # causal, bf16) and roberta-large (batch 64 x seq 128, f32, HIGHEST)
    "flash_attention_opt": (_flash_causal, [((8, 512, H, HD), BF)] * 3),
    "flash_attention_roberta_highest": (_flash_bidir_highest, [
        ((64, 128, 16, HD), F32)] * 3),
    "flash_decode": (flash_decode, [
        ((SLOTS, H, HD), BF), ((N_PAGES, PS, KV, HD), BF),
        ((N_PAGES, PS, KV, HD), BF), ((SLOTS, N_LIVE), I32), ((SLOTS,), I32)]),
    "flash_verify": (flash_verify, [
        ((SLOTS, 5, H, HD), BF), ((N_PAGES, PS, KV, HD), BF),
        ((N_PAGES, PS, KV, HD), BF), ((SLOTS, N_LIVE), I32), ((SLOTS,), I32)]),
    "flash_prefill": (flash_prefill, [
        ((1, 64, H, HD), BF), ((N_PAGES, PS, KV, HD), BF),
        ((N_PAGES, PS, KV, HD), BF), ((1, N_LIVE), I32), ((1,), I32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _zo_matmul_weight_producers(text):
    """For each fused-matmul custom call of an optimized HLO text: the
    (opcode, shape, fused computation's text) that produce its
    operands."""
    insts, comps, comp = {}, {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            comp = comps.setdefault(head.group(1), [])
        elif line == "}":
            comp = None
        elif comp is not None:
            comp.append(line)
            inst = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ([a-z][\w\-]*)"
                            r"\((.*)", line)
            if inst:
                insts[inst.group(1)] = inst.groups()[1:]
    calls = []
    for shape, opcode, rest in insts.values():
        if opcode == "custom-call" and "tpu_custom_call" in rest and \
                f"/{obs.MATMUL}" in rest:
            producers = []
            for operand in re.findall(r"%[\w.\-]+", rest.split(")")[0]):
                shp, op, body = insts[operand]
                called = re.search(r"calls=(%[\w.\-]+)", body)
                producers.append((op, shp, "\n".join(
                    comps.get(called.group(1), ())) if called else ""))
            calls.append(producers)
    return calls


def test_fused_step_reads_weights_from_the_stack(one_chip, monkeypatch):
    """The fused step of a 2-layer model at roberta-large widths (f32,
    HIGHEST), compiled for the chip: every fused-matmul call takes a
    (L, K, N) stack as an operand and none takes an operand made by a
    dynamic-slice -- the per-layer weight copy out of the stack is gone."""
    from repro.configs import get_config
    from repro.core.engine import MezoConfig, build_strategy
    from repro.kernels import ops
    from repro.models import build_model

    monkeypatch.setattr(ops, "_INTERPRET", False)
    cfg = dataclasses.replace(get_config("roberta-large"), n_layers=2,
                              vocab=512)
    model = build_model(cfg)
    strategy = build_strategy("fused", "sgd")
    mezo = MezoConfig(lr=1e-6, eps=1e-3, use_kernel=True)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), jax.eval_shape(
        lambda: strategy.init_state(model.init(jax.random.PRNGKey(0)),
                                    mezo)))
    batch = {"tokens": on_chip((8, 128), I32), "label": on_chip((8,), I32)}
    with jax.default_matmul_precision("highest"):
        text = strategy.lower(model.loss, state, batch, jnp.uint32(1),
                              mezo).compile().as_text()
    calls = _zo_matmul_weight_producers(text)
    assert len(calls) == 12                  # 6 projections x 2 forwards
    for producers in calls:
        assert any(shape.split("]")[0].count(",") == 2
                   for _, shape, _ in producers)
        assert not any(op == "dynamic-slice" or " dynamic-slice(" in fused
                       for op, _, fused in producers)

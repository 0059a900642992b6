"""Flash-attention kernel (interpret mode) vs the jnp oracle, and the
dispatch that sends a self-attention core to one or the other."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.models.layers import attention

KEY = jax.random.PRNGKey(0)
BF, F32 = jnp.bfloat16, jnp.float32
TOL = {F32: 2e-4, BF: 5e-2}


def _qkv(b, s, t, h, kv, hd, dtype=jnp.float32):
    q = jax.random.normal(KEY, (b, s, h, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, kv, hd),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, t, kv, hd),
                          jnp.float32).astype(dtype)
    return q, k, v


def _close(got, want, dtype):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [
    (1, 64, 4, 2, 16),    # GQA g=2
    (2, 128, 8, 1, 32),   # MQA
    (1, 96, 4, 4, 16),    # MHA, non-128 seq
])
def test_flash_matches_reference(shape, causal):
    b, s, h, kv, hd = shape
    q, k, v = _qkv(b, s, s, h, kv, hd)
    got = flash_attention(q, k, v, causal=causal, blocks=(1, 32, 32),
                          interpret=True)
    want = attention(q, k, v, causal=causal, chunk=0)
    _close(got, want, F32)


# (b, s, t, h, kv, hd, dtype, causal, (hkv, bq, bk))
CASES = {
    "causal_mha_f32": (2, 64, 64, 4, 4, 16, F32, True, (2, 16, 32)),
    "noncausal_mha_f32": (2, 64, 64, 4, 4, 16, F32, False, (2, 16, 32)),
    "causal_gqa_bf16": (1, 64, 64, 8, 2, 32, BF, True, (2, 32, 32)),
    "noncausal_gqa_bf16": (1, 64, 64, 8, 2, 32, BF, False, (1, 32, 16)),
    "causal_mqa_bf16": (2, 64, 64, 4, 1, 64, BF, True, (1, 16, 64)),
    "all_heads_one_step_f32": (1, 32, 32, 8, 8, 16, F32, True,
                               (8, 16, 16)),
    "t_twice_s_noncausal": (1, 32, 64, 4, 2, 16, F32, False, (2, 16, 16)),
    "t_twice_s_causal": (1, 32, 64, 4, 2, 16, BF, True, (1, 16, 32)),
    "s_twice_t_causal": (1, 64, 32, 4, 4, 16, F32, True, (4, 32, 16)),
    "picked_tiling_bf16": (2, 64, 64, 4, 2, 64, BF, True, None),
    # MHA heads narrower than 128 lanes share a 128-lane group
    "shared_lanes_hd64_causal_f32": (2, 64, 64, 4, 4, 64, F32, True,
                                     (2, 16, 32)),
    "shared_lanes_hd32_noncausal_bf16": (1, 64, 128, 8, 8, 32, BF, False,
                                         (8, 32, 64)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_flash_kernel_cases(name):
    b, s, t, h, kv, hd, dtype, causal, blocks = CASES[name]
    q, k, v = _qkv(b, s, t, h, kv, hd, dtype)
    got = flash_attention(q, k, v, causal=causal, blocks=blocks,
                          interpret=True)
    assert got.dtype == dtype and got.shape == q.shape
    want = attention(q, k, v, causal=causal, chunk=0)
    _close(got, want, dtype)


def test_flash_block_invariance():
    q, k, v = _qkv(1, 64, 64, 4, 2, 16)
    a = flash_attention(q, k, v, causal=True, blocks=(2, 64, 64),
                        interpret=True)
    b = flash_attention(q, k, v, causal=True, blocks=(1, 16, 32),
                        interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


def test_flash_bf16():
    q, k, v = _qkv(1, 64, 64, 4, 2, 32, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, blocks=(1, 32, 32),
                          interpret=True)
    want = attention(q, k, v, causal=True, chunk=0)
    _close(got, want, BF)


def test_flash_matches_chunked_path():
    q, k, v = _qkv(1, 128, 128, 4, 4, 16)
    got = flash_attention(q, k, v, causal=True, blocks=(2, 32, 64),
                          interpret=True)
    want = attention(q, k, v, causal=True, chunk=32)
    _close(got, want, F32)


def test_flash_long_context_block_skipping():
    """S << T: every query row walks every key tile, non-causal."""
    q, k, v = _qkv(1, 32, 256, 4, 4, 16)
    got = flash_attention(q, k, v, causal=False, blocks=(4, 32, 64),
                          interpret=True)
    want = attention(q, k, v, causal=False, chunk=0)
    _close(got, want, F32)


def test_key_tiles_stop_at_the_diagonal():
    # causal: whole tiles below the block, then its (bq, bq) diagonal
    assert fa._key_tiles(0, 128, 512, 512, True) == [(0, 128, True)]
    assert fa._key_tiles(384, 128, 256, 512, True) == [
        (0, 256, False), (256, 128, False), (384, 128, True)]
    # rows past the last key see every key, unmasked
    assert fa._key_tiles(256, 128, 512, 256, True) == [(0, 256, False)]
    assert fa._key_tiles(128, 128, 512, 512, False) == [(0, 512, False)]


# --- the dispatch predicate -------------------------------------------

OPT = ((8, 512, 32, 64), (8, 512, 32, 64), BF)            # opt-1.3b cell
GQA = ((2, 512, 32, 128), (2, 512, 8, 128), BF)           # qwen3-4b widths
RL = ((64, 128, 16, 64), (64, 128, 16, 64), F32)          # roberta-large


@pytest.mark.parametrize("case, causal", [(OPT, True), (GQA, True),
                                          (RL, False)],
                         ids=["opt", "gqa", "roberta_highest"])
def test_takes_aligned_unmasked_shapes(case, causal):
    q, k, dt = case
    with jax.default_matmul_precision("highest"):
        assert fa.takes(q, k, dt, causal=causal, backend="tpu")


@pytest.mark.parametrize("why", ["kv_mask", "head_dim", "unaligned_s",
                                 "unaligned_t", "backend", "dtype",
                                 "causal_t_not_s", "no_tiling_fits"])
def test_takes_refuses(why):
    q, k, dt = OPT
    kw = dict(backend="tpu")
    if why == "kv_mask":
        kw["kv_mask"] = jnp.ones((8, 512), bool)
    elif why == "head_dim":
        q, k = (8, 512, 32, 24), (8, 512, 32, 24)
    elif why == "unaligned_s":
        q = (8, 500, 32, 64)
    elif why == "unaligned_t":
        k = (8, 500, 32, 64)
    elif why == "backend":
        kw["backend"] = "cpu"
    elif why == "dtype":
        dt = jnp.float16
    elif why == "causal_t_not_s":
        k = (8, 1024, 32, 64)
    else:
        q, k = (2, 4096, 32, 128), (2, 4096, 8, 128)
    assert not fa.takes(q, k, dt, **kw)
    if why == "causal_t_not_s":
        assert fa.takes(q, k, dt, causal=False, **kw)


@pytest.mark.parametrize("case, causal, highest, want", [
    (OPT, True, False, (8, 128, 512)),
    (RL, False, True, (16, 128, 128)),
], ids=["opt", "roberta_highest"])
def test_picked_tiling_fits_and_is_lane_dense(case, causal, highest, want):
    (_, s, h, hd), (_, t, kvh, _), dt = case
    got = fa.attention_blocks(s, t, h, kvh, hd, dt, causal, highest)
    assert got == want
    hkv, bq, bk = got
    assert s % bq == 0 and t % bk == 0 and (hkv * hd) % 128 == 0
    assert fa.attention_vmem_bytes(hkv, h // kvh, hd, s, t, bq, dt, causal,
                                   highest) <= fa.VMEM_BUDGET


def _model_cfg(arch):
    from repro.configs import get_config
    return get_config(arch).reduced(d_model=128, n_heads=2, n_kv_heads=2,
                                    max_seq=128)


@pytest.mark.parametrize("arch", ["opt-1.3b", "roberta-large"])
def test_model_logits_agree_on_both_paths(arch, monkeypatch):
    """The same model's logits with the core on the jnp path (a CPU
    backend) and on the kernel (a TPU backend, steered here)."""
    from repro.models import build_model

    cfg = _model_cfg(arch)
    assert cfg.resolved_head_dim == 64
    model = build_model(cfg)
    p = model.init(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 128), 0,
                              cfg.vocab)
    before = obs.attention_cores()
    ref, _ = model.forward(p, {"tokens": toks})
    mid = obs.attention_cores()
    monkeypatch.setattr(ops, "BACKEND", "tpu")
    got, _ = model.forward(p, {"tokens": toks})
    after = obs.attention_cores()
    assert mid.get(obs.ATTN_JNP, 0) > before.get(obs.ATTN_JNP, 0)
    assert after.get(obs.ATTN_KERNEL, 0) > mid.get(obs.ATTN_KERNEL, 0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-3, atol=2e-3)

"""Per-architecture smoke tests (required deliverable f).

Each assigned arch instantiates a REDUCED config of the same family and
runs one forward + one MeZO train step + two decode steps on CPU,
asserting output shapes and no NaNs. Full configs are exercised only by
the dry-run.

Set REPRO_FAMILY=<family[,family]> to restrict the parametrized tests
to those families -- the CI family-matrix job runs one job per family so
a regression names itself in the job list.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, ARCHS, get_config
from repro.core import MezoConfig
from repro.models import build_model

_FAM = os.environ.get("REPRO_FAMILY")
SMOKE_ARCHS = [a for a in ALL_ARCHS
               if not _FAM or get_config(a).family in _FAM.split(",")]

B, S = 2, 16


def make_batch(cfg, key):
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
        "targets": jax.random.randint(key, (B, S), 0, cfg.vocab),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }
    if cfg.family == "encdec":
        batch["enc_embeds"] = jax.random.normal(
            key, (B, cfg.enc_len, cfg.d_model))
    if cfg.num_patches:
        batch["patch_embeds"] = jax.random.normal(
            key, (B, cfg.num_patches, cfg.d_model))
    if cfg.n_classes:
        batch["label"] = jnp.arange(B) % cfg.n_classes
    return batch


@pytest.mark.parametrize("arch", SMOKE_ARCHS)
def test_smoke_forward_and_train_step(arch, zo_step):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    batch = make_batch(cfg, key)

    logits, aux = model.forward(params, batch)
    if cfg.n_classes:
        assert logits.shape == (B, cfg.n_classes)
    else:
        assert logits.shape == (B, S, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    loss0 = float(model.loss(params, batch))
    assert np.isfinite(loss0)

    p2, maux = zo_step(model.loss, jax.tree.map(jnp.copy, params), batch,
                       jnp.uint32(0), MezoConfig(eps=1e-3, lr=1e-4))
    assert np.isfinite(float(maux.loss))
    for leaf in jax.tree.leaves(p2):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


@pytest.mark.parametrize("arch", [a for a in SMOKE_ARCHS
                                  if get_config(a).family != "encoder"])
def test_smoke_decode(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(B, 8)
    tok = jnp.zeros((B, 1), jnp.int32)
    lg, cache = model.decode_step(params, cache, tok, jnp.int32(0))
    lg, cache = model.decode_step(params, cache, tok, jnp.int32(1))
    assert lg.shape == (B, 1, cfg.vocab)
    assert np.isfinite(np.asarray(lg, np.float32)).all()


@pytest.mark.parametrize("arch", [a for a in ("qwen3-4b", "gemma-2b",
                                              "rwkv6-7b", "jamba-v0.1-52b",
                                              "granite-moe-1b-a400m")
                                  if a in SMOKE_ARCHS])
def test_decode_matches_forward(arch):
    """Step-by-step decode must reproduce the full-sequence forward."""
    cfg = get_config(arch).reduced()
    if cfg.n_experts:
        # capacity semantics differ between T=B*S and T=B token batches;
        # use generous capacity so nothing is dropped either way
        import dataclasses
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    T = 6
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab)
    full_logits, _ = model.forward(params, {"tokens": toks})

    cache = model.init_cache(B, T)
    outs = []
    for t in range(T):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1],
                                      jnp.int32(t))
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits, np.float32),
                               np.asarray(full_logits, np.float32),
                               rtol=2e-3, atol=2e-3)


def test_forward_last_only_matches_full():
    cfg = get_config("qwen3-4b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab)
    full, _ = model.forward(params, {"tokens": toks})
    last, _ = model.forward(params, {"tokens": toks}, last_only=True)
    np.testing.assert_allclose(np.asarray(last[:, 0], np.float32),
                               np.asarray(full[:, -1], np.float32),
                               rtol=1e-4, atol=1e-5)


def test_assigned_configs_exact_values():
    """The 10 assigned architectures carry the exact assigned dims."""
    expect = {
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "glm4-9b": (40, 4096, 32, 2, 13696, 151552),
        "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024),
        "gemma-2b": (18, 2048, 8, 1, 16384, 256000),
        "pixtral-12b": (40, 5120, 32, 8, 14336, 131072),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163840),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "rwkv6-7b": (32, 4096, 0, 0, 14336, 65536),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
    }
    for arch, (nl, dm, nh, kv, dff, v) in expect.items():
        cfg = get_config(arch)
        assert cfg.n_layers == nl and cfg.d_model == dm, arch
        assert cfg.n_heads == nh and cfg.n_kv_heads == kv, arch
        assert cfg.d_ff == dff and cfg.vocab == v, arch
    assert get_config("kimi-k2-1t-a32b").n_experts == 384
    assert get_config("kimi-k2-1t-a32b").topk == 8
    assert get_config("granite-moe-1b-a400m").n_experts == 32
    assert get_config("jamba-v0.1-52b").n_experts == 16
    assert get_config("gemma-2b").head_dim == 256
    assert get_config("qwen3-4b").qk_norm

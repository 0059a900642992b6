#!/usr/bin/env python3
"""Bring-up smoke run of the main path on one TPU, at opt-1.3b width.

    python chip_smoke.py

One process holds the chip and drives the real entry points in-process,
on random weights made from seed 0:

  train    ``repro.launch.train``: the fused ZO step with the zo_matmul
           kernel, 4 steps at batch 8 x seq 512, replay log in .smoke/;
           then both train cells' steps traced for the kernel's weight
           reads, which must all come in place from the layer stacks
  serve    ``repro.launch.serve``: that replay log as one user's adapter,
           paged KV + chunked prefill + speculative decode, 8 requests
           over 4 slots, prompt 256, gen 32
  kernels  each main-path Pallas kernel at opt-1.3b shapes against its
           float32 jnp reference

Every phase must pass: losses finite, every request answered with
exactly ``gen`` tokens, each kernel within ``KERNEL_TOL`` of its
reference, and the compiled train step and serving programs holding a
``tpu_custom_call`` (the Pallas kernels ran, not the jnp references that
CPU runs take). The script exits non-zero when JAX finds no TPU or any
check fails; otherwise its last stdout line is one JSON object naming the
device. Times, peak HBM and kernel errors printed on the way are
bring-up observations, not benchmark numbers.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".smoke")
ARCH = "opt-1.3b"
USER = "smoke"
# rel-L2 of a kernel's output against its f32 reference: bf16 outputs
# round at 2**-9 ~ 2e-3, so 1e-2 passes rounding and fails a wrong tile
KERNEL_TOL = 1e-2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def peak_hbm():
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def train_phase(ckpt_dir: str, *, reduced: bool = False, steps: int = 4,
                batch: int = 8, seq: int = 512) -> dict:
    """Fine-tune through the training CLI; returns the losses and
    whether the compiled step holds a Pallas kernel."""
    import jax
    import jax.numpy as jnp

    from repro.core import rng as zrng
    from repro.launch import train

    shutil.rmtree(ckpt_dir, ignore_errors=True)    # fresh run, no resume
    argv = ["--arch", ARCH, "--estimator", "fused", "--update", "sgd",
            "--use-kernel", "--steps", str(steps), "--batch", str(batch),
            "--seq", str(seq), "--ckpt-dir", ckpt_dir, "--log-every", "1"]
    tr = train.main(argv + (["--reduced"] if reduced else []))
    if len(tr.losses) != steps or not all(map(math.isfinite, tr.losses)):
        raise AssertionError(f"train: bad losses {tr.losses}")

    # the step program at the shapes the trainer ran
    params = jax.eval_shape(tr.model.init, jax.random.PRNGKey(0))
    state = tr.strategy.init_state(params, tr.tcfg.mezo)
    batch_arrays = {k: jnp.asarray(v) for k, v in next(tr.batches).items()}
    text = tr.strategy.lower(
        tr.model.loss, state, batch_arrays, zrng.fold_seed(jnp.uint32(0), 0),
        tr.tcfg.mezo).compile().as_text()
    return {"losses": tr.losses,
            "kernels": {"train_step": "tpu_custom_call" in text}}


def step_weight_reads(arch: str, batch: int, seq: int) -> dict:
    """zo_matmul weight reads, by path, counted while tracing the fused
    step (``--use-kernel``) of ``arch`` at full widths on abstract
    inputs: nothing is allocated or compiled."""
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.configs import get_config
    from repro.core.engine import MezoConfig, build_strategy
    from repro.models import build_model

    cfg = get_config(arch)
    model = build_model(cfg)
    strategy = build_strategy("fused", "sgd")
    mezo = MezoConfig(use_kernel=True)
    state = jax.eval_shape(lambda: strategy.init_state(
        model.init(jax.random.PRNGKey(0)), mezo))
    rows = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    labels = {"label": jax.ShapeDtypeStruct((batch,), jnp.int32)} \
        if cfg.n_classes else {"targets": rows}
    before = obs.zo_matmul_weights()
    strategy.lower(lambda *a, **k: model.loss(*a, **k), state,
                   {"tokens": rows, **labels}, jnp.uint32(0), mezo)
    after = obs.zo_matmul_weights()
    return {k: n - before.get(k, 0) for k, n in after.items()
            if n != before.get(k, 0)}


def serve_phase(ckpt_dir: str, *, reduced: bool = False, requests: int = 8,
                slots: int = 4, prompt_len: int = 256, gen: int = 32) -> dict:
    """Serve the trained adapter through the serving CLI; returns the
    completions, engine times and whether the decode (draft), verify and
    prefill programs hold a Pallas kernel."""
    import jax.numpy as jnp

    from repro.launch import serve

    argv = ["--arch", ARCH, "--adapter", f"{USER}={ckpt_dir}", "--paged",
            "--page-size", "16", "--prefill-chunk", "64", "--spec-k", "4",
            "--requests", str(requests), "--slots", str(slots),
            "--prompt-len", str(prompt_len), "--gen", str(gen)]
    engine, done = serve.main(argv + (["--reduced"] if reduced else []))
    if len(done) != requests or any(c.tokens.size != gen for c in done):
        raise AssertionError(
            f"serve: want {requests} x {gen} tokens, got "
            f"{[c.tokens.size for c in done]}")

    # the engine's jitted programs at the shapes this run used: decode
    # windows span every page a slot may hold, prefill chunks the prompt
    fns = engine._fns
    b, w = engine.n_slots, engine.spec_k + 1
    c = min(engine.prefill_chunk, prompt_len)
    n_prompt = 1
    while n_prompt * engine.page_size < prompt_len:
        n_prompt *= 2
    n_prompt = min(n_prompt, engine.slot_pages)
    base = engine.store.materialize(None)
    tuned = engine.store.materialize(USER)

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)

    programs = {
        "decode": fns["draft_spec"].lower(
            base, engine.cache, i32(b), i32(b), i32(b, engine.slot_pages),
            i32(b), engine.spec_k),
        "verify": fns["verify_spec"].lower(
            tuned, engine.cache, i32(b, w), i32(b), i32(b, engine.slot_pages),
            jnp.ones((b, w), bool)),
        "prefill": fns["prefill_chunk"].lower(
            tuned, engine.cache, i32(1, c), i32(1), i32(1, n_prompt),
            jnp.int32(0)),
    }
    kernels = {f"serve_{k}": "tpu_custom_call" in v.compile().as_text()
               for k, v in programs.items()}
    st = engine.stats
    return {"completions": done, "prefill_s": st.prefill_s, "decode_s": st.decode_s,
            "spec_accept_rate": st.spec_accept_rate,
            "adapter_materialize_s": engine.store.stats["materialize_s"],
            "kernels": kernels}


def _errors(got, want):
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    rel = float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))
    return float(np.max(np.abs(got - want))), rel


def kernels_phase(seed: int = 0) -> None:
    """Each main-path kernel at opt-1.3b shapes on the chip against its
    f32 jnp reference: raises past ``KERNEL_TOL`` or without a
    ``tpu_custom_call`` in the compiled kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels import ref
    from repro.kernels import zo_perturb as zp
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_decode import flash_decode, paged_attn_ref
    from repro.kernels.flash_prefill import flash_prefill, prefill_attn_ref
    from repro.kernels.flash_verify import flash_verify, verify_attn_ref
    from repro.models.layers import attention

    cfg = get_config(ARCH)
    d, f = cfg.d_model, cfg.d_ff
    kvh, h, hd = cfg.n_kv_heads, cfg.n_heads, cfg.resolved_head_dim
    ps, slots, n_live = 16, 4, 18               # the serve phase's pool
    rng = np.random.default_rng(seed)
    bf, f32 = jnp.bfloat16, jnp.float32

    def normal(*shape, dtype=bf):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    x = normal(512, d)
    w = normal(d, f) * 0.02
    q8 = jnp.asarray(rng.integers(-127, 128, (d, f)), jnp.int8)
    scale = jnp.full((f,), 2.0 ** -9, f32)      # power of two: exact dequant
    wq = q8.astype(f32) * scale
    xu = normal(4, 512, d)
    seeds = jnp.asarray([3, 5, 7, 11], jnp.uint32)
    coeffs = jnp.asarray([1e-3, -1e-3, 2e-3, -2e-3], f32)
    seed1, coeff = jnp.uint32(1), f32(1e-3)

    def zo_ref(xx, ww, s, cc):
        return ref.zo_matmul_ref(xx.astype(f32), ww.astype(f32), s, 0, cc)

    xa, ka, va = (normal(2, 512, h, hd), normal(2, 512, kvh, hd),
                  normal(2, 512, kvh, hd))         # the train step's core

    n_pages = slots * n_live + 1                 # page 0 is the trash page
    kp = normal(n_pages, ps, kvh, hd)
    vp = normal(n_pages, ps, kvh, hd)
    pages = jnp.asarray(1 + rng.permutation(n_pages - 1).reshape(
        slots, n_live), jnp.int32)

    def paged(kernel, reference, q, pos, pg):
        return (kernel, (q, kp, vp, pg, pos),
                lambda: reference(q.astype(f32), kp.astype(f32),
                                  vp.astype(f32), pg, pos))

    # name -> (kernel over arrays, its arguments, f32 reference thunk)
    cases = {
        "zo_matmul_bf16": (
            lambda a, b, s, c: zp.zo_matmul(a, b, s, 0, c),
            (x, w, seed1, coeff), lambda: zo_ref(x, w, seed1, coeff)),
        "zo_matmul_int8": (
            lambda a, b, s, c, sc: zp.zo_matmul(a, b, s, 0, c, scale=sc),
            (x, q8, seed1, coeff, scale), lambda: zo_ref(x, wq, seed1, coeff)),
        "zo_matmul_users_int8": (
            lambda a, b, s, c, sc: zp.zo_matmul_users(a, b, s, 0, c,
                                                      scale=sc),
            (xu, q8, seeds, coeffs, scale),
            lambda: jnp.stack([zo_ref(xu[i], wq, seeds[i], coeffs[i])
                               for i in range(4)])),
        "zo_add_f32": (
            lambda b, s, c: zp.zo_add(b, s, 0, c),
            (w.astype(f32), seed1, coeff),
            lambda: ref.zo_add_ref(w.astype(f32), seed1, 0, coeff)),
        "zo_add_int8": (
            lambda b, s, c, sc: zp.zo_add(b, s, 0, c, scale=sc),
            (q8, seed1, coeff, scale),
            lambda: ref.zo_add_ref(wq, seed1, 0, coeff)),
        "flash_decode": paged(
            flash_decode, paged_attn_ref, normal(slots, h, hd),
            jnp.asarray([0, ps - 1, ps, n_live * ps - 1], jnp.int32), pages),
        "flash_verify": paged(
            flash_verify, verify_attn_ref, normal(slots, 5, h, hd),
            jnp.asarray([0, ps - 2, 100, n_live * ps - 5], jnp.int32), pages),
        "flash_prefill": paged(
            flash_prefill, prefill_attn_ref, normal(2, 64, h, hd),
            jnp.asarray([0, 192], jnp.int32), pages[:2]),
        "flash_attention": (
            lambda a, b, c: flash_attention(a, b, c, causal=True),
            (xa, ka, va),
            lambda: attention(xa.astype(f32), ka.astype(f32),
                              va.astype(f32), causal=True)),
    }
    for name, (kernel, args, reference) in cases.items():
        compiled = jax.jit(kernel).lower(*args).compile()
        got = jax.block_until_ready(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = reference()
        max_abs, rel = _errors(got, want)
        custom = "tpu_custom_call" in compiled.as_text()
        log(f"kernel {name}: max_abs={max_abs!r} rel_l2={rel!r} "
            f"tpu_custom_call={custom}")
        if not (rel <= KERNEL_TOL and custom):
            raise AssertionError(f"kernel {name}: rel_l2 {rel!r} > "
                                 f"{KERNEL_TOL} or no tpu_custom_call")


def compile_totals():
    """Backend compiles (or persistent-cache loads) so far and their
    seconds, from the program's own counter."""
    from repro import obs
    per_fun = obs.compiles().values()
    return sum(n for n, _ in per_fun), sum(s for _, s in per_fun)


def timed(name: str, fn, *args):
    (n0, c0), t0 = compile_totals(), time.perf_counter()
    out = fn(*args)
    n1, c1 = compile_totals()
    log(f"{name}: wall {time.perf_counter() - t0!r}s, of which {n1 - n0} "
        f"backend compiles or cache loads {c1 - c0!r}s | peak HBM "
        f"{peak_hbm()} B")
    return out


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is on platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import compile_cache

    log(f"device {dev.device_kind!r} x{len(jax.devices())}, compile cache "
        f"{compile_cache.enable()}")
    ckpt = os.path.join(WORK, "ckpt")

    t = timed("train", train_phase, ckpt)
    from repro import obs
    # both train cells' steps: two perturbed forwards, each reading its
    # 6 projections per layer body in place from the stacks; the heads
    # are unaligned and take the jnp path
    reads = {arch: step_weight_reads(arch, b, s) for arch, b, s in
             (("opt-1.3b", 8, 512), ("roberta-large", 64, 128))}
    log(f"train: losses {t['losses']} | attention cores traced, by path: "
        f"{obs.attention_cores()} | zo_matmul weight reads traced per "
        f"step, by path: {reads}")
    if any(r != {obs.W_STACKED: 12} for r in reads.values()):
        raise AssertionError(f"train: weight reads {reads}, want "
                             f"{{{obs.W_STACKED!r}: 12}} per step")
    s = timed("serve", serve_phase, ckpt)
    log(f"serve: {len(s['completions'])} requests | engine prefill "
        f"{s['prefill_s']!r}s, decode {s['decode_s']!r}s (first calls "
        f"compile), adapter replay {s['adapter_materialize_s']!r}s | spec "
        f"acceptance {s['spec_accept_rate']!r}")
    timed("kernels", kernels_phase)

    programs = {**t["kernels"], **s["kernels"]}
    log(f"tpu_custom_call in {programs}")
    if not all(programs.values()):
        raise AssertionError(f"programs without a Pallas kernel: {programs}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

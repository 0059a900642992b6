"""Train cells: the fused ZO fine-tune step, as ``runtime.trainer``
drives it, timed over a window of steps.

Set-up builds one :class:`~repro.runtime.trainer.Trainer` (fused
estimator, sgd update, the ``zo_matmul`` kernel, replay log on) over
weights made from the seed, and drives it through its first
``check_steps`` steps; those compile the step and are what the output
check compares. The same trainer state then runs the window. Each step
is the trainer's loop body: next batch from the generator, the fused
step, the replay-log append, and ``block_until_ready``. Steps are
numbered from 1, so the checkpoint manager's parameter snapshot (taken
at step 0 and every ``snapshot_every`` steps) stays out of every run.

After the window the program's state is freed and the reference
(``bench/reference``) repeats the first steps in float32 from the same
weights and batches.
"""

from __future__ import annotations

import gc
import math
import shutil
import time
from typing import Any, Dict

import numpy as np

from bench.harness import traffic as T
from bench.harness.check import Check, rel_gap, worst_leaf_gap
from bench.harness.weights import leaves_of, make_leaf, make_params


def model_config(config: Dict[str, Any]):
    from repro.models.config import ModelConfig
    return ModelConfig(**config["model"])


def _leaf_norms_vs_start(params, shapes, key32: int, with_rms=False):
    """Per leaf: ||params - start|| with the start regenerated leaf by
    leaf from the seed (float32 norms); with ``with_rms`` also each
    start leaf's root mean square."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        b = b.astype(jnp.float32)
        return (jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b))),
                jnp.sqrt(jnp.mean(jnp.square(b))))

    key = jax.random.PRNGKey(key32)
    flat = jax.tree_util.tree_leaves(params)
    out, rms = {}, {}
    for i, ((path, s), leaf) in enumerate(zip(leaves_of(shapes), flat)):
        start = make_leaf(key, i, path, tuple(s.shape),
                          jnp.dtype(s.dtype).name)
        d, r = norms(leaf, start)
        out[path], rms[path] = float(d), float(r)
        del start
    return (out, rms) if with_rms else out


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.core import rng as zrng
    from repro.core.engine import MezoConfig
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = model_config(ctx.config)
    tr = ctx.cell["traffic"]
    n_check = int(ctx.cell["check"]["steps"])
    mezo = MezoConfig(lr=tr["lr"], eps=tr["eps"], use_kernel=True)
    train_seed = T.seed32(ctx.seed, 1)
    weights_key = T.seed32(ctx.seed, 0)
    tokens_per_step = tr["batch"] * tr["seq"]
    ckpt = ctx.work("train", "ckpt")
    shutil.rmtree(ckpt)

    trainer = Trainer(
        cfg, TrainerConfig(estimator="fused", update="sgd", mezo=mezo,
                           seed=train_seed, ckpt_dir=ckpt,
                           snapshot_every=2 ** 62, log_every=2 ** 62),
        batches=T.train_batches(tr, cfg.vocab, ctx.seed, first_step=1),
        log_fn=ctx.log)
    shapes = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    params = make_params(shapes, weights_key)
    state = trainer.strategy.init_state(params, mezo)
    del params

    def one_step(state, step):
        with ctx.span("train.batch"):
            batch = {k: jnp.asarray(v)
                     for k, v in next(trainer.batches).items()}
            seed = zrng.fold_seed(jnp.uint32(train_seed), step)
        with ctx.span("train.step"):
            state, aux = trainer.strategy.step(
                trainer.model.loss, state, batch, seed, mezo, None)
        with ctx.span("train.replay_log"):
            trainer.ckpt.on_step(step, state, aux)
        with ctx.span("train.sync"):
            jax.block_until_ready(state)
        return state, aux

    # set-up: the first steps compile the step and feed the check
    prog = {"loss": [], "gs": []}
    step = 1
    for _ in range(n_check):
        state, aux = one_step(state, step)
        prog["loss"].append(float(aux.loss))
        prog["gs"].append(float(np.asarray(aux.gs)[0]))
        step += 1
    prog_delta = _leaf_norms_vs_start(state.params, shapes, weights_key)
    setup_s = ctx.elapsed()
    c0 = ctx.clock.snapshot()
    ctx.log(f"set-up {setup_s!r}s; check steps: loss {prog['loss']} "
            f"gs {prog['gs']}")

    # the window
    n_steps = 0
    tracer = None
    if ctx.trace:
        from bench.harness.context import profile_options
        tracer = jax.profiler.trace(ctx.work("trace"),
                                    profiler_options=profile_options())
        tracer.__enter__()
    with ctx.span("train.window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds:
            state, aux = one_step(state, step)
            step += 1
            n_steps += 1
        t1 = time.perf_counter()
    if tracer is not None:
        tracer.__exit__(None, None, None)
    c1 = ctx.clock.snapshot()
    window_s = t1 - t0
    last_loss = float(aux.loss)

    from bench.harness.device import peak_bytes
    peak = peak_bytes(ctx.devs)
    del state, aux, trainer
    gc.collect()

    check = Check(ctx.cell["check"]["limits"])
    ref = reference(cfg, tr, ctx.seed, train_seed, weights_key, shapes,
                    n_check)
    prog = {"lp": [l + tr["eps"] * g for l, g in zip(prog["loss"],
                                                     prog["gs"])],
            "lm": [l - tr["eps"] * g for l, g in zip(prog["loss"],
                                                     prog["gs"])],
            "gs": prog["gs"], "delta": prog_delta}
    for name, value in readings(prog, ref, shapes, tr["lr"],
                                ctx.log).items():
        check.add(name, value)

    return {
        "e2e": {"setup_s": setup_s,
                "train_tok_s": n_steps * tokens_per_step / window_s,
                "peak_hbm_gib": peak / 2 ** 30},
        "peak_bytes": peak,
        "attempted": n_steps,
        "failed": 0 if math.isfinite(last_loss) else n_steps,
        "check": check,
        "window_s": window_s,
        "compiles_in_window": [c1[0] - c0[0], c1[1] - c0[1]],
        "train": {"steps": n_steps, "tokens_per_step": tokens_per_step,
                  "batch": tr["batch"], "seq": tr["seq"]},
        "trace_dir": ctx.work("trace") if ctx.trace else None,
    }


def readings(prog, ref, shapes, lr: float, log=None) -> Dict[str, float]:
    """The numbers compared: the worst relative gap of a perturbed loss
    over the steps, and of a leaf's change over the steps
    (``check.worst_leaf_gap``).

    A leaf whose every update is under half a unit in the last place of
    its storage dtype at its typical size moves by round-off alone: its
    change flips with the last digits of gs. Such leaves are left out of
    the change by a rule on the reference's gs and the leaf's dtype; at
    opt-1.3b that is every bf16 leaf, and the float32 LayerNorm leaves
    remain. The first step's gradient, gs * z, is logged beside them and
    not compared: its gap is that of a difference of two rounded losses,
    which swings from seed to seed (PERF.md)."""
    import jax.numpy as jnp

    out = {"loss": max(
        [rel_gap(a, b) for a, b in zip(prog["lp"], ref["lp"])]
        + [rel_gap(a, b) for a, b in zip(prog["lm"], ref["lm"])])}
    step = lr * max(abs(g) for g in ref["gs"])
    moves = {p for p, s in leaves_of(shapes)
             if step >= 0.5 * float(jnp.finfo(s.dtype).eps) * ref["rms"][p]}
    grad_norm = {p: abs(ref["gs"][0]) * math.sqrt(int(np.prod(s.shape)))
                 for p, s in leaves_of(shapes) if p in moves}
    out["delta"], leaf, n_out = worst_leaf_gap(
        {p: prog["delta"][p] for p in moves},
        {p: ref["delta"][p] for p in moves}, grad_norm)
    if log:
        log(f"program: lp {prog['lp']} lm {prog['lm']} gs {prog['gs']}")
        log(f"reference: lp {ref['lp']} lm {ref['lm']} gs {ref['gs']}; "
            f"first gradient gap {rel_gap(abs(prog['gs'][0]), abs(ref['gs'][0]))!r}; "
            f"{len(moves)} leaves move past round-off, worst delta leaf "
            f"{leaf} ({n_out} left out by the gradient rule)")
    return out


def control(ctx, record=None) -> Dict[str, float]:
    """The control of the output check: the reference computed with the
    matrix products one precision below the model's (float8 for bf16,
    three-pass HIGH for f32 at HIGHEST) in the program's place, read
    against the float32 reference."""
    import jax

    from bench.reference import forward
    from repro.models import build_model

    cfg = model_config(ctx.config)
    tr = ctx.cell["traffic"]
    n = int(ctx.cell["check"]["steps"])
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    args = (cfg, tr, ctx.seed, T.seed32(ctx.seed, 1), T.seed32(ctx.seed, 0),
            shapes, n)
    ref = reference(*args)
    low = reference(*args, mm=forward.control_mm(cfg.dtype))
    return readings(low, ref, shapes, tr["lr"])


def reference(cfg, tr, seed: int, train_seed: int, weights_key: int,
              shapes, n_steps: int, mm=None) -> Dict[str, Any]:
    """The first ``n_steps`` steps in float32 from the same weights and
    batches: perturbed losses, gs, and each leaf's change. ``mm``
    replaces the float32 matrix product (the control)."""
    import jax
    import jax.numpy as jnp

    from bench.reference import zo

    params = make_params(shapes, weights_key)
    batches = [{k: jnp.asarray(v) for k, v in
                T.train_batch(tr, cfg.vocab, seed, step).items()}
               for step in range(1, n_steps + 1)]
    out = zo.zo_steps(params, batches, train_seed, 1, tr["lr"], tr["eps"],
                      tr["task"], cfg.n_heads,
                      **({} if mm is None else {"mm": mm}))
    out["delta"], out["rms"] = _leaf_norms_vs_start(
        out.pop("params"), shapes, weights_key, with_rms=True)
    return out

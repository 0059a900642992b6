"""Training launcher (the paper's end-to-end flow, cluster-shaped).

On real hardware this runs under ``jax.distributed.initialize`` with the
production mesh; on this CPU container it runs reduced configs single-
device (examples/quickstart.py) -- same code path, smaller shapes.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
      --optimizer mezo --steps 200 --batch 8 --seq 64

``--optimizer mezo`` (the default) trains with the ZO step
``build_strategy(--estimator, --update)``, fused + sgd unless the flags
say otherwise, e.g.

  ... --estimator fused --update momentum --momentum 0.9

``--optimizer adam`` runs the gradient baseline.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

import numpy as np

from repro.configs import ALL_ARCHS, get_config
from repro.core.engine import estimator_names, update_rule_names
from repro.core.mezo import MezoConfig
from repro.data.synthetic import lm_batches, sst2_batches
from repro.launch import compile_cache
from repro.optim.adam import AdamConfig
from repro.runtime.trainer import Trainer, TrainerConfig


def make_trainer(args) -> Trainer:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.seq and cfg.family != "encoder":
        cfg = dataclasses.replace(cfg, max_seq=max(cfg.max_seq, args.seq))

    if cfg.n_classes:
        batches = sst2_batches(args.batch, args.seq or 64, cfg.vocab,
                               seed=args.seed)
    else:
        batches = lm_batches(args.batch, args.seq or 64, cfg.vocab,
                             seed=args.seed)
        if cfg.family == "encdec" or cfg.num_patches:
            base = batches

            def with_frontend_stub(it=base):
                rng = np.random.default_rng(args.seed + 7)
                for b in it:
                    if cfg.family == "encdec":
                        b["enc_embeds"] = rng.standard_normal(
                            (args.batch, cfg.enc_len, cfg.d_model),
                            dtype=np.float32)
                    if cfg.num_patches:
                        b["patch_embeds"] = rng.standard_normal(
                            (args.batch, cfg.num_patches, cfg.d_model),
                            dtype=np.float32)
                    yield b
            batches = with_frontend_stub()

    tcfg = TrainerConfig(
        optimizer=args.optimizer,
        estimator=args.estimator, update=args.update,
        quant=args.quant,
        mezo=MezoConfig(eps=args.eps, lr=args.lr,
                        n_directions=args.directions, dist=args.zo_dist,
                        use_kernel=args.use_kernel,
                        momentum=args.momentum,
                        momentum_window=args.momentum_window,
                        weight_decay=args.weight_decay),
        adam=AdamConfig(lr=args.adam_lr),
        n_steps=args.steps, seed=args.seed, ckpt_dir=args.ckpt_dir,
        snapshot_every=args.snapshot_every, log_every=args.log_every,
        straggler_redundancy=args.straggler_redundancy)
    return Trainer(cfg, tcfg, batches)


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt-1.3b", choices=ALL_ARCHS)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config of the same family")
    ap.add_argument("--optimizer", default="mezo", choices=["mezo", "adam"],
                    help="mezo (the ZO step) or adam (gradient baseline)")
    ap.add_argument("--estimator", default="fused",
                    choices=estimator_names(),
                    help="ZO direction evaluator (vmapdir: the "
                         "materialized reference)")
    ap.add_argument("--update", default="sgd", choices=update_rule_names(),
                    help="ZO update rule applied to the (seed, gs) "
                         "estimate")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--eps", type=float, default=1e-3)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--adam-lr", type=float, default=1e-4)
    ap.add_argument("--directions", type=int, default=1)
    ap.add_argument("--momentum", type=float, default=0.9,
                    help="ZO momentum beta (momentum update rule only)")
    ap.add_argument("--momentum-window", type=int, default=8,
                    help="steps of (seed, gs) history the truncated "
                         "seed-replay momentum keeps")
    ap.add_argument("--weight-decay", type=float, default=0.0)
    ap.add_argument("--zo-dist", default="rademacher",
                    choices=["rademacher", "gaussian"])
    ap.add_argument("--quant", default="none",
                    help="base-weight quantization mode (none | int8): "
                         "int8 freezes the base as int8 + per-channel "
                         "scales with dequant fused into the perturbed-"
                         "forward kernels; the ZO update stream lands in "
                         "per-leaf f32 deltas. Validated by the trainer "
                         "(unknown modes raise with the supported list)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route MXU-aligned leaves/projections through the "
                         "Pallas ZO kernels (zo_add, and zo_matmul for the "
                         "fused estimator). TPU-oriented: on CPU the kernels "
                         "run in slow interpret mode")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--snapshot-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--straggler-redundancy", type=int, default=0)
    ap.add_argument("--metrics-out", default=None)
    return ap


def main(argv=None) -> Trainer:
    """Run the CLI on ``argv`` (default ``sys.argv[1:]``); returns the
    finished Trainer, whose ``losses`` hold every step's loss."""
    args = build_argparser().parse_args(argv)
    compile_cache.enable()

    tr = make_trainer(args)
    params = tr.train()
    del params
    if args.metrics_out:
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump({"arch": args.arch, "optimizer": args.optimizer,
                       "losses": tr.losses}, f)
    print(f"[train] done: loss {tr.losses[0]:.4f} -> {tr.losses[-1]:.4f} "
          f"({len(tr.losses)} steps)")
    return tr


if __name__ == "__main__":
    main()

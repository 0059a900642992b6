#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds the system under test (``src/``).
Set-up makes the cell's weights and inputs from ``--seed``, warms every
shape the window uses, then the window measures for ``--seconds``. With
``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. Every run compares what the timed path produced with the plain
reference in ``bench/reference``; the numbers compared, each beside its
limit, are the last lines on standard error and the last key of the
line. The last line of standard output is one JSON object.

Exits non-zero, printing no result, when JAX's first device is not a
TPU or there are fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
WORK_DIR = os.path.join(ROOT, ".bench_cache", "work")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="serve cells: offered requests/s instead of the "
                         "cell's own (used by the knee sweep only)")
    return ap.parse_args(argv)


def setup_jax():
    """Persistent compilation cache at a fixed path inside the checkout,
    for the benchmark and for the system (which takes the directory
    from ``JAX_COMPILATION_CACHE_DIR``); every program is cached."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.makedirs(CACHE_DIR, exist_ok=True)
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def apply_config(jax, config) -> None:
    """What a configuration file states about how its model computes:
    ``matmul_precision`` (JAX's default for float32 products)."""
    if config.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          config["matmul_precision"])


def main(argv=None) -> int:
    args = parse(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness import device as D
    from bench.harness import result
    from bench.harness.context import Ctx
    from bench.harness.spec import Bench

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    apply_config(setup_jax(), config)
    try:
        devs = D.require_tpu(cell["chips"])
        peaks = D.peaks(devs[0].device_kind)
    except (D.NoChip, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    ctx = Ctx(cell=cell, config=config, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              t_start=T_START, work_dir=WORK_DIR, devs=devs,
              clock=D.CompileClock().install(), rate=args.rate)
    ctx.log(f"{cell['name']} on {D.describe(devs)}, seed {args.seed}, "
            f"{args.seconds}s, trace {args.trace}")
    record = bench.driver(cell["driver"]).run(ctx)
    line = result.build(bench, ctx, record, peaks)
    for msg in record["check"].lines():
        print(msg, file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

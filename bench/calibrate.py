#!/usr/bin/env python3
"""Readings that the limits of a cell's output check are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

In one process, for each seed: one run of the cell (its window as
short as ``--seconds``) gives the program's readings, and the cell
driver's ``control`` the control's: the reference computed one
precision below the model's in the program's place. Prints one JSON line per seed;
needs the chip the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [os.path.join(os.path.dirname(here), "src"),
                    os.path.dirname(here)]
    from bench import run as R
    from bench.harness import device as D
    from bench.harness.context import Ctx
    from bench.harness.spec import Bench

    bench = Bench(R.ROOT)
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    R.apply_config(R.setup_jax(), config)
    try:
        devs = D.require_tpu(cell["chips"])
    except D.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    driver = bench.driver(cell["driver"])
    clock = D.CompileClock().install()
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = Ctx(cell=cell, config=config, seed=seed, seconds=args.seconds,
                  trace=False, t_start=time.perf_counter(),
                  work_dir=R.WORK_DIR, devs=devs, clock=clock)
        record = driver.run(ctx)
        prog = record["check"].readings
        ctl = driver.control(ctx, record)
        print(json.dumps({"seed": seed, "program": prog, "control": ctl,
                          "e2e": record["e2e"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

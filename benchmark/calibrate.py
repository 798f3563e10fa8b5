#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip at
the cell's own size, in one process: the timed program on a dozen seeds
or more, and the control (the reference one precision below the
configuration, in the program's place) on three or more.  The runs of
the benchmark itself never run this.

    python3 benchmark/calibrate.py --workload hpl.f64.n8192 \
        --seeds 101 102 ... --control-seeds 201 202 203

Each line is one reading: {"who": "program"|"control", "seed", numbers}.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

import harness  # noqa: E402
import reference  # noqa: E402


def library(cell, run, args, say):
    cfg = cell.config
    for s in args.seeds:
        run.reseed(s)
        for k in range(run.K):
            X = run.call(k)
            X.block_until_ready()
            run.outs.append((k, X))
        checks, _n, _f = run.check()
        say({"who": "program", "seed": s,
             **{name: v for name, v, _l, _ok in checks}})
    run.ops = None
    control = cell.routine().control
    for s in args.control_seeds:
        worst = {"residual": 0.0, "gap": 0.0}
        for k in range(run.K):
            A, B = reference.host_operands(cfg, run.n, run.nrhs, s, k)
            X = np.asarray(control(cfg, A, B), np.float64)
            got = reference.numbers(cfg, A, X, B, reference.solve(A, B))
            worst = {m: max(worst[m], got[m]) for m in worst}
        say({"who": "control", "seed": s, **worst})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = harness.Cell(args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax

    devices = harness.check_device(cell.chips)[: cell.chips]
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    def say(d):
        print(json.dumps({"cell": cell.name, **d}), flush=True)

    run = cell.driver().Run(cell, (args.seeds or args.control_seeds)[0],
                            devices)
    run.setup()
    library(cell, run, args, say)
    return 0


if __name__ == "__main__":
    sys.exit(main())

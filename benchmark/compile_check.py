#!/usr/bin/env python3
"""Compile-only rehearsal, on the CPU host with no chip: each cell's timed
program compiled at its real size for a described v5e, with the
fullest device's bytes from ``memory_analysis()``.  Nothing runs, so
nothing here is a chip number.

    JAX_PLATFORMS=cpu python3 benchmark/compile_check.py [cell ...]
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402

HBM = 15.75 * 2**30  # what a v5e chip offers a program (PR 21)


def programs(cell, topo, jax, jnp, st):
    """(name, fn, arg specs) of the cell's timed programs."""
    from jax.sharding import SingleDeviceSharding

    cfg = cell.config
    dt = jnp.dtype(cfg["dtype"])
    one = SingleDeviceSharding(topo.devices[0])
    drv = cell.driver()
    n, nrhs = cfg["n"], cfg["nrhs"]
    specs = (jax.ShapeDtypeStruct((n, n), dt, sharding=one),
             jax.ShapeDtypeStruct((n, nrhs), dt, sharding=one))
    return [("solve", drv.solve_fn(st, cfg, drv.options(st, cfg)), specs)]


def main(names) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_enable_compilation_cache", False)
    import slate_tpu as st

    # the schedule routers ask the backend: make them take the TPU branch
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = harness.benchmark()
    for w in bench["workloads"]:
        if names and w["name"] not in names:
            continue
        cell = harness.Cell(w["name"])
        for label, fn, specs in programs(cell, topo, jax, jnp, st):
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(*specs).compile()
            ma = compiled.memory_analysis()
            text = compiled.as_text()
            total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
            print(json.dumps({
                "cell": w["name"], "program": label,
                "compile_s": time.perf_counter() - t0,
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "bytes_per_device": total,
                "share_of_15.75GiB": total / HBM,
                "mosaic_calls": text.count("tpu_custom_call"),
                "collectives": sum(text.count(c) for c in (
                    "all-reduce", "all-gather", "collective-permute",
                    "all-to-all", "reduce-scatter")),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

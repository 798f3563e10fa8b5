#!/usr/bin/env python3
"""Is a change metadata-only?  Compiles one benchmark cell's timed program
from two checkouts and compares the optimized HLO texts with ``metadata=``
and the source-location tables (``FileNames`` ... ``StackFrames``)
stripped.  Exit 0 when the two match.

    python3 tools/hlo_identity.py <checkout_a> <checkout_b> [--cell NAME]

On a TPU host it compiles for the chip; elsewhere (``JAX_PLATFORMS=cpu``)
for a described v5e, as ``benchmark/compile_check.py`` does.  Each
checkout compiles in a process of its own, one after the other, so a
chip is held by one process at a time.
"""

import argparse
import difflib
import os
import re
import subprocess
import sys
import tempfile

TABLES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$")
METADATA = re.compile(r",? metadata=\{[^}]*\}")


def strip(text: str) -> list:
    """The HLO lines with every metadata field and location table gone."""
    out, skip = [], False
    for line in METADATA.sub("", text).splitlines():
        if TABLES.match(line):
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(line)
    return out


def compile_text(root: str, cell_name: str) -> str:
    """The optimized HLO of ``cell_name``'s program from ``root``."""
    root = os.path.abspath(root)
    sys.path[:0] = [root, os.path.join(root, "benchmark")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_enable_compilation_cache", False)
    import harness

    cell = harness.Cell(cell_name, root)
    kw = {}
    if jax.devices()[0].platform != "tpu":
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        # the schedule routers ask the backend: take the TPU branch
        jax.default_backend = lambda: "tpu"
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        kw["sharding"] = SingleDeviceSharding(topo.devices[0])
    import slate_tpu as st

    cfg, drv = cell.config, cell.driver()
    dt = jnp.dtype(cfg["dtype"])
    specs = (jax.ShapeDtypeStruct((cfg["n"], cfg["n"]), dt, **kw),
             jax.ShapeDtypeStruct((cfg["n"], cfg["nrhs"]), dt, **kw))
    fn = drv.solve_fn(st, cfg, drv.options(st, cfg))
    return jax.jit(fn).lower(*specs).compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("a")
    ap.add_argument("b")
    ap.add_argument("--cell", default="hpl.f64.n8192")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        with open(args.child, "w") as f:
            f.write(compile_text(args.a, args.cell))
        return 0
    texts = []
    with tempfile.TemporaryDirectory() as d:
        for i, root in enumerate((args.a, args.b)):
            out = os.path.join(d, f"{i}.hlo")
            subprocess.run([sys.executable, os.path.abspath(__file__), root,
                            root, "--cell", args.cell, "--child", out],
                           check=True)
            with open(out) as f:
                texts.append(strip(f.read()))
    diff = list(difflib.unified_diff(*texts, lineterm="", n=0))
    print(f"{args.cell}: {len(texts[0])} and {len(texts[1])} HLO lines "
          f"without metadata, {'identical' if not diff else 'DIFFERENT'}")
    for line in diff[:40]:
        print(line[:200])
    return 1 if diff else 0


if __name__ == "__main__":
    sys.exit(main())

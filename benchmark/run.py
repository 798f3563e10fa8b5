#!/usr/bin/env python3
"""Runs one benchmark cell once and prints its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, operands made on the device from the seed, compile or
cache load, warm-up of the cell's own shapes) is ``setup_s``.  Then the
window runs for ``--seconds``; nothing may compile inside it.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a short sub-window runs under the device profiler and the
result carries the per-layer metrics.  Once the window has closed and the
device peak is read, a sample of the answers drawn from the seed is
compared with the plain reference: that decides ``correct``.

Needs a TPU with at least the cell's chips: on anything else it exits 2
and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402


class CompileCounter:
    """Counts JAX lowerings (every compile, cache hit or not) while on."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, name, _secs, **_kw):
        if self.on and name == self.EVENT:
            self.count += 1


class Tracer:
    """The device profiler around a sub-window, marked for the reducer."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.active = None

    def start(self):
        import jax

        jax.profiler.start_trace(self.dir)
        import trace_reduce

        self.active = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self.active.__enter__()

    def stop(self):
        import jax

        self.active.__exit__(None, None, None)
        self.active = None
        jax.profiler.stop_trace()

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def layer_metrics(cell, run, roof, red):
    """Each per-layer reader of the cell, over what the traced run saw."""
    inputs = dict(run.layer_inputs())
    inputs.update(trace=red, roof=roof)
    out = {}
    for m in cell.per_layer:
        value = harness.reader(m["name"], cell.root).read(inputs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, allow_cpu=False, root=ROOT) -> int:
    """``allow_cpu`` and ``root`` are for benchmark/tests only: a CPU
    rehearsal of a copy of the benchmark at tiny sizes."""
    args = parse(argv)
    try:
        cell = harness.Cell(args.workload, root)
    except harness.HarnessError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    # the operator's cache wins; otherwise one fixed path in the checkout
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax

    try:
        devices = harness.check_device(cell.chips, allow_cpu)[: cell.chips]
        roof = harness.roof(devices[0].device_kind, cell.root)
    except harness.HarnessError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", True)
    # small programs are cached too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CompileCounter()
    tracer = Tracer() if args.trace else None
    run = cell.driver().Run(cell, args.seed, devices, tracer)
    try:
        run.setup()
        setup_s = time.perf_counter() - T_START
        counter.on = True
        e2e = run.window(args.seconds)
        counter.on = False
        peak = harness.memory_peak(devices)
        if counter.count:
            print(f"bench: {counter.count} compiles inside the window",
                  file=sys.stderr)
            return 3
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": peak}
        result = {}
        if args.trace:
            import trace_reduce

            pdata = trace_reduce.load(trace_reduce.find_xplane(tracer.dir))
            red = trace_reduce.reduce(pdata, [d.id for d in devices])
            metrics = layer_metrics(cell, run, roof, red)
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            result["breakdown"] = trace_reduce.breakdown(pdata, red)
            del pdata
        else:
            e2e["setup_s"] = setup_s
            metrics = {}
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
        checks, attempted, failed = run.check()
    finally:
        if tracer is not None:
            tracer.close()
    correct = all(ok for *_x, ok in checks)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    out.update(result)
    harness.emit(out, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Sweep runner (reference: test/run_tests.py — builds command lists per
routine class with size presets quick/small/medium, JUnit XML output).

Usage:
    python run_tests.py                     # quick preset, all routines
    python run_tests.py --size small --grid 2x2 --xml results.xml gemm posv
    python run_tests.py --target d          # accepted for reference parity
"""

import argparse
import os
import re
import subprocess
import sys
import threading
import time

PRESETS = {
    "quick": {"dim": "32,50", "nb": "16", "type": "d"},
    "small": {"dim": "64,100", "nb": "16,32", "type": "s,d"},
    "medium": {"dim": "128,256", "nb": "32,64", "type": "s,d,c,z"},
}


# The ROADMAP tier-1 contract, verbatim: command shape, 870 s timeout
# (kill 10 s after terminate), and DOTS_PASSED accounting over the
# progress lines.  `python run_tests.py --tier1` replaces hand-pasting.
TIER1_TIMEOUT = 870.0
TIER1_KILL_GRACE = 10.0
_DOTS_RE = re.compile(r"^[.FEsx]+( *\[ *[0-9]+%\])?$")


def tier1() -> int:
    cmd = [
        sys.executable, "-m", "pytest", "tests/", "-q", "-m", "not slow",
        "--continue-on-collection-errors", "-p", "no:cacheprovider",
        "-p", "no:xdist", "-p", "no:randomly",
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.Popen(
        cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=os.path.dirname(os.path.abspath(__file__)) or ".",
    )
    timed_out = False

    def _watchdog():
        nonlocal timed_out
        try:
            proc.wait(timeout=TIER1_TIMEOUT)
        except subprocess.TimeoutExpired:
            timed_out = True
            proc.terminate()
            try:
                proc.wait(timeout=TIER1_KILL_GRACE)
            except subprocess.TimeoutExpired:
                proc.kill()

    w = threading.Thread(target=_watchdog, daemon=True)
    w.start()
    dots = 0
    assert proc.stdout is not None
    for line in proc.stdout:
        sys.stdout.write(line)
        sys.stdout.flush()
        if _DOTS_RE.match(line.rstrip("\n")):
            dots += line.count(".")
    rc = proc.wait()
    w.join()
    if timed_out:
        rc = 124  # the driver's `timeout` convention
    print(f"DOTS_PASSED={dots}")
    print(f"tier1: rc={rc} wall={time.monotonic() - t0:.0f}s")
    return rc


def schedules_smoke() -> int:
    """Parity gate for the factorization schedules: the whole of
    tests/test_recursive_schedules.py across all four dtypes
    (marker-independent — the slow marks only budget the tier-1 gate),
    including the cheap n=256 driver-routing/metrics tests, minus only
    the heavy n=2048 end-to-end driver case.  For touching
    ops/*_kernels.py or the drivers' Option.Schedule routing without
    paying a full tier-1."""
    cmd = [
        sys.executable, "-m", "pytest",
        "tests/test_recursive_schedules.py", "-q",
        "-k", "not driver_n2048",
        "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.call(
        cmd, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)) or ".",
    )


# Env-activated mixed-precision stream for the --refine gate: metrics
# are read at import (the production activation path); the atexit dump
# writes the JSONL refine_report joins.  One deliberately ill-
# conditioned system exercises the fallback, well under the report's
# rate threshold.
_REFINE_DRIVER = """
import jax
jax.config.update("jax_enable_x64", True)  # the f64/f32 pair is the gate
import numpy as np
import slate_tpu as st
from slate_tpu.matgen import cond_matrix
from slate_tpu.matrix.matrix import HermitianMatrix, Matrix

B = np.arange(96, dtype=np.float64).reshape(48, 2) / 48.0
for seed in (0, 1, 2):
    A = cond_matrix(48, 1e3, seed=seed)
    X, info, iters = st.gesv_mixed(Matrix.from_global(A, 16),
                                   Matrix.from_global(B, 16))
    assert int(info) == 0 and iters >= 0, (int(info), iters)
S = cond_matrix(48, 1e4, spd=True)
X, info, iters = st.posv_mixed(
    HermitianMatrix.from_global(S, 16, uplo=st.Uplo.Lower),
    Matrix.from_global(B, 16))
assert int(info) == 0 and iters >= 0
# divergence leg: cond >> 1/eps_f32 must demote to the fallback solver
A = cond_matrix(48, 1e9)
X, info, iters = st.gesv_mixed(Matrix.from_global(A, 16),
                               Matrix.from_global(B, 16))
assert int(info) == 0 and iters < 0, (int(info), iters)
assert np.all(np.isfinite(np.asarray(X.to_global())))
print("refine driver: 4 converged, 1 fallback, 0 hangs")
"""


def refine_gate() -> int:
    """Refine gate, two legs: (1) the mixed-precision suite (slow
    parametrizations included); (2) an env-activated driver stream
    (SLATE_TPU_METRICS, the production path) whose JSONL is joined by
    tools/refine_report.py — a fallback rate past the threshold fails
    the gate."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    cmd = [
        sys.executable, "-m", "pytest", "tests/test_refine.py", "-q",
        "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
    ]
    rc = subprocess.call(cmd, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         cwd=here)
    if rc != 0:
        return rc
    jsonl = os.path.join(tempfile.gettempdir(), f"refine_{os.getpid()}.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu", SLATE_TPU_METRICS=jsonl)
    try:
        rc = subprocess.call([sys.executable, "-c", _REFINE_DRIVER], env=env,
                             cwd=here)
        if rc != 0:
            return rc
        return subprocess.call(
            [sys.executable, os.path.join("tools", "refine_report.py"),
             jsonl, "--max-fallback-rate", "0.5"],
            cwd=here,
        )
    finally:
        try:
            os.unlink(jsonl)
        except OSError:
            pass


# Env-activated faulty stream for the --chaos gate: SLATE_TPU_FAULTS +
# SLATE_TPU_METRICS are read at import (the production activation path),
# the atexit dump writes the JSONL chaos_report joins.
_CHAOS_DRIVER = """
import numpy as np
from slate_tpu.exceptions import SlateError
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import SolverService

rng = np.random.default_rng(0)
n = 12
svc = SolverService(cache=ExecutableCache(manifest_path=None), batch_max=4,
                    dim_floor=16, nrhs_floor=4, retry_backoff_s=0.002,
                    breaker_cooldown_s=0.02, retry_seed=0)
futs = [svc.submit("gesv", rng.standard_normal((n, n)) + n * np.eye(n),
                   rng.standard_normal((n, 2)), retries=2)
        for _ in range(24)]
ok = typed = 0
for f in futs:
    try:
        assert np.all(np.isfinite(f.result(timeout=300)))
        ok += 1
    except SlateError:
        typed += 1
assert ok + typed == len(futs), "a future hung"
print(f"chaos driver: {ok} solved, {typed} typed errors, 0 hangs")
svc.stop()
"""

# Artifact leg of the --chaos gate: SLATE_TPU_FAULTS arms the three
# artifact sites (env path, read at import), a store is warmed (misses
# never advance the fault sites — the ladder starts after a successful
# read), then four loads eat one injection each and the fourth proves
# the store healthy.  chaos_report joins faults.injected.artifact_*
# against the detection counters.
_CHAOS_ARTIFACT_DRIVER = """
import os
import tempfile
import jax
jax.config.update("jax_enable_x64", True)  # the production f64/x64 config
import numpy as np
from slate_tpu.serve import buckets as bk
from slate_tpu.serve.cache import ExecutableCache

td = tempfile.mkdtemp(prefix="slate_chaos_art_")
cache = ExecutableCache(manifest_path=os.path.join(td, "m.json"),
                        artifact_dir=os.path.join(td, "a"))
key = bk.bucket_for("gesv", 10, 10, 2, np.float64, floor=16,
                    nrhs_floor=4, schedule="recursive")
cache.ensure_manifest(key, (1,))
cache.warmup(batch_max=1)  # builds + persists the export artifact
st = cache.artifacts
outcomes = []
for i in range(4):  # corrupt, stale, load_fail fire once each, then clean
    outcomes.append(st.load(key, 1) is not None)
assert outcomes == [False, False, False, True], outcomes
print("chaos artifact driver: 3 injected loads degraded, 4th verified clean")
"""


def chaos() -> int:
    """Chaos gate, three legs: (1) the fault-injection suite — every
    site x hardening combination including the slow-marked sustained
    streams; (2) an env-activated faulty stream (SLATE_TPU_FAULTS +
    SLATE_TPU_METRICS, the production path) whose JSONL is joined by
    tools/chaos_report.py — a fault site with injections but no
    recovery signal fails the gate; (3) the same join over the three
    artifact-store load sites (artifact_corrupt/_stale/_load_fail),
    run as its own pass so the per-site attribution is airtight."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    cmd = [
        sys.executable, "-m", "pytest", "tests/test_chaos.py", "-q",
        "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
    ]
    rc = subprocess.call(cmd, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         cwd=here)
    if rc != 0:
        return rc
    legs = (
        (_CHAOS_DRIVER, "execute:p=0.3,seed=3;worker_death:every=7"),
        (_CHAOS_ARTIFACT_DRIVER,
         "artifact_corrupt:once;artifact_stale:once;"
         "artifact_load_fail:once"),
    )
    for i, (driver, faults_spec) in enumerate(legs):
        jsonl = os.path.join(
            tempfile.gettempdir(), f"chaos_{os.getpid()}_{i}.jsonl"
        )
        env = dict(
            os.environ, JAX_PLATFORMS="cpu", SLATE_TPU_METRICS=jsonl,
            SLATE_TPU_FAULTS=faults_spec,
        )
        try:
            rc = subprocess.call(
                [sys.executable, "-c", driver], env=env, cwd=here
            )
            if rc == 0:
                rc = subprocess.call(
                    [sys.executable,
                     os.path.join("tools", "chaos_report.py"), jsonl],
                    cwd=here,
                )
            if rc != 0:
                return rc
        finally:
            try:
                os.unlink(jsonl)
            except OSError:
                pass
    return 0


# Env-activated placement stream for the --sharded gate: a forced
# 8-fake-device CPU mesh (XLA_FLAGS in the gate env, set before jax
# imports), a replica-pool service with an spmd submesh, a warmed mixed
# small/large stream that must stay compile-free, and an atexit metrics
# dump tools/placement_report.py joins (nonzero on a starved replica).
_SHARDED_DRIVER = """
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import metrics
from slate_tpu.serve import buckets as bk
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.placement import PlacementPolicy
from slate_tpu.serve.service import SolverService

assert len(jax.devices()) >= 8, jax.devices()
rng = np.random.default_rng(0)
svc = SolverService(
    cache=ExecutableCache(manifest_path=None), batch_max=4,
    batch_window_s=0.002, dim_floor=16, nrhs_floor=4,
    placement=PlacementPolicy(replicas=3, mesh="2x2", shard_threshold=40),
)
key_s = bk.bucket_for("gesv", 12, 12, 2, np.float64, floor=16, nrhs_floor=4)
key_l = bk.bucket_for("gesv", 50, 50, 2, np.float64, floor=16, nrhs_floor=4,
                      mesh="2x2")
svc.cache.ensure_manifest(key_s, (1, 4))
svc.cache.ensure_manifest(key_l, (1,))
svc.warmup()  # primes all 3 replica devices + the spmd executable

def prob(n, seed):
    r = np.random.default_rng(seed)
    return (r.standard_normal((n, n)) + n * np.eye(n),
            r.standard_normal((n, 2)))

probs = [prob(12, i) for i in range(18)] + [prob(50, 100 + i)
                                            for i in range(2)]
with metrics.deltas() as d:
    futs = [svc.submit("gesv", A, B) for A, B in probs]
    for (A, B), f in zip(probs, futs):
        X = f.result(timeout=600)
        assert np.abs(X - np.linalg.solve(A, B)).max() < 1e-8
    assert d.get("jit.compilations") == 0, (
        "warmed placement stream compiled: %d" % d.get("jit.compilations"))
    assert d.get("serve.routed_sharded") == 2
    assert d.get("serve.replicated_dispatch") == 18
busy = [r["name"] for r in svc.health()["replicas"] if r["dispatched"] > 0]
assert len(busy) >= 2, busy
print(f"sharded driver: 18 replicated over replicas {busy}, "
      "2 sharded on 2x2, 0 steady-state compiles")
svc.stop()
"""


def sharded() -> int:
    """Sharded-serving gate, two legs: (1) the placement suite
    (policy units + the 8-fake-device acceptance stream); (2) an
    env-activated placement stream (SLATE_TPU_METRICS, forced device
    count — the production activation path) whose JSONL is joined by
    tools/placement_report.py — a starved replica fails the gate."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_placement.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=here,
    )
    if rc != 0:
        return rc
    jsonl = os.path.join(
        tempfile.gettempdir(), f"placement_{os.getpid()}.jsonl"
    )
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", SLATE_TPU_METRICS=jsonl,
        XLA_FLAGS=(
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip(),
    )
    try:
        rc = subprocess.call(
            [sys.executable, "-c", _SHARDED_DRIVER], env=env, cwd=here
        )
        if rc != 0:
            return rc
        return subprocess.call(
            [sys.executable, os.path.join("tools", "placement_report.py"),
             jsonl],
            cwd=here,
        )
    finally:
        try:
            os.unlink(jsonl)
        except OSError:
            pass


# Env-activated tracing+latency stream for the --latency gate:
# SLATE_TPU_METRICS + SLATE_TPU_TRACE_RING are read at import (the
# production activation path); faults are armed AFTER warmup (an
# execute fault during warmup would fail the precompile by design).
# The driver asserts the ISSUE acceptance inline: every delivered
# request's trace is a complete admit -> deliver span chain in the
# Chrome export, and a retried request carries a backoff span.
_LATENCY_DRIVER = """
import json
import sys
import numpy as np
from slate_tpu.aux import faults, metrics, spans
from slate_tpu.exceptions import SlateError
from slate_tpu.serve import buckets as bk
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import SolverService

trace_path = sys.argv[1]
assert spans.is_on() and spans.capacity() >= 4096  # env armed the ring
svc = SolverService(cache=ExecutableCache(manifest_path=None), batch_max=4,
                    batch_window_s=0.002, dim_floor=16, nrhs_floor=4,
                    retry_backoff_s=0.002, breaker_cooldown_s=0.05,
                    retry_seed=0)
k1 = bk.bucket_for("gesv", 12, 12, 2, np.float64, floor=16, nrhs_floor=4)
k2 = bk.bucket_for("posv", 24, 24, 2, np.float64, floor=16, nrhs_floor=4)
svc.cache.ensure_manifest(k1, (1, 4))
svc.cache.ensure_manifest(k2, (1, 4))
svc.warmup()  # warmed: the latency split measures serving, not compiles
# latency+execute injection (ISSUE acceptance): every=6 is
# deterministic — at least one batch fails and retries with backoff
faults.configure("execute:every=6;latency:p=0.3,ms=5,seed=5")
faults.on()

def prob(rt, n, seed):
    r = np.random.default_rng(seed)
    A = r.standard_normal((n, n))
    A = A @ A.T + n * np.eye(n) if rt == "posv" else A + n * np.eye(n)
    return rt, A, r.standard_normal((n, 2))

probs = [prob("gesv", 12, i) for i in range(16)] + [
    prob("posv", 24, 100 + i) for i in range(8)]
futs = [svc.submit(rt, A, B, deadline=120.0, retries=3)
        for rt, A, B in probs]
ok = typed = 0
for f in futs:
    try:
        X = f.result(timeout=300)
        assert np.all(np.isfinite(X))
        ok += 1
    except SlateError:
        typed += 1  # retry budget exhausted into a faulted direct path
assert ok + typed == len(futs), "a future hung"
assert ok >= len(futs) - 4, f"too many failures: {ok}/{len(futs)}"
faults.reset()
svc.stop()
spans.export_chrome(trace_path)

data = json.load(open(trace_path))
evs = [e for e in data["traceEvents"] if e.get("ph") in ("X", "i")]
traces = {}
for e in evs:
    tr = e.get("args", {}).get("trace")
    if tr:
        traces.setdefault(tr, {}).setdefault(e["name"], []).append(e)
roots = {tr: t["request"][0] for tr, t in traces.items() if "request" in t}
orphans = sorted(tr for tr in traces if tr not in roots)
assert not orphans, f"orphan traces (no request root): {orphans}"
delivered = {tr: r for tr, r in roots.items()
             if r["args"].get("outcome") == "ok"}
assert len(delivered) == ok, (len(delivered), ok)
for tr in delivered:
    names = set(traces[tr])
    assert "admit" in names and "queued" in names, (tr, names)
    assert "execute" in names or "direct" in names, (tr, names)
retried = [tr for tr in traces if "backoff" in traces[tr]]
assert retried, "execute faults fired but no backoff span recorded"
h = svc.health()
assert h["latency"], "health() must surface per-bucket percentiles"
print(f"latency driver: {ok} delivered, {typed} typed, "
      f"{len(delivered)} complete span chains, {len(retried)} retried "
      f"with backoff spans, 0 orphans")
"""


def latency_gate() -> int:
    """Latency/tracing gate, three legs: (1) the span + histogram
    suites; (2) an env-activated warmed serve stream under
    latency+execute fault injection (SLATE_TPU_METRICS +
    SLATE_TPU_TRACE_RING, the production activation path) that exports
    a Chrome trace and asserts every delivered request has a complete
    admit -> deliver span chain; (3) tools/latency_report.py over the
    stream's JSONL — per-bucket p50/p95/p99 with the queued-vs-execute
    split, failing past the p99 budget."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_spans.py",
         "tests/test_metrics.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=here,
    )
    if rc != 0:
        return rc
    with tempfile.TemporaryDirectory(prefix="slate_latency_") as td:
        jsonl = os.path.join(td, "latency.jsonl")
        trace_json = os.path.join(td, "trace.json")
        env = dict(
            os.environ, JAX_PLATFORMS="cpu", SLATE_TPU_METRICS=jsonl,
            SLATE_TPU_TRACE_RING="8192",
        )
        env.pop("SLATE_TPU_FAULTS", None)  # the driver arms post-warmup
        rc = subprocess.call(
            [sys.executable, "-c", _LATENCY_DRIVER, trace_json],
            env=env, cwd=here,
        )
        if rc != 0:
            return rc
        return subprocess.call(
            [sys.executable, os.path.join("tools", "latency_report.py"),
             jsonl, "--p99-budget", "30"],
            cwd=here,
        )


# Env-activated repeated-A stream for the --factor gate:
# SLATE_TPU_FACTOR_CACHE=1 + SLATE_TPU_METRICS are read at import (the
# production activation path).  One submit factors and caches; the
# warmed 20-request same-A stream must be trsm-only (hits) and
# compile-free; the JSONL is joined by tools/factor_report.py.
_FACTOR_DRIVER = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import metrics
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import SolverService

svc = SolverService(cache=ExecutableCache(manifest_path=None), batch_max=4,
                    batch_window_s=0.002, dim_floor=16, nrhs_floor=4)
assert svc.factor_cache is not None, "SLATE_TPU_FACTOR_CACHE must arm it"
rng = np.random.default_rng(0)
n = 12
A = rng.standard_normal((n, n)) + n * np.eye(n)
B0 = rng.standard_normal((n, 2))
X0 = svc.submit("gesv", A, B0).result(timeout=300)
assert np.abs(X0 - np.linalg.solve(A, B0)).max() < 1e-9
svc.warmup()  # the miss registered the solve bucket; precompile it
with metrics.deltas() as d:
    futs = [svc.submit("gesv", A, rng.standard_normal((n, 2)))
            for _ in range(20)]
    for f in futs:
        assert np.all(np.isfinite(f.result(timeout=300)))
    hits = d.get("serve.factor_cache.hit")
    comp = d.get("jit.compilations")
assert hits >= 19, hits
assert comp == 0, f"warmed repeated-A stream compiled: {comp}"
svc.stop()
print(f"factor driver: 1 factor + 20 trsm-only solves, "
      f"{int(hits)} hits, 0 compiles")
"""


def factor_gate() -> int:
    """Factor-cache gate, two legs: (1) the factor-cache suite
    (keying, budgets, up/downdate, solve-phase manifest/artifact
    round-trips, the warmed repeated-A acceptance stream); (2) an
    env-activated repeated-A stream (SLATE_TPU_FACTOR_CACHE=1 +
    SLATE_TPU_METRICS, the production activation path) whose JSONL is
    joined by tools/factor_report.py — a repeated-A stream with zero
    hits fails the gate."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_factor_cache.py",
         "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=here,
    )
    if rc != 0:
        return rc
    jsonl = os.path.join(
        tempfile.gettempdir(), f"factor_{os.getpid()}.jsonl"
    )
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", SLATE_TPU_METRICS=jsonl,
        SLATE_TPU_FACTOR_CACHE="1",
    )
    env.pop("SLATE_TPU_FAULTS", None)
    try:
        rc = subprocess.call(
            [sys.executable, "-c", _FACTOR_DRIVER], env=env, cwd=here
        )
        if rc != 0:
            return rc
        return subprocess.call(
            [sys.executable, os.path.join("tools", "factor_report.py"),
             jsonl],
            cwd=here,
        )
    finally:
        try:
            os.unlink(jsonl)
        except OSError:
            pass


# Env-activated repeated-A gels stream for the --fabric gate:
# SLATE_TPU_FACTOR_CACHE=1 (+ SLATE_TPU_FACTOR_ARENA=1 on the armed
# leg) are read at service construction — the production activation
# path.  One gels submit factors the QR pack and caches it; the warmed
# >= 20-solve pristine-session stream must be hits-only, compile-free
# and (armed) upload-free; a streamed append + fenced CSNE solve
# closes the loop.  Every X lands in argv[1] so the gate can prove the
# arena-off leg byte-identical to the armed one.
_FABRIC_DRIVER = """
import os
import sys
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import metrics
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import SolverService
from slate_tpu.fabric.session import FactorSession

out = sys.argv[1]
svc = SolverService(cache=ExecutableCache(manifest_path=None), batch_max=4,
                    batch_window_s=0.002, dim_floor=16, nrhs_floor=4)
assert svc.factor_cache is not None, "SLATE_TPU_FACTOR_CACHE must arm it"
want_arena = bool(os.environ.get("SLATE_TPU_FACTOR_ARENA"))
assert (svc.arena is not None) == want_arena, svc.arena
rng = np.random.default_rng(0)
m, n = 40, 12
A = rng.standard_normal((m, n))
B0 = rng.standard_normal((m, 2))
X0 = svc.submit("gels", A, B0).result(timeout=300)
assert np.abs(X0 - np.linalg.lstsq(A, B0, rcond=None)[0]).max() < 1e-9
svc.warmup()  # the miss registered the gels solve bucket; precompile it
sess = FactorSession(svc, A)
Bs = [rng.standard_normal((m, 2)) for _ in range(20)]
with metrics.deltas() as d:
    Xs = [sess.solve(B) for B in Bs]
    hits = int(d.get("serve.factor_cache.hit") or 0)
    comp = int(d.get("jit.compilations") or 0)
    avoided = int(d.get("serve.arena.upload_avoided_bytes") or 0)
assert hits >= 19, hits
assert comp == 0, f"warmed gels session stream compiled: {comp}"
if want_arena:
    assert avoided > 0, "arena armed but every hit still re-uploaded"
else:
    assert avoided == 0, "arena unarmed but arena counters moved"
C = rng.standard_normal((5, n))
sess.append(C)
B2 = rng.standard_normal((m + 5, 2))
X2 = sess.solve(B2)
ref = np.linalg.lstsq(np.vstack([A, C]), B2, rcond=None)[0]
assert np.abs(X2 - ref).max() < 1e-9, "streamed session solve drifted"
np.save(out, np.stack([X0, *Xs, X2]))
svc.stop()
print(f"fabric driver[arena={'on' if want_arena else 'off'}]: 1 factor "
      f"+ {len(Xs)} session solves, {hits} hits, 0 compiles, "
      f"upload_avoided={avoided}")
"""


def fabric_gate() -> int:
    """Factor-fabric gate, three legs: (1) the fabric suite (arena
    budgets/spill/cross-replica, session update-vs-refactor parity,
    breakdown refactor, fence coverage); (2) the env-activated
    repeated-A gels stream with the arena ARMED (factor once, >= 20
    warmed session solves, 0 compiles, upload_avoided_bytes > 0),
    judged by tools/factor_report.py; (3) the same stream with the
    arena OFF, whose every X must be byte-identical to leg 2's —
    the unarmed service is provably legacy."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_fabric.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=here,
    )
    if rc != 0:
        return rc
    tmp = tempfile.gettempdir()
    jsonl = os.path.join(tmp, f"fabric_{os.getpid()}.jsonl")
    jsonl_off = os.path.join(tmp, f"fabric_off_{os.getpid()}.jsonl")
    out_on = os.path.join(tmp, f"fabric_on_{os.getpid()}.npy")
    out_off = os.path.join(tmp, f"fabric_off_{os.getpid()}.npy")
    base = dict(os.environ, JAX_PLATFORMS="cpu",
                SLATE_TPU_FACTOR_CACHE="1")
    for k in ("SLATE_TPU_FAULTS", "SLATE_TPU_FACTOR_ARENA",
              "SLATE_TPU_METRICS"):
        base.pop(k, None)
    try:
        rc = subprocess.call(
            [sys.executable, "-c", _FABRIC_DRIVER, out_on],
            env=dict(base, SLATE_TPU_METRICS=jsonl,
                     SLATE_TPU_FACTOR_ARENA="1"),
            cwd=here,
        )
        if rc != 0:
            return rc
        rc = subprocess.call(
            [sys.executable, "-c", _FABRIC_DRIVER, out_off],
            # metrics on (the driver asserts hit counters) but the
            # arena env stays popped — this is the legacy leg
            env=dict(base, SLATE_TPU_METRICS=jsonl_off), cwd=here,
        )
        if rc != 0:
            return rc
        import numpy as np

        a, b = np.load(out_on), np.load(out_off)
        if a.dtype != b.dtype or a.shape != b.shape \
                or a.tobytes() != b.tobytes():
            print("FABRIC GATE: arena-off X stream is not "
                  "byte-identical to the armed leg — the unarmed "
                  "service is not legacy")
            return 1
        print("fabric gate: arena-off leg byte-identical to armed leg")
        return subprocess.call(
            [sys.executable, os.path.join("tools", "factor_report.py"),
             jsonl],
            cwd=here,
        )
    finally:
        for p in (jsonl, jsonl_off, out_on, out_off):
            try:
                os.unlink(p)
            except OSError:
                pass


# Two-leg bursty two-tenant stream for the --adaptive gate.  Same
# phase-1 trace both legs: an abusive tenant floods 48 requests, then a
# well-behaved tenant submits 8 on its own bucket; every dispatch pays
# a deterministic injected 30 ms (machine-independent queueing).  The
# STATIC leg (tenancy/adaptation off — tags accepted but inert) must
# PROVABLY miss the well-behaved p99 budget: the flood head-of-line
# blocks the shared FIFO.  The ADAPTIVE leg (tenant quotas + WFQ +
# adaptive window) must hold it, then two overload phases (tight-
# deadline abuser traffic driving the burn EWMA up) must end in typed
# Shed refusals — every admitted future still resolves.
_ADAPTIVE_DRIVER = """
import sys
import time
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import faults, metrics
from slate_tpu.exceptions import SlateError
from slate_tpu.serve import buckets as bk
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import Rejected, Shed, SolverService

mode = sys.argv[1]  # "static" | "adaptive"
BUDGET = 0.25
n_good, n_abuse = 24, 12  # distinct buckets: the flood never coalesces
                          # with the victim's traffic

kw = dict(cache=ExecutableCache(manifest_path=None), batch_max=4,
          batch_window_s=0.01, dim_floor=16, nrhs_floor=4)
if mode == "adaptive":
    kw.update(
        tenants="good:weight=4;abuser:rate=10,burst=4,share=0.25",
        adaptive=True, latency_budget_s=BUDGET,
    )
svc = SolverService(**kw)
k_good = bk.bucket_for("gesv", n_good, n_good, 2, np.float64, floor=16,
                       nrhs_floor=4)
k_abuse = bk.bucket_for("gesv", n_abuse, n_abuse, 2, np.float64, floor=16,
                        nrhs_floor=4)
svc.cache.ensure_manifest(k_good, (1, 4))
svc.cache.ensure_manifest(k_abuse, (1, 4))
svc.warmup()  # the burst measures queueing, not compiles
faults.configure("latency:every=1,ms=30")  # armed POST-warmup
faults.on()

def prob(n, seed):
    r = np.random.default_rng(seed)
    return (r.standard_normal((n, n)) + n * np.eye(n),
            r.standard_normal((n, 2)))

A_a, B_a = prob(n_abuse, 1)
futs, shed, rejected = [], 0, 0

def sub(**skw):
    global shed, rejected
    try:
        futs.append(svc.submit("gesv", A_a, B_a, tenant="abuser",
                               priority="low", **skw))
    except Shed:
        shed += 1
    except Rejected:
        rejected += 1

for _ in range(48):  # phase 1: the flood...
    sub()
for i in range(8):  # ...then the victim
    A, B = prob(n_good, 100 + i)
    futs.append(svc.submit("gesv", A, B, tenant="good", priority="high",
                           deadline=10.0))
if mode == "adaptive":
    # phase 2: tight-deadline abuser traffic melts its own SLO — the
    # burn EWMA climbs; phase 3: the controller must be shedding
    time.sleep(0.4)  # tokens refill (~4), phase-1 queue drains
    for _ in range(8):
        sub(deadline=0.02)
    deadline = time.monotonic() + 10.0
    while shed == 0 and time.monotonic() < deadline:
        time.sleep(0.05)
        sub(deadline=0.02)
ok = typed = 0
for f in futs:
    try:
        assert np.all(np.isfinite(f.result(timeout=300)))
        ok += 1
    except SlateError:
        typed += 1
assert ok + typed == len(futs), "a future hung"
faults.reset()
h = svc.health()
svc.stop()
p99_good_bucket = metrics.percentile(
    f"serve.latency.{k_good.label}.total", 99)
if mode == "static":
    assert p99_good_bucket is not None and p99_good_bucket > BUDGET, (
        "static config should have missed the %.0f ms budget, got %s"
        % (BUDGET * 1e3, p99_good_bucket))
    print(f"adaptive driver [static]: victim p99 "
          f"{p99_good_bucket * 1e3:.0f} ms MISSES the "
          f"{BUDGET * 1e3:.0f} ms budget (as designed), "
          f"{ok} delivered / {typed} typed")
else:
    p99_good = metrics.percentile("serve.latency.tenant.good.total", 99)
    assert p99_good is not None and p99_good <= BUDGET, (
        "adaptive config missed the victim budget: %s" % p99_good)
    assert shed > 0, "overload never shed the abuser"
    assert rejected > 0, "the abuser quota never rejected"
    assert h["tenants"]["abuser"]["shed"] == shed
    assert h["admission"]["overload_level"] >= 1, h["admission"]
    assert any(k_abuse.label in k or k_good.label in k
               for k in h["admission"]["windows"]), h["admission"]
    print(f"adaptive driver [adaptive]: victim p99 "
          f"{p99_good * 1e3:.0f} ms holds the {BUDGET * 1e3:.0f} ms "
          f"budget; abuser shed={shed} quota-rejected={rejected}; "
          f"{ok} delivered / {typed} typed, 0 hangs")
"""

# tenant_flood chaos leg: the site is armed via env (the production
# activation path), one real submit triggers a synthetic 24-request
# low-priority burst from tenant "flood", whose tight quota refuses
# most of it — chaos_report then joins faults.injected.tenant_flood
# against the serve.shed/serve.rejected* recovery family.
_FLOOD_DRIVER = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import metrics
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import SolverService

svc = SolverService(cache=ExecutableCache(manifest_path=None), batch_max=4,
                    dim_floor=16, nrhs_floor=4)
assert svc._admission is not None, "SLATE_TPU_TENANTS must arm the plane"
rng = np.random.default_rng(0)
n = 12
A = rng.standard_normal((n, n)) + n * np.eye(n)
B = rng.standard_normal((n, 2))
X = svc.submit("gesv", A, B, tenant="good").result(timeout=300)
assert np.abs(X - np.linalg.solve(A, B)).max() < 1e-9
c = metrics.counters()
assert c.get("faults.injected.tenant_flood", 0) >= 1, c
refused = c.get("serve.rejected", 0) + c.get("serve.shed", 0)
assert refused >= 1, "the flood burst was never refused"
svc.stop()
print(f"flood driver: 1 real request delivered, synthetic burst "
      f"refused {int(refused)}x")
"""


def adaptive_gate() -> int:
    """Admission/fairness gate, three legs: (1) the admission suite
    (fake-clock controller units + the fairness invariant); (2) the
    two-leg bursty two-tenant stream — the static config must
    provably MISS the well-behaved tenant's p99 budget while the
    adaptive config holds it, sheds the abuser, and resolves every
    future typed — with tools/tenant_report.py rendering the
    per-tenant verdict from the adaptive leg's JSONL; (3) a
    tenant_flood chaos leg joined by tools/chaos_report.py."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_admission.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=here,
    )
    if rc != 0:
        return rc
    with tempfile.TemporaryDirectory(prefix="slate_adaptive_") as td:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for var in ("SLATE_TPU_FAULTS", "SLATE_TPU_TENANTS",
                    "SLATE_TPU_ADAPTIVE", "SLATE_TPU_FACTOR_CACHE"):
            env.pop(var, None)
        # leg 2a: static config — the driver asserts the budget MISS
        rc = subprocess.call(
            [sys.executable, "-c", _ADAPTIVE_DRIVER, "static"],
            env=dict(env, SLATE_TPU_METRICS=os.path.join(td, "static.jsonl")),
            cwd=here,
        )
        if rc != 0:
            return rc
        # leg 2b: adaptive config — holds the budget, sheds the abuser
        jsonl = os.path.join(td, "adaptive.jsonl")
        rc = subprocess.call(
            [sys.executable, "-c", _ADAPTIVE_DRIVER, "adaptive"],
            env=dict(env, SLATE_TPU_METRICS=jsonl), cwd=here,
        )
        if rc != 0:
            return rc
        rc = subprocess.call(
            [sys.executable, os.path.join("tools", "tenant_report.py"),
             jsonl, "--p99-budget", "0.25", "--well-behaved", "good",
             "--abusive", "abuser"],
            cwd=here,
        )
        if rc != 0:
            return rc
        # leg 3: tenant_flood chaos attribution
        flood = os.path.join(td, "flood.jsonl")
        rc = subprocess.call(
            [sys.executable, "-c", _FLOOD_DRIVER],
            env=dict(
                env, SLATE_TPU_METRICS=flood,
                SLATE_TPU_TENANTS="flood:rate=1,burst=2,share=0.1",
                SLATE_TPU_FAULTS="tenant_flood:once,burst=24",
            ),
            cwd=here,
        )
        if rc != 0:
            return rc
        return subprocess.call(
            [sys.executable, os.path.join("tools", "chaos_report.py"),
             flood],
            cwd=here,
        )


# Restart-drill drivers for the --coldstart gate.  Each runs in its OWN
# subprocess so the restore leg is a true fresh interpreter: nothing
# carries over but the artifact dir + manifest on disk.

_COLDSTART_WARM = """
import sys
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import SolverService

art, man = sys.argv[1], sys.argv[2]
rng = np.random.default_rng(0)
n1, n2 = 10, 20
A1 = rng.standard_normal((n1, n1)) + n1 * np.eye(n1)
B1 = rng.standard_normal((n1, 2))
G = rng.standard_normal((n2, n2))
A2 = G @ G.T + n2 * np.eye(n2)
B2 = rng.standard_normal((n2, 3))

cache = ExecutableCache(manifest_path=man, artifact_dir=art)
# schedule="recursive": pure-JAX kernels whose exported modules are
# custom-call free, so every bucket lands on the export rung (auto
# routes to vendor LAPACK on CPU -> cache_seed, no zero-compile leg)
svc = SolverService(cache=cache, batch_max=4, batch_window_s=0.005,
                    dim_floor=16, nrhs_floor=4, schedule="recursive")
assert svc.wait_ready(120), svc.health()
futs = [svc.submit("gesv", A1 + i * 0.01 * np.eye(n1), B1)
        for i in range(4)]
futs += [svc.submit("posv", A2, B2)]
for f in futs:
    assert np.all(np.isfinite(f.result(timeout=300)))
# build + persist BOTH batch points of both buckets (traffic above
# registered them in the manifest; warmup bakes the rest to artifacts)
compiled = cache.warmup(batch_max=4)
svc.stop()
import os
n_art = len([f for f in os.listdir(art) if f.endswith(".slate_exe")])
assert n_art >= 4, f"expected >= 4 artifacts, found {n_art}"
print(f"coldstart warm: {compiled} warmup compiles, {n_art} artifacts")
"""

_COLDSTART_RESTORE = """
import sys
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import metrics
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import SolverService

art, man, leg = sys.argv[1], sys.argv[2], sys.argv[3]
rng = np.random.default_rng(1)
n1, n2 = 10, 20
A1 = rng.standard_normal((n1, n1)) + n1 * np.eye(n1)
B1 = rng.standard_normal((n1, 2))
G = rng.standard_normal((n2, n2))
A2 = G @ G.T + n2 * np.eye(n2)
B2 = rng.standard_normal((n2, 3))

cache = ExecutableCache(manifest_path=man, artifact_dir=art)
svc = SolverService(cache=cache, batch_max=4, batch_window_s=0.005,
                    dim_floor=16, nrhs_floor=4,
                    schedule="recursive")  # restores on start
assert svc.wait_ready(300), svc.health()
h = svc.health()
assert h["ready"] and h["phase"] == "ready", h
res = h["restore"]
assert res is not None and res["failed"] == 0, res
if leg == "clean":
    # every entry must come from a verified artifact, zero recompiles
    assert res["compiled"] == 0 and res["restored"] >= 4, res
elif leg == "flipped":
    # the byte-flipped artifact must be detected and recompiled
    assert res["compiled"] >= 1, res
    assert metrics.counters().get("serve.artifact_corrupt", 0) >= 1
elif leg == "chaos":
    # once-per-site injection: corrupt, stale, load_fail each eat one
    # load; the fourth restores clean
    assert res["compiled"] == 3 and res["restored"] == 1, res

with metrics.deltas() as d:
    futs = []
    for i in range(4):
        futs.append(svc.submit("gesv", A1 + i * 1e-3 * np.eye(n1), B1))
        futs.append(svc.submit("posv", A2 + i * 1e-3 * np.eye(n2), B2))
    for f in futs:
        assert np.all(np.isfinite(f.result(timeout=300)))
    for i in range(12):
        X1 = svc.submit("gesv", A1, B1).result(timeout=300)
    X2 = svc.submit("posv", A2, B2).result(timeout=300)
    assert d.get("serve.requests") >= 20
    assert d.get("jit.compilations") == 0, (
        "restored steady state must not compile: "
        f"{d.get('jit.compilations')}")
svc.stop()
# correctness vs numpy (no slate dispatch: keeps the window honest)
assert np.abs(X1 - np.linalg.solve(A1, B1)).max() < 1e-9
assert np.abs(X2 - np.linalg.solve(A2, B2)).max() < 1e-9
print(f"coldstart {leg}: ready via {res}, "
      f"{int(d.get('serve.requests'))} requests, 0 compiles"
      if leg == "clean" else
      f"coldstart {leg}: ready via {res}, recovered correctly")
"""


def coldstart() -> int:
    """Cold-start gate, three legs sharing one artifact dir: (1) the
    artifact suite; (2) the ISSUE restart drill — warm a service in
    one process, restore in a FRESH process with zero compiles in a
    >= 20-request steady-state stream, then byte-flip one artifact and
    drill again expecting a counted recompile; (3) a chaos pass arming
    the three artifact fault sites, gated by tools/artifact_report.py
    (nonzero when any injected fault escaped verification)."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_artifacts.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=here,
    )
    if rc != 0:
        return rc
    with tempfile.TemporaryDirectory(prefix="slate_coldstart_") as td:
        art = os.path.join(td, "artifacts")
        man = os.path.join(td, "warmup.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SLATE_TPU_FAULTS", None)

        def run(code, *argv, **extra_env):
            e = dict(env, **extra_env)
            return subprocess.call(
                [sys.executable, "-c", code, *argv], env=e, cwd=here
            )

        rc = run(_COLDSTART_WARM, art, man)
        if rc != 0:
            return rc
        rc = run(_COLDSTART_RESTORE, art, man, "clean",
                 SLATE_TPU_METRICS=os.path.join(td, "clean.jsonl"))
        if rc != 0:
            return rc
        # byte-flip drill: corrupt one artifact payload on disk
        victims = sorted(
            f for f in os.listdir(art) if f.endswith(".slate_exe")
        )
        path = os.path.join(art, victims[0])
        blob = bytearray(open(path, "rb").read())
        blob[-3] ^= 0xFF
        open(path, "wb").write(bytes(blob))
        rc = run(_COLDSTART_RESTORE, art, man, "flipped",
                 SLATE_TPU_METRICS=os.path.join(td, "flipped.jsonl"))
        if rc != 0:
            return rc
        # chaos leg: every artifact fault site injected once, then the
        # report joins injected-vs-detected from the JSONL
        jsonl = os.path.join(td, "chaos.jsonl")
        rc = run(
            _COLDSTART_RESTORE, art, man, "chaos",
            SLATE_TPU_METRICS=jsonl,
            SLATE_TPU_FAULTS=(
                "artifact_corrupt:once;artifact_stale:once;"
                "artifact_load_fail:once"
            ),
        )
        if rc != 0:
            return rc
        return subprocess.call(
            [sys.executable, os.path.join("tools", "artifact_report.py"),
             jsonl],
            cwd=here,
        )


# Env-activated device-telemetry stream for the --perf gate:
# SLATE_TPU_DEVMON=1 + SLATE_TPU_METRICS are read at import (the
# production activation path).  A warmed mixed-shape stream must yield
# health() cost/memory evidence for EVERY warmed bucket (the ISSUE
# acceptance), a graceful device snapshot on CPU (byte fields None,
# never a crash), and stay compile-free; the JSONL is then judged by
# tools/roofline_report.py.
_PERF_DRIVER = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import devmon, metrics
from slate_tpu.serve import buckets as bk
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import SolverService

assert devmon.is_on(), "SLATE_TPU_DEVMON must arm the telemetry plane"
svc = SolverService(cache=ExecutableCache(manifest_path=None), batch_max=4,
                    batch_window_s=0.002, dim_floor=16, nrhs_floor=4)
k1 = bk.bucket_for("gesv", 12, 12, 2, np.float64, floor=16, nrhs_floor=4)
k2 = bk.bucket_for("posv", 24, 24, 2, np.float64, floor=16, nrhs_floor=4)
svc.cache.ensure_manifest(k1, (1, 4))
svc.cache.ensure_manifest(k2, (1, 4))
svc.warmup()  # cold builds: the registry captures here

def prob(rt, n, seed):
    r = np.random.default_rng(seed)
    A = r.standard_normal((n, n))
    A = A @ A.T + n * np.eye(n) if rt == "posv" else A + n * np.eye(n)
    return rt, A, r.standard_normal((n, 2))

probs = [prob("gesv", 12, i) for i in range(16)] + [
    prob("posv", 24, 100 + i) for i in range(8)]
with metrics.deltas() as d:
    futs = [svc.submit(rt, A, B) for rt, A, B in probs]
    for f in futs:
        assert np.all(np.isfinite(f.result(timeout=300)))
    assert d.get("jit.compilations") == 0, (
        "warmed telemetry stream compiled: %d" % d.get("jit.compilations"))

h = svc.health()
for lbl in (k1.label, k2.label):
    per = (h["cost"] or {}).get(lbl)
    assert per, (lbl, h["cost"])
    for b, c in per.items():
        assert c.get("flops", 0) > 0 and c.get("peak_bytes", 0) > 0, (
            lbl, b, c)
    assert h["latency"][lbl]["peak_bytes"] > 0, h["latency"][lbl]
assert isinstance(h["devices"], list) and h["devices"], h["devices"]
for dev in h["devices"]:  # CPU: graceful None, never a crash
    assert "bytes_in_use" in dev, dev
print(f"perf driver: {len(probs)} warmed requests over "
      f"{len(h['cost'])} buckets with cost/memory evidence, 0 compiles")
svc.stop()
"""


# Interpret-mode Pallas leg: CPU CI runs every panel kernel of the
# ``pallas`` schedule family through pl.pallas_call(..., interpret=True)
# against its jnp reference twin — the family is gated without real
# chips (the compiled Mosaic path shares the SAME kernel bodies).
_PALLAS_PANEL_DRIVER = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
import jax.numpy as jnp
from jax import lax
from slate_tpu.ops.pallas import panel_kernels as pk
from slate_tpu.ops.qr_fast import _qr_panel_strips
from slate_tpu.ops.householder import materialize_v

rng = np.random.default_rng(0)
checked = 0
for dt in (np.float32, np.float64, np.complex64, np.complex128):
    tol = 5e3 * np.finfo(np.dtype(dt)).eps

    def rand(shape):
        x = rng.standard_normal(shape)
        if np.issubdtype(dt, np.complexfloating):
            x = x + 1j * rng.standard_normal(shape)
        return jnp.asarray(x, dt)

    def close(a, b, exact=False):
        global checked
        checked += 1
        err = float(jnp.max(jnp.abs(a - b)))
        ref = max(float(jnp.max(jnp.abs(b))), 1.0)
        lim = 0.0 if exact else tol * ref
        assert err <= lim, (np.dtype(dt).name, checked, err, lim)

    b = 64
    A = rand((b, b)); G = A @ jnp.conj(A).T + b * jnp.eye(b, dtype=dt)
    close(jnp.tril(pk.chol_base_pallas(G, interpret=True)),
          jnp.tril(pk.chol_base_reference(G)))
    for M, w, act in ((96, 32, None), (96, 32, 80), (160, 24, None)):
        P = rand((M, w))
        lu_p, p_p = pk.panel_lu_pallas(P, act=act, interpret=True)
        lu_r, p_r = pk.panel_lu_reference(P, act=act)
        close(lu_p, lu_r, exact=True)
        assert bool(jnp.all(p_p == p_r)), "pivot order drifted"
    Pn = rand((96, 32))
    Vp, taus = _qr_panel_strips(Pn, 16)
    V = materialize_v(Vp)
    close(pk.larft_pallas(V, taus, interpret=True),
          pk.larft_reference(V, taus), exact=True)
    C = rand((48, 48)); Aa = rand((48, 24))
    close(pk.syrk_diag_pallas(C, Aa, interpret=True),
          pk.syrk_diag_reference(C, Aa), exact=True)
    C2 = rand((48, 40)); Bb = rand((40, 24))
    close(pk.gemm_sub_pallas(C2, Aa, Bb, interpret=True),
          pk.gemm_sub_reference(C2, Aa, Bb), exact=True)
    n, nrhs = 128, 16
    B = rand((n, nrhs))
    L = jnp.tril(rand((n, n)), -1) * 0.3 + jnp.diag(
        jnp.asarray(2.0 + rng.random(n), dt))
    close(pk.trsm_lower_pallas(L, B, interpret=True),
          pk.trsm_lower_reference(L, B))
    Lu = jnp.tril(rand((n, n)), -1) * 0.3 + jnp.eye(n, dtype=dt)
    close(pk.trsm_lower_pallas(Lu, B, unit=True, interpret=True),
          pk.trsm_lower_reference(Lu, B, unit=True))
    U = jnp.triu(rand((n, n)), 1) * 0.3 + jnp.diag(
        jnp.asarray(2.0 + rng.random(n), dt))
    close(pk.trsm_upper_pallas(U, B, interpret=True),
          pk.trsm_upper_reference(U, B))
print(f"pallas interpret leg: {checked} kernel/dtype parity checks green")
"""


def perf_gate() -> int:
    """Perf gate, five legs: (1) the devmon suite; (2) the interpret-
    mode Pallas leg — every panel kernel of the ``pallas`` schedule
    family runs via ``pl.pallas_call(..., interpret=True)`` against its
    jnp twin on CPU (f32/f64/c64/c128, act-masked + non-pow2 panels,
    exact pivot order); (3) the regression sentinel on the checked-in
    floor — BENCH_FLOOR_CPU.json diffed against itself passes while a
    synthetically-regressed copy exits nonzero; (4) an
    env-activated devmon serve stream whose JSONL
    tools/roofline_report.py must classify (nonzero on any
    unclassifiable warmed bucket — the warmed solve buckets included);
    (5) a quick warmed bench leg diffed ``--floor`` against the
    checked-in BENCH_FLOOR_CPU.json (dtrsm solve-phase entries
    included)."""
    import json
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    # ONE scrubbed env for every leg: a chaos env armed at import
    # would inject into warmup builds and the bench leg's serve
    # entries, an env-armed factor cache detours streams off the
    # bucket-build path, a deployment's peaks override would shift
    # the suite's default-table assertions and every roofline verdict,
    # and — worst — an inherited SLATE_TPU_WARMUP/ARTIFACTS would
    # attach the gate's CPU builds to the operator's PRODUCTION
    # manifest/store and overwrite its captured evidence (an inherited
    # SLATE_TPU_METRICS likewise clobbers the operator's JSONL at
    # every subprocess exit).  This gate measures perf against
    # hermetic defaults; legs that need metrics/devmon set their own.
    tenv = dict(os.environ, JAX_PLATFORMS="cpu")
    for var in ("SLATE_TPU_FAULTS", "SLATE_TPU_FACTOR_CACHE",
                "SLATE_TPU_PEAKS", "SLATE_TPU_WARMUP",
                "SLATE_TPU_ARTIFACTS", "SLATE_TPU_METRICS",
                "SLATE_TPU_DEVMON"):
        tenv.pop(var, None)
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_devmon.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=tenv, cwd=here,
    )
    if rc != 0:
        return rc
    rc = subprocess.call(
        [sys.executable, "-c", _PALLAS_PANEL_DRIVER], env=tenv, cwd=here,
    )
    if rc != 0:
        print("perf gate: pallas interpret leg failed")
        return rc
    bench_diff = os.path.join("tools", "bench_diff.py")
    with tempfile.TemporaryDirectory(prefix="slate_perf_") as td:
        base = "BENCH_FLOOR_CPU.json"
        # leg 2a: an unchanged document must pass
        rc = subprocess.call(
            [sys.executable, bench_diff, base, base], cwd=here,
        )
        if rc != 0:
            print(f"perf gate: {base} against itself flagged a regression")
            return rc
        # leg 2b: a synthetic 2x GFLOP/s collapse must exit nonzero
        with open(os.path.join(here, base)) as f:
            doc = json.load(f)
        if isinstance(doc.get("value"), (int, float)):
            doc["value"] *= 0.5
        for e in doc["extra"].values():
            if isinstance(e, dict) and "gflops" in e:
                e["gflops"] *= 0.5
        reg = os.path.join(td, "regressed.json")
        with open(reg, "w") as f:
            json.dump(doc, f)
        rc = subprocess.call(
            [sys.executable, bench_diff, base, reg], cwd=here,
        )
        if rc != 1:
            # rc must be THE regression verdict: 0 means the sentinel
            # missed, 2 means it never compared an entry (unusable
            # input) — either way the check proved nothing
            print(f"perf gate: synthetic regression not flagged (rc={rc})")
            return 1
        # leg 3: devmon serve stream + roofline classification, on the
        # scrubbed env (the driver and the report both resolve peaks)
        jsonl = os.path.join(td, "perf.jsonl")
        rc = subprocess.call(
            [sys.executable, "-c", _PERF_DRIVER],
            env=dict(tenv, SLATE_TPU_METRICS=jsonl, SLATE_TPU_DEVMON="1"),
            cwd=here,
        )
        if rc != 0:
            return rc
        rc = subprocess.call(
            [sys.executable, os.path.join("tools", "roofline_report.py"),
             jsonl],
            env=tenv, cwd=here,
        )
        if rc != 0:
            return rc
        # leg 4: quick warmed bench, floored against the checked-in
        # baseline (bench owns stdout for its JSON line)
        live = os.path.join(td, "bench_quick.json")
        with open(live, "w") as f:
            rc = subprocess.call(
                [sys.executable, "bench.py", "--quick"],
                env=tenv, cwd=here, stdout=f,
            )
        if rc != 0:
            return rc
        return subprocess.call(
            [sys.executable, bench_diff, "--floor",
             "BENCH_FLOOR_CPU.json", live],
            env=tenv, cwd=here,
        )


# Four-phase SDC drill for the --integrity gate.  SLATE_TPU_INTEGRITY
# ("full,abft") is read at import — the production activation path —
# and asserted; each phase then tunes an explicit policy (short
# quarantine cooldowns, hedging on/off) because the drill must finish
# in seconds.  Faults are armed POST-warmup (an sdc during warmup
# builds would be injected into discarded dummy dispatches, inflating
# the injected count the report joins against detections).
_INTEGRITY_DRIVER = """
import time
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import faults, metrics
from slate_tpu.exceptions import SlateError
from slate_tpu.integrity import IntegrityPolicy, from_options
from slate_tpu.serve import buckets as bk
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.factor_cache import FactorCache
from slate_tpu.serve.service import SolverService

p_env = from_options(None)
assert p_env is not None and p_env.mode == "full" and p_env.abft, (
    "SLATE_TPU_INTEGRITY must arm the plane")

n1, n2 = 12, 24

def prob(rt, n, seed):
    r = np.random.default_rng(seed)
    A = r.standard_normal((n, n))
    A = A @ A.T + n * np.eye(n) if rt == "posv" else A + n * np.eye(n)
    return rt, A, r.standard_normal((n, 2))

def run(svc, probs):
    futs = [svc.submit(rt, A, B) for rt, A, B in probs]
    ok = typed = wrong = 0
    for (rt, A, B), f in zip(probs, futs):
        try:
            X = f.result(timeout=300)
        except SlateError:
            typed += 1
            continue
        scale = np.abs(A).max() * np.abs(X).max() + np.abs(B).max()
        if np.abs(A @ X - B).max() <= 1e-6 * scale:
            ok += 1
        else:
            wrong += 1
    return ok, typed, wrong

def svc_for(pol, **kw):
    return SolverService(
        cache=ExecutableCache(manifest_path=None), batch_max=4,
        batch_window_s=0.002, dim_floor=16, nrhs_floor=4, replicas=2,
        integrity=pol, **kw)

# -- phase A: ABFT-certified stream under sdc_solve; hedged recovery --
pol = IntegrityPolicy(mode="full", abft=True, hedge_factor=0.0,
                      quarantine_cooldown_s=0.25)
svc = svc_for(pol)
for rt, n in (("gesv", n1), ("posv", n2)):
    k = bk.bucket_for(rt, n, n, 2, np.float64, floor=16, nrhs_floor=4,
                      tag="abft")
    svc.cache.ensure_manifest(k, (1, 4))
svc.warmup()
faults.configure("sdc_solve:every=4,seed=2")
faults.on()
probs = [prob("gesv", n1, i) for i in range(24)] + [
    prob("posv", n2, 100 + i) for i in range(12)]
ok, typed, wrong = run(svc, probs)
faults.reset()
assert wrong == 0, f"phase A: {wrong} silent wrong answers delivered"
assert ok + typed == len(probs) and ok >= 30, (ok, typed)
c = metrics.counters()
assert c.get("serve.integrity.fail", 0) >= 1, c
assert c.get("serve.integrity.recovered", 0) >= 1, c
assert c.get("serve.hedge.sent", 0) >= 1, c
assert c.get("serve.hedge.won", 0) >= 1, c
nA = len(probs)

# -- phase B: every dispatch corrupted -> quarantine, then probe back --
faults.configure("sdc_solve:every=1")
faults.on()
okB, typedB, wrongB = run(svc, [prob("gesv", n1, 500 + i)
                                for i in range(8)])
faults.reset()
assert wrongB == 0 and okB + typedB == 8, (okB, typedB, wrongB)
assert metrics.counters().get("serve.integrity.quarantined", 0) >= 1, (
    "poisoned replicas never quarantined")
time.sleep(0.3)  # past the quarantine cooldown: next delivery probes
okP, typedP, wrongP = run(svc, [prob("gesv", n1, 600 + i)
                                for i in range(6)])
assert wrongP == 0 and okP == 6, (okP, typedP, wrongP)
h = svc.health()
assert h["integrity"] is not None and not h["integrity"]["quarantined"], (
    h["integrity"])
assert metrics.counters().get("serve.integrity.unquarantined", 0) >= 1
svc.stop()

# -- phase C: sdc_factor through the factor-cache miss path -----------
pol2 = IntegrityPolicy(mode="full", hedge_factor=0.0,
                       quarantine_cooldown_s=0.25)
svc2 = svc_for(pol2, factor_cache=FactorCache())
faults.configure("sdc_factor:every=3,seed=1")
faults.on()
probsC = [prob("gesv", n1, 700 + i) for i in range(10)] + [
    prob("posv", n2, 800 + i) for i in range(4)]
okC, typedC, wrongC = run(svc2, probsC)
# repeated-A hits against possibly-poisoned cached factors: the
# residual fence must catch them (counted stale), never a wrong X
rt0, A0, _ = prob("gesv", n1, 700)
okR, typedR, wrongR = run(svc2, [
    (rt0, A0, np.random.default_rng(900 + i).standard_normal((n1, 2)))
    for i in range(4)])
faults.reset()
assert wrongC == 0 and wrongR == 0, (wrongC, wrongR)
assert okC + typedC == len(probsC) and okR + typedR == 4
svc2.stop()
nC = len(probsC) + 4

# -- phase D: stragglers hedge off a deliberately-slowed lane ---------
pol3 = IntegrityPolicy(mode="full", hedge_factor=0.5,
                       hedge_min_age_s=0.005)
svc3 = svc_for(pol3)
# nrhs=5 -> rhs bucket 8: a FRESH bucket label, so the p99 history the
# straggler trigger reads comes from phase D's own warmed clean
# traffic (phase C's unwarmed first dispatch put its compile wall into
# the 16x16x4 label's histogram, which would stretch p99 to seconds)
def probD(seed):
    r = np.random.default_rng(seed)
    return ("gesv", r.standard_normal((n1, n1)) + n1 * np.eye(n1),
            r.standard_normal((n1, 5)))
kD = bk.bucket_for("gesv", n1, n1, 5, np.float64, floor=16, nrhs_floor=4)
svc3.cache.ensure_manifest(kD, (1, 4))
svc3.warmup()
# clean traffic first: the straggler trigger compares queued age to
# the bucket's OWN p99 history
okW, _, _ = run(svc3, [probD(950 + i) for i in range(6)])
assert okW == 6
sent0 = metrics.counters().get("serve.hedge.sent", 0)
won0 = metrics.counters().get("serve.hedge.won", 0)
wasted0 = metrics.counters().get("serve.hedge.wasted", 0)
faults.configure("latency:every=2,ms=150")  # every other dispatch slow
faults.on()
okD, typedD, wrongD = run(svc3, [probD(1000 + i) for i in range(32)])
faults.reset()
assert wrongD == 0 and okD == 32, (okD, typedD, wrongD)
# drain before reading: the losing twins of already-resolved futures
# are still queued/in flight, and their wasted/won accounting lands at
# their own completion (stop(drain=True) is the satellite doing real
# work here)
svc3.stop(drain=True, drain_timeout=60.0)
c = metrics.counters()
sent1 = c.get("serve.hedge.sent", 0)
assert sent1 > sent0, "no straggler was hedged off the slowed lane"
assert (c.get("serve.hedge.won", 0) - won0
        + c.get("serve.hedge.wasted", 0) - wasted0) >= 1, (
    "hedged pairs completed without won/wasted accounting")
total = nA + 8 + 6 + nC + 6 + 32
print(f"integrity driver: {total} requests over 4 phases, 0 silent "
      f"wrong answers; fail={int(c.get('serve.integrity.fail', 0))} "
      f"recovered={int(c.get('serve.integrity.recovered', 0))} "
      f"hedge sent={int(c.get('serve.hedge.sent', 0))} "
      f"won={int(c.get('serve.hedge.won', 0))} "
      f"quarantined={int(c.get('serve.integrity.quarantined', 0))} "
      f"unquarantined={int(c.get('serve.integrity.unquarantined', 0))}")
"""

# Negative leg: the SAME corruption with the plane disabled must
# deliver wrong answers (proving the injection is real) and the report
# over its JSONL must exit NONZERO (proving an escape is flagged).
_INTEGRITY_ESCAPE_DRIVER = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import faults
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import SolverService

svc = SolverService(cache=ExecutableCache(manifest_path=None),
                    batch_max=4, batch_window_s=0.002, dim_floor=16,
                    nrhs_floor=4, integrity=False)
assert svc._integrity is None
n = 12
rng = np.random.default_rng(0)
svc.submit("gesv", rng.standard_normal((n, n)) + n * np.eye(n),
           rng.standard_normal((n, 2))).result(timeout=300)  # warm
faults.configure("sdc_solve:every=2,seed=0")
faults.on()
wrong = 0
for i in range(8):
    r = np.random.default_rng(10 + i)
    A = r.standard_normal((n, n)) + n * np.eye(n)
    B = r.standard_normal((n, 2))
    X = svc.submit("gesv", A, B).result(timeout=300)
    scale = np.abs(A).max() * np.abs(X).max() + np.abs(B).max()
    if np.abs(A @ X - B).max() > 1e-6 * scale:
        wrong += 1
faults.reset()
svc.stop()
assert wrong >= 1, "undefended stream delivered no wrong X (site dead?)"
print(f"escape driver: {wrong} silent wrong answers delivered "
      "(integrity off, as designed)")
"""


def integrity_gate() -> int:
    """Integrity gate, three legs: (1) the integrity suite (ABFT
    checks, certification, quarantine, hedging, drain/restore-stuck
    satellites); (2) the four-phase SDC drill — sdc_factor + sdc_solve
    armed over a warmed mixed gesv/posv stream with zero silent wrong
    answers, quarantine engage/recover, hedges sent and won — judged
    by tools/integrity_report.py (exit 0); (3) the escape proof: the
    same corruption with the plane OFF delivers wrong answers and the
    report exits NONZERO on that JSONL."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_integrity.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=here,
    )
    if rc != 0:
        return rc
    with tempfile.TemporaryDirectory(prefix="slate_integrity_") as td:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for var in ("SLATE_TPU_FAULTS", "SLATE_TPU_FACTOR_CACHE",
                    "SLATE_TPU_TENANTS", "SLATE_TPU_ADAPTIVE",
                    "SLATE_TPU_INTEGRITY", "SLATE_TPU_WARMUP",
                    "SLATE_TPU_ARTIFACTS"):
            env.pop(var, None)
        jsonl = os.path.join(td, "integrity.jsonl")
        rc = subprocess.call(
            [sys.executable, "-c", _INTEGRITY_DRIVER],
            env=dict(env, SLATE_TPU_METRICS=jsonl,
                     SLATE_TPU_INTEGRITY="full,abft"),
            cwd=here,
        )
        if rc != 0:
            return rc
        rc = subprocess.call(
            [sys.executable, os.path.join("tools", "integrity_report.py"),
             jsonl],
            cwd=here,
        )
        if rc != 0:
            return rc
        # escape leg: plane off, same sites armed — the report MUST
        # flag the run (a verdict tool that cannot fail proves nothing)
        esc = os.path.join(td, "escape.jsonl")
        rc = subprocess.call(
            [sys.executable, "-c", _INTEGRITY_ESCAPE_DRIVER],
            env=dict(env, SLATE_TPU_METRICS=esc), cwd=here,
        )
        if rc != 0:
            return rc
        rc = subprocess.call(
            [sys.executable, os.path.join("tools", "integrity_report.py"),
             esc],
            cwd=here,
        )
        if rc == 0:
            print("integrity gate: report failed to flag an undefended "
                  "SDC escape")
            return 1
    return 0


# Race-plane drivers for the --race gate.  The stress leg runs the
# chaos/hedge/drain/quarantine paths under the INSTRUMENTED sync
# runtime (SLATE_TPU_SYNC_CHECK env — the production activation path,
# read at import before any lock is constructed) with seeded yield
# points, then dumps the runtime's findings for tools/race_report.py
# to judge: the shipped tree must come out clean.
_RACE_STRESS_DRIVER = """
import sys
import time
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import faults, sync
from slate_tpu.exceptions import SlateError
from slate_tpu.integrity import IntegrityPolicy
from slate_tpu.serve import buckets as bk
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import SolverService

out = sys.argv[1]
assert sync.is_on(), "SLATE_TPU_SYNC_CHECK must arm the runtime"
from slate_tpu.aux import metrics
assert metrics.is_on(), "stress leg needs metrics (the hedge p99 source)"
pol = IntegrityPolicy(mode="full", hedge_factor=0.5, hedge_min_age_s=0.005,
                      quarantine_cooldown_s=0.2)
svc = SolverService(cache=ExecutableCache(manifest_path=None), batch_max=4,
                    batch_window_s=0.002, dim_floor=16, nrhs_floor=4,
                    replicas=2, integrity=pol, retry_backoff_s=0.002,
                    breaker_cooldown_s=0.02, retry_seed=0)
n = 12
k = bk.bucket_for("gesv", n, n, 2, np.float64, floor=16, nrhs_floor=4)
svc.cache.ensure_manifest(k, (1, 4))
svc.warmup()

def prob(seed):
    r = np.random.default_rng(seed)
    return r.standard_normal((n, n)) + n * np.eye(n), r.standard_normal((n, 2))

# clean warmed traffic first: the straggler sweep compares queued age
# to the bucket's OWN p99 history
futs = [svc.submit("gesv", *prob(i)) for i in range(8)]
for f in futs:
    assert np.all(np.isfinite(f.result(timeout=300)))
# chaos phase: injected latency makes stragglers (hedge clones share
# futures across lanes), sdc_solve drives certificate re-execution and
# quarantine churn, worker_death exercises supervision re-enqueues,
# lock_contend inflates instrumented hold times — the concurrency
# paths PR14's review passes kept catching bugs in, now swept by the
# lockset/lock-order checkers under seeded yields
faults.configure(
    "latency:every=3,ms=40;sdc_solve:every=5,seed=1;"
    "worker_death:every=11;lock_contend:p=0.05,seed=2,ms=1")
faults.on()
ok = typed = 0
futs = [svc.submit("gesv", *prob(100 + i), retries=2) for i in range(32)]
for f in futs:
    try:
        assert np.all(np.isfinite(f.result(timeout=300)))
        ok += 1
    except SlateError:
        typed += 1
faults.reset()
assert ok + typed == 32, "a future hung"
# hedge-pressure rounds: the chaos phase above does not GUARANTEE a
# straggler hedge (timing-dependent), and a leg advertised as sweeping
# the hedge path must not pass without it — inflate every dispatch so
# the backlog ages past hedge_factor x p99 until the _HedgeGroup
# probes actually fire, bounded
rounds = 0
while "_HedgeGroup.delivered" not in sync.report()["field_names"]:
    rounds += 1
    assert rounds <= 5, (
        "hedge path never exercised: " + str(sync.report()["field_names"]))
    faults.configure("latency:every=1,ms=50")
    faults.on()
    futs = [svc.submit("gesv", *prob(1000 * rounds + i)) for i in range(16)]
    for f in futs:
        try:
            f.result(timeout=300)
        except SlateError:
            pass
    faults.reset()
svc.stop(drain=True, drain_timeout=60.0)
rep = sync.report()
sync.dump(out)
# coverage, not just a count: the worker-pool, hedge-group and
# factor-cache probes are distinct bug surfaces (PR14's fixes were on
# the hedge path) — a fields total alone cannot tell them apart
names = set(rep["field_names"])
assert {"_Replica.q", "_Replica.inflight"} <= names, names
assert "_HedgeGroup.delivered" in names, names
print(f"race stress driver: {ok} delivered / {typed} typed under the "
      f"instrumented runtime (+{rounds} hedge round(s)); "
      f"{rep['fields']} probed fields, "
      f"{len(rep['edges'])} runtime order edges, "
      f"{len(rep['violations'])} violations")
"""

# Planted lock-order inversion: two locks, two threads, inverted
# acquisition order (sequenced, so the fixture detects without
# deadlocking).  The detector must report the inversion with BOTH
# stacks, and race_report over the dump must exit NONZERO.
_RACE_INVERSION_DRIVER = """
import sys
import threading
from slate_tpu.aux import sync

out = sys.argv[1]
assert sync.is_on(), "SLATE_TPU_SYNC_CHECK must arm the runtime"
A = sync.Lock(name="fixture.A")
B = sync.Lock(name="fixture.B")

def t1():
    with A:
        with B:
            pass

def t2():
    with B:
        with A:
            pass

th = threading.Thread(target=t1); th.start(); th.join()  # records A -> B
th = threading.Thread(target=t2); th.start(); th.join()  # inverts: B -> A
sync.dump(out)
v = [x for x in sync.violations() if x["kind"] == "lock_order"]
assert v and len(v[0]["stacks"]) == 2 and all(v[0]["stacks"]), v
print("race inversion driver: planted inversion detected, both stacks")
"""

# Planted unguarded write: a shared field probed by guarded() touched
# by two threads with no common lock and no happens-before edge.  The
# lockset checker must flag it, and race_report must exit NONZERO.
_RACE_UNGUARDED_DRIVER = """
import sys
import threading
from slate_tpu.aux import sync

out = sys.argv[1]
assert sync.is_on(), "SLATE_TPU_SYNC_CHECK must arm the runtime"

class Shared:
    def __init__(self):
        self.hits = 0  # guarded by: lock — and the writes below skip it

s = Shared()

def writer():
    sync.guarded(s, "hits")
    s.hits += 1

th = threading.Thread(target=writer); th.start(); th.join()
sync.guarded(s, "hits")  # main thread: no lock, no hand-off edge
s.hits += 1
sync.dump(out)
v = [x for x in sync.violations() if x["kind"] == "lockset"]
assert v and len(v[0]["stacks"]) == 2, v
print("race unguarded driver: planted unguarded write detected")
"""


def race_gate() -> int:
    """Race/deadlock gate, five legs:

    1. the race suite (static rule fixtures, the deterministic
       deadlock-reproduction and Condition hand-off regression tests);
    2. the static rules over the full tree (lock-discipline +
       race-guarded-by + race-lock-order) via the slate-lint CLI;
    3. the lock-order graph artifact check (cycle-free AND in sync
       with the checked-in LOCK_ORDER.json);
    4. the instrumented chaos/hedge/drain/quarantine stress leg under
       SLATE_TPU_SYNC_CHECK with seeded yields, judged clean by
       tools/race_report.py;
    5. the two planted fixtures (lock-order inversion, unguarded
       annotated write) — race_report must exit NONZERO on each (a
       verdict tool that cannot fail proves nothing)."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for var in ("SLATE_TPU_FAULTS", "SLATE_TPU_TENANTS",
                "SLATE_TPU_ADAPTIVE", "SLATE_TPU_FACTOR_CACHE",
                "SLATE_TPU_INTEGRITY", "SLATE_TPU_SYNC_CHECK",
                "SLATE_TPU_WARMUP", "SLATE_TPU_ARTIFACTS",
                "SLATE_TPU_METRICS"):
        env.pop(var, None)
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_races.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=env, cwd=here,
    )
    if rc != 0:
        return rc
    rc = subprocess.call(
        [sys.executable, os.path.join("tools", "slate_lint.py"),
         "--rules", "lock-discipline,race-guarded-by,race-lock-order"],
        env=env, cwd=here,
    )
    if rc != 0:
        print("race gate: static race rules flagged the tree")
        return rc
    rc = subprocess.call(
        [sys.executable, os.path.join("tools", "race_report.py"),
         "--check-graph"],
        env=env, cwd=here,
    )
    if rc != 0:
        print("race gate: lock-order graph artifact out of sync")
        return rc
    with tempfile.TemporaryDirectory(prefix="slate_race_") as td:
        legs = (
            ("stress", _RACE_STRESS_DRIVER,
             "1,seed=7,yield=0.2,yield_us=200", True),
            ("inversion", _RACE_INVERSION_DRIVER, "1,seed=7", False),
            ("unguarded", _RACE_UNGUARDED_DRIVER, "1,seed=7", False),
        )
        for name, driver, spec, expect_clean in legs:
            dump = os.path.join(td, f"{name}.json")
            leg_env = dict(env, SLATE_TPU_SYNC_CHECK=spec)
            if name == "stress":
                # straggler hedging needs the p99 source: metrics on
                # (the sink file is scratch — race_report judges the
                # sync dump, not the JSONL)
                leg_env["SLATE_TPU_METRICS"] = os.path.join(
                    td, "stress_metrics.jsonl")
            rc = subprocess.call(
                [sys.executable, "-c", driver, dump],
                env=leg_env, cwd=here,
            )
            if rc != 0:
                print(f"race gate: {name} driver failed (rc={rc})")
                return rc
            rc = subprocess.call(
                [sys.executable, os.path.join("tools", "race_report.py"),
                 dump],
                cwd=here,
            )
            if expect_clean and rc != 0:
                print(f"race gate: {name} leg reported violations on "
                      "the shipped tree")
                return rc
            if not expect_clean and rc == 0:
                print(f"race gate: report failed to flag the planted "
                      f"{name} fixture")
                return 1
    return 0


# the full-tree slate-lint run must stay cheap enough to gate every PR
# on the 2-core CI box; blowing this budget is itself a gate failure
LINT_BUDGET_S = 15.0


def lint_gate() -> int:
    """Static-analysis gate (slate_tpu/analysis + tools/slate_lint.py):

    1. the lint test suite — per-rule fixture positives/negatives,
       suppression + baseline semantics, JSON schema, and a self-run
       asserting the shipped tree is clean;
    2. a full-tree slate-lint run against the checked-in baseline —
       nonzero on any NEW finding, and nonzero if the run blows the
       :data:`LINT_BUDGET_S` runtime budget.
    """
    here = os.path.dirname(os.path.abspath(__file__)) or "."
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_lint.py", "-q",
         "-p", "no:cacheprovider"],
        env=env, cwd=here,
    )
    if rc != 0:
        print("lint: fixture/self-run suite failed")
        return rc
    # the CLI, not an in-process import: tools/slate_lint.py loads the
    # analysis package without executing slate_tpu/__init__, so this
    # gate keeps reporting parse errors as findings even when the tree
    # is import-broken.  Wall clock (interpreter startup included) is
    # what the budget means on the CI box.
    t0 = time.monotonic()
    rc = subprocess.call(
        [sys.executable, os.path.join("tools", "slate_lint.py")],
        env=env, cwd=here,
    )
    wall = time.monotonic() - t0
    if wall > LINT_BUDGET_S:
        print(f"lint: full-tree run took {wall:.1f}s, over the "
              f"{LINT_BUDGET_S:.0f}s per-PR budget")
        return 1
    if rc != 0:
        print("lint: new findings (fix them, suppress with a "
              "justification, or --write-baseline for accepted legacy)")
        return rc
    print(f"lint: tree clean ({wall:.1f}s)")
    return 0


# Soak driver: ~10^4 requests (x100 with SLATE_SOAK_SCALE=full)
# replayed open-loop against ONE service with EVERY plane armed at
# once — batching, factor cache, tenants+adaptive admission, deadline
# traffic, integrity certification with hedging and quarantine — while
# latency/SDC/worker-death faults fire and the health timeline
# samples.  Phase 2 is the record->replay round trip: a low-rate
# stream is recorded off the live delivery tap, the RECORDING is
# replayed twice (same spec, same seed), and the driver asserts the
# workload-mix histograms agree and the two runs land within the
# documented tolerance.  tools/soak_report.py judges the dump.
_SOAK_DRIVER = """
import os
import sys
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import faults, metrics, spans
from slate_tpu.integrity import policy as ipol
from slate_tpu.serve import buckets as bk
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.factor_cache import FactorCache
from slate_tpu.serve.service import SolverService
from slate_tpu.soak import record, replay
from slate_tpu.soak.timeline import TimelineSampler

full = os.environ.get("SLATE_SOAK_SCALE") == "full"
S = 100 if full else 1
metrics.on()
metrics.reset()
spans.on(ring=262144 if full else 65536)
svc = SolverService(
    cache=ExecutableCache(manifest_path=None), batch_max=8,
    batch_window_s=0.001, dim_floor=16, nrhs_floor=4, replicas=2,
    retry_backoff_s=0.002, breaker_cooldown_s=0.02, retry_seed=0,
    factor_cache=FactorCache(max_entries=64),
    tenants="gold:weight=4;good:weight=2;free:rate=300,share=0.5;"
            "abuser:rate=60,burst=16,share=0.25",
    adaptive=True, latency_budget_s=0.5,
    integrity=ipol.parse_spec("full,hedge=1.5,cooldown=0.25"),
)
for rt, n in (("gesv", 12), ("posv", 12), ("gesv", 24)):
    k = bk.bucket_for(rt, n, n, 2, np.float64, floor=16, nrhs_floor=4)
    svc.cache.ensure_manifest(k, (1, 8))
    # the factor cache dispatches hits onto the solve-phase sibling:
    # omit it from warmup and the soak compiles mid-run
    svc.cache.ensure_manifest(k.solve_sibling(), (1, 8))
svc.warmup()

spec = replay.merge_specs(
    replay.gen_repeated_a(5000 * S, seed=2, rate_rps=240, distinct=10),
    replay.gen_repeated_a(1500 * S, seed=3, rate_rps=75, distinct=4,
                          routine="posv"),
    replay.gen_multitenant(1800 * S, seed=1, rate_rps=88),
    replay.gen_deadline_storm(800 * S, seed=4, rate_rps=40),
    replay.gen_adversarial_flood(900 * S, seed=5, rate_rps=45),
)
rt_spec = replay.merge_specs(
    replay.gen_multitenant(700, seed=11, rate_rps=70),
    replay.gen_repeated_a(500, seed=12, rate_rps=60, distinct=5),
)
# pool-warm BOTH phases' factors, then zero the books: the soak
# measures the steady state (0 compiles, warm factor cache)
replay.replay(svc, replay.warm_spec(spec), speed=1.0, seed=0)
replay.replay(svc, replay.warm_spec(rt_spec), speed=1.0, seed=0)
metrics.reset()

faults.configure("latency:every=97,ms=30;sdc_solve:every=211,seed=3;"
                 "worker_death:every=1501")
faults.on()
sampler = TimelineSampler(svc, period_s=0.05).start()
res = replay.replay(svc, spec, speed=1.0, seed=0)
faults.reset()
assert res["submitted"] == (res["delivered"] + res["typed_errors"]
                            + res["refused"]), res
print(f"soak main: {res['submitted']} submitted, "
      f"{res['delivered']} delivered, {res['typed_errors']} typed, "
      f"{res['refused']} refused, {res['bad_results']} bad, "
      f"{res['requests_per_s']} req/s, "
      f"p99={(res['p99_s'] or 0) * 1e3:.1f}ms")

# ---- elastic lifecycle under the instrumented sync runtime ---------
# grow the fleet by one lane (phase 2 traffic rides on 3 replicas),
# shrink it back after the determinism runs: the add/remove paths run
# inside the same SLATE_TPU_SYNC_CHECK net as the rest of the drill
added = svc.add_replica()
with svc._cond:
    fleet = len(svc._replicas)
assert fleet == 3, fleet
print(f"soak: replica {added} added, fleet={fleet}")

# ---- phase 2: record -> replay round trip + determinism ------------
rec = record.Recorder().attach()
rt_res = replay.replay(svc, rt_spec, speed=1.0, seed=0)
rec.detach()
recorded = rec.rows()
assert len(recorded) == rt_res["delivered"] + rt_res["typed_errors"], (
    len(recorded), rt_res)
mix_in = record.mix_histogram(recorded)

runs = []
for i in (0, 1):
    r2 = record.Recorder().attach()
    runs.append((replay.replay(svc, recorded, speed=1.0, seed=0),
                 record.mix_histogram(r2.detach().rows())))
mix_out = runs[0][1]

def close(a, b, what):
    assert set(a) == set(b), (what, sorted(a), sorted(b))
    for key in a:
        tol = max(5, int(0.05 * a[key]))
        assert abs(a[key] - b[key]) <= tol, (what, key, a[key], b[key])

close(mix_in["tenants"], mix_out["tenants"], "tenants")
close(mix_in["priorities"], mix_out["priorities"], "priorities")
close(mix_in["shapes"], mix_out["shapes"], "shapes")
# repeat groups: fingerprints are of the matrix BYTES, which differ
# between original and regenerated operands — the preserved invariant
# is the group-size structure, not the fingerprint values
gs_in = sorted(mix_in["repeat_groups"].values())
gs_out = sorted(mix_out["repeat_groups"].values())
assert abs(len(gs_in) - len(gs_out)) <= 1, (gs_in, gs_out)
assert abs(sum(gs_in) - sum(gs_out)) <= max(10, int(0.05 * sum(gs_in)))
# determinism: same recorded spec + same seed, twice — delivered
# tallies agree within the documented tolerance (scheduling jitter
# moves a few requests between delivered and shed, never the sum)
(ra, _), (rb, _) = runs
for r in (ra, rb):
    assert r["submitted"] == (r["delivered"] + r["typed_errors"]
                              + r["refused"]), r
tol = max(10, int(0.02 * ra["submitted"]))
assert abs(ra["delivered"] - rb["delivered"]) <= tol, (ra, rb)
print(f"round trip: {len(recorded)} recorded, mixes agree; "
      f"determinism: {ra['delivered']} vs {rb['delivered']} delivered")

# drain the added lane back out mid-traffic-history: every queued
# request it held must re-home (none dropped — the books below still
# reconcile) and health must show the lane as a terminal row
removed = svc.remove_replica(added, drain_timeout=120)
h = svc.health()
states = {l["name"]: l.get("state") for l in h["replicas"]}
assert states.get(removed) == "removed", states
assert removed in (h["capacity"] or {}).get("terminal_lanes", [removed]), h
with svc._cond:
    fleet = len(svc._replicas)
assert fleet == 2, fleet
print(f"soak: replica {removed} drained + removed, fleet={fleet}")

pressure = spans.pressure()
if pressure["evicted"] == 0:
    replay.orphan_spans()  # publishes the soak.orphan_spans gauge
else:  # an evicting ring fabricates orphans; report skips the check
    print(f"span ring evicted {pressure['evicted']} - orphan audit "
          "skipped")
sampler.stop()
svc.stop(drain=True, drain_timeout=300)
c = metrics.counters()
assert c["serve.requests"] == c["soak.submitted"] - c["soak.refused"], (
    c["serve.requests"], c["soak.submitted"], c["soak.refused"])
# the gate armed SLATE_TPU_SYNC_CHECK: the whole drill (replica
# add/remove included) ran under the lockset/inversion checker, and a
# single recorded violation fails the soak right here
from slate_tpu.aux import sync
assert sync.is_on(), "SLATE_TPU_SYNC_CHECK must arm the runtime"
v = sync.violations()
assert not v, ("sync checker flagged the drill", v[:3])
metrics.dump()
print("soak driver: all phases complete, books reconcile, sync clean")
"""

# Negative leg: the SAME SDC corruption with the integrity plane AND
# the factor-cache residual fence disarmed must deliver wrong answers
# to the replay engine's client-side check (soak.bad_results > 0) and
# the soak report over that JSONL must exit NONZERO.
_SOAK_ESCAPE_DRIVER = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import faults, metrics, spans
from slate_tpu.serve import buckets as bk
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import SolverService
from slate_tpu.soak import replay
from slate_tpu.soak.timeline import TimelineSampler

metrics.on()
metrics.reset()
spans.on(ring=8192)
svc = SolverService(cache=ExecutableCache(manifest_path=None),
                    batch_max=8, batch_window_s=0.001, dim_floor=16,
                    nrhs_floor=4, replicas=2, factor_cache=False,
                    integrity=False)
assert svc._integrity is None
k = bk.bucket_for("gesv", 12, 12, 2, np.float64, floor=16, nrhs_floor=4)
svc.cache.ensure_manifest(k, (1, 8))
svc.warmup()
metrics.reset()
spec = replay.gen_repeated_a(400, seed=7, rate_rps=200, distinct=4)
faults.configure("sdc_solve:every=7,seed=5")
faults.on()
sampler = TimelineSampler(svc, period_s=0.05).start()
res = replay.replay(svc, spec, speed=1.0, seed=0)
faults.reset()
sampler.stop()
replay.orphan_spans()  # publishes the soak.orphan_spans gauge
svc.stop(drain=True, drain_timeout=120)
metrics.dump()
assert res["bad_results"] > 0, (
    "undefended soak delivered no wrong X (site dead?)", res)
print(f"escape driver: {res['bad_results']} silent wrong answers "
      "delivered (integrity off, as designed)")
"""


def soak_gate(full: bool = False) -> int:
    """Trace-driven soak gate, three legs: (1) the soak suite
    (recorder/replay/timeline units, all-planes health shape,
    metrics_merge); (2) the soak drill — ~10^4 requests (~10^6 with
    ``--full``) against a fully-armed 2-replica service under
    latency/SDC/worker-death faults, with the record->replay round
    trip and the two-run determinism check inline — judged by
    tools/soak_report.py (exit 0: books reconcile, zero escapes, zero
    orphans, tails in budget, compile-free steady state, every
    disruption recovered); (3) the escape proof: the same SDC with
    every defense disarmed must make the report exit NONZERO."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_soak.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=here,
    )
    if rc != 0:
        return rc
    with tempfile.TemporaryDirectory(prefix="slate_soak_") as td:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for var in ("SLATE_TPU_FAULTS", "SLATE_TPU_FACTOR_CACHE",
                    "SLATE_TPU_TENANTS", "SLATE_TPU_ADAPTIVE",
                    "SLATE_TPU_INTEGRITY", "SLATE_TPU_WARMUP",
                    "SLATE_TPU_ARTIFACTS"):
            env.pop(var, None)
        jsonl = os.path.join(td, "soak.jsonl")
        # the drill runs under the instrumented sync runtime: every
        # lock acquisition in the replay (including the add/remove
        # replica lifecycle it now exercises) is order-checked against
        # LOCK_ORDER.json, so a lock-order regression fails the soak
        # even before the race gate runs
        denv = dict(env, SLATE_TPU_METRICS=jsonl,
                    SLATE_TPU_SYNC_CHECK="1")
        if full:
            denv["SLATE_SOAK_SCALE"] = "full"
        rc = subprocess.call(
            [sys.executable, "-c", _SOAK_DRIVER], env=denv, cwd=here,
        )
        if rc != 0:
            return rc
        rc = subprocess.call(
            [sys.executable, os.path.join("tools", "soak_report.py"),
             jsonl, "--p99-budget-ms", "2000",
             "--tenant-p99-budget-ms", "2000",
             "--min-timeline-rows", "50",
             "--min-delivered", str(500000 if full else 5000)],
            cwd=here,
        )
        if rc != 0:
            return rc
        # escape leg: defenses off, same SDC — the report MUST flag
        # the run (a verdict tool that cannot fail proves nothing).
        # "defenses off" means the DELIVERY defenses: the instrumented
        # sync runtime stays armed so a lock-order regression on the
        # escape path cannot hide behind the expected nonzero verdict
        esc = os.path.join(td, "escape.jsonl")
        rc = subprocess.call(
            [sys.executable, "-c", _SOAK_ESCAPE_DRIVER],
            env=dict(env, SLATE_TPU_METRICS=esc,
                     SLATE_TPU_SYNC_CHECK="1"),
            cwd=here,
        )
        if rc != 0:
            return rc
        rc = subprocess.call(
            [sys.executable, os.path.join("tools", "soak_report.py"), esc],
            cwd=here,
        )
        if rc == 0:
            print("soak gate: report failed to flag an undefended "
                  "SDC escape")
            return 1
    return 0


# Elastic-capacity driver: one recorded bursty trace (gen_burst ->
# record.save -> record.load, so the measured workload IS a spec file)
# replayed twice under a fixed per-dispatch latency tax that saturates
# a single lane at ~60 req/s.  Leg 1: a static replicas=1 fleet eats
# the 120 req/s burst and blows its tail budget.  Leg 2: the SAME
# trace with SLATE_TPU_SCALE armed — the autoscaler must grow the
# fleet through the burst (artifact-warmed lanes, zero compiles),
# hold the budget, and give every lane back.  The driver only
# publishes the evidence (scale.gate.* gauges + the decision
# timeline); tools/capacity_report.py renders the verdict.
_SCALE_DRIVER = """
import os
import sys
import threading
import time
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from slate_tpu.aux import faults, metrics, spans
from slate_tpu.serve import buckets as bk
from slate_tpu.scale import gate
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.factor_cache import FactorCache
from slate_tpu.serve.service import SolverService
from slate_tpu.soak import record, replay

art, trace = sys.argv[1], sys.argv[2]
BUDGET_S = 1.0
POLICY = ("min=1,max=3,up=1.0,down=0.2,up_cooldown=0.25,"
          "down_cooldown=2.0,step=2,period=0.05")

metrics.on()
metrics.reset()
spans.on(ring=65536)

spec = replay.gen_burst(500, seed=9, base_rps=30, burst_rps=120,
                        burst_start_s=1.0, burst_len_s=2.0,
                        n=12, nrhs=2, distinct=4)
record.save(spec, trace, source="gen_burst")
rows = record.load(trace)

def build():
    svc = SolverService(
        cache=ExecutableCache(manifest_path=None, artifact_dir=art),
        batch_max=1, batch_window_s=0.0005, dim_floor=16,
        nrhs_floor=4, replicas=1,
        factor_cache=FactorCache(max_entries=16),
    )
    k = bk.bucket_for("gesv", 12, 12, 2, np.float64, floor=16,
                      nrhs_floor=4)
    svc.cache.ensure_manifest(k, (1,))
    svc.cache.ensure_manifest(k.solve_sibling(), (1,))
    svc.warmup()
    # factor-pool warm with the replay's seed: the measured legs hit
    replay.replay(svc, replay.warm_spec(rows), speed=1.0, seed=0)
    return svc

# fixed latency tax on every dispatch: capacity is lanes, not luck
faults.configure("latency:every=1,ms=12")

# ---- leg 1: static fleet (replicas=1, scaler unarmed) --------------
os.environ.pop("SLATE_TPU_SCALE", None)
svc = build()
assert svc._scaler is None, "scaler armed without SLATE_TPU_SCALE"
faults.on()
res_static = replay.replay(svc, rows, speed=1.0, seed=0)
faults.off()  # off, not reset: leg 2 re-arms the SAME latency tax
svc.stop(drain=True, drain_timeout=120)
print(f"static leg: p99={(res_static['p99_s'] or 0) * 1e3:.1f}ms "
      f"over {res_static['submitted']} requests")

# ---- leg 2: elastic fleet, same trace, same faults -----------------
os.environ["SLATE_TPU_SCALE"] = POLICY
svc = build()
assert svc._scaler is not None, "SLATE_TPU_SCALE failed to arm"
metrics.reset()  # evidence window: the measured replay only

peak = {"n": 1}
watch_stop = threading.Event()
def _watch():
    while not watch_stop.is_set():
        with svc._cond:
            n = len(svc._replicas)
        peak["n"] = max(peak["n"], n)
        time.sleep(0.02)
watcher = threading.Thread(target=_watch, daemon=True)
watcher.start()

faults.on()
res_elastic = replay.replay(svc, rows, speed=1.0, seed=0)
faults.reset()  # teardown proper: the tail drain runs untaxed
# quiet tail: the scaler must give the burst capacity back on its own
deadline = time.monotonic() + 30
while time.monotonic() < deadline:
    with svc._cond:
        n_end = len(svc._replicas)
    if n_end == 1:
        break
    time.sleep(0.05)
watch_stop.set()
watcher.join(2)
compiles = int(metrics.counters().get("jit.compilations", 0))
# zero-steady-state-compiles accounting: a scale-up lane's device
# prime inside add_replica IS a counted backend compile
# (serve.device_primes — cold-start budget, pre-traffic).  The gate
# claim is about the DISPATCH path: every compile in the window must
# be such a prime, so steady-state compiles = total - primes.
primes = int(metrics.counters().get("serve.device_primes", 0))

gate.publish({
    "static_p99_s": res_static["p99_s"] or 0.0,
    "elastic_p99_s": res_elastic["p99_s"] or 0.0,
    "budget_s": BUDGET_S,
    "replica_peak": peak["n"],
    "replicas_end": n_end,
    "min_replicas": 1,
    "max_replicas": 3,
    "up_threshold": 1.0,
    "new_lane_compiles": compiles - primes,
    "device_primes": primes,
})
svc.stop(drain=True, drain_timeout=120)
metrics.dump()
print(f"elastic leg: p99={(res_elastic['p99_s'] or 0) * 1e3:.1f}ms, "
      f"peak={peak['n']} lanes, end={n_end}, "
      f"steady-state compiles={compiles - primes} "
      f"({primes} pre-traffic lane primes)")
"""


def scale_gate() -> int:
    """Elastic-capacity gate, two legs: (1) the scale suite (pure
    controller/aggregator/warmup-plan units plus the live add/remove
    lifecycle tests); (2) the burst drill — one recorded bursty trace
    replayed against a static fleet (must MISS its p99 budget) and an
    elastic fleet (must HOLD it inside max_replicas, warm every new
    lane from artifacts with zero compiles, and return to
    min_replicas) — judged by tools/capacity_report.py."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_scale.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=here,
    )
    if rc != 0:
        return rc
    with tempfile.TemporaryDirectory(prefix="slate_scale_") as td:
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
        )
        for var in ("SLATE_TPU_FAULTS", "SLATE_TPU_FACTOR_CACHE",
                    "SLATE_TPU_TENANTS", "SLATE_TPU_ADAPTIVE",
                    "SLATE_TPU_INTEGRITY", "SLATE_TPU_WARMUP",
                    "SLATE_TPU_ARTIFACTS", "SLATE_TPU_SCALE"):
            env.pop(var, None)
        jsonl = os.path.join(td, "scale.jsonl")
        art = os.path.join(td, "artifacts")
        trace = os.path.join(td, "burst.jsonl")
        # the burst drill runs under the instrumented sync runtime too:
        # the add/remove replica lifecycle is the lock-heaviest path in
        # the tree (same arming as the soak drill)
        rc = subprocess.call(
            [sys.executable, "-c", _SCALE_DRIVER, art, trace],
            env=dict(env, SLATE_TPU_METRICS=jsonl,
                     SLATE_TPU_SYNC_CHECK="1"),
            cwd=here,
        )
        if rc != 0:
            return rc
        rc = subprocess.call(
            [sys.executable, os.path.join("tools", "capacity_report.py"),
             jsonl],
            cwd=here,
        )
    return rc


# Fleet drill: one router (this process) + two REAL spawned worker
# processes on CPU.  host0 carries an SDC stream (sdc_solve:every=2),
# host1 a latency tax — the router's full certification, quarantine,
# re-dispatch and lifecycle planes must contain both.  Phases: SDC
# quarantine + probe recovery, fleet-wide quota abuse, a real SIGKILL
# host death (chaos site host_death) with respawn -> rejoin -> forced
# probe, injected rpc timeouts + a partition, then the observability
# fan-in (per-host dumps, stitched trace, orphan gauge).  Every
# delivery is reference-checked client-side (note_bad_result) — the
# drill only publishes evidence; tools/fleet_report.py is the judge.
_FLEET_DRIVER = """
import os
import subprocess
import sys
import time
import numpy as np
from slate_tpu.aux import faults, metrics, spans
from slate_tpu.exceptions import SlateError
from slate_tpu.fleet.router import (
    FleetRouter, note_bad_result, note_trace_orphans,
)
from slate_tpu.serve.service import Rejected

outdir, repo = sys.argv[1], sys.argv[2]

metrics.on()
metrics.reset()
spans.on(ring=65536)

N = 12
rng = np.random.default_rng(3)
A = (rng.standard_normal((N, N)) + N * np.eye(N)).astype(np.float32)

def prob(seed):
    return np.random.default_rng(seed).standard_normal(
        (N, 2)).astype(np.float32)

base = {
    "JAX_PLATFORMS": "cpu",
    "SLATE_TPU_METRICS": "1",
    "SLATE_TPU_TRACE_RING": "65536",
    "SLATE_TPU_SYNC_CHECK": "1",
    "SLATE_TPU_FAULTS": None,
}
host0 = dict(base, SLATE_TPU_FAULTS="sdc_solve:every=2")
host1 = dict(base, SLATE_TPU_FAULTS="latency:every=3,ms=40")

r = FleetRouter(
    spawn=2, cert="full",
    tenants="abuser:rate=4,burst=4;victim:rate=500,burst=100",
    heartbeat_s=0.2, rpc_timeout_s=30.0, dead_after=2,
    redispatch_max=2, hedge_s=1.0, respawn=True,
    quarantine_cooldown_s=0.4, spawn_env=[host0, host1], seed=7,
)
r.start()

checked = [0]

def solve(tenant="victim", seed=0):
    B = prob(seed)
    try:
        X = r.submit("gesv", A, B, deadline=60.0,
                     tenant=tenant).result(timeout=120)
    except Exception as e:
        return e
    # NaN-safe reference check: any non-finite or off-fence entry is a
    # silent wrong answer the defenses let through
    if not np.all(np.abs(A @ X - B) <= 1e-2):
        note_bad_result()
    checked[0] += 1
    return None

# ---- phase 1: SDC containment, quarantine + probe recovery ---------
for i in range(120):
    e = solve(seed=100 + i)
    assert e is None, f"victim solve failed under SDC: {e!r}"
    c = metrics.counters()
    if (c.get("fleet.quarantined", 0) >= 1
            and c.get("fleet.unquarantined", 0) >= 1):
        break
    time.sleep(0.01)
c = metrics.counters()
assert c.get("fleet.quarantined", 0) >= 1, "sdc host never quarantined"
assert c.get("fleet.unquarantined", 0) >= 1, "quarantine never probed back"
print(f"phase 1: quarantine engaged+recovered after {i + 1} solves")

# ---- phase 2: fleet-wide quota (abuser refused, victim whole) ------
rejected = 0
for i in range(14):
    e = solve(tenant="abuser", seed=200 + i)
    if e is not None:
        assert isinstance(e, Rejected), f"abuser got {e!r}, not Rejected"
        rejected += 1
assert rejected > 0, "abuser burst never hit the fleet-wide quota"
for i in range(6):
    e = solve(seed=300 + i)
    assert e is None, f"victim starved during abuse: {e!r}"
print(f"phase 2: abuser rejected {rejected}/14, victim served")

# ---- phase 3: real host death (SIGKILL) + fail-fast re-dispatch ----
# contract: every future RESOLVES — a correct re-dispatched answer or
# a TYPED error (the sole survivor may be the SDC lane, whose cert
# failures have no re-dispatch target until the respawn) — none hang,
# none deliver garbage (solve() reference-checks every delivery)
faults.configure("host_death:once")
faults.on()
delivered3 = 0
for i in range(10):
    e = solve(seed=400 + i)
    if e is None:
        delivered3 += 1
    else:
        assert isinstance(e, SlateError), f"untyped failure: {e!r}"
# death is DECLARED by the liveness plane (heartbeat misses reaching
# dead_after), not by the request path — the 10 solves above can
# finish inside a single beat, so give the monitor a few beats
deadline = time.monotonic() + 10
while time.monotonic() < deadline:
    if metrics.counters().get("fleet.host_dead", 0) >= 1:
        break
    time.sleep(0.05)
c = metrics.counters()
assert c.get("fleet.host_dead", 0) >= 1, "death was never declared"
assert c.get("fleet.redispatched", 0) >= 1, "no re-dispatch recovered it"
assert delivered3 >= 1, "no request survived the host death"
print(f"phase 3: host died, 10/10 futures resolved "
      f"({delivered3} delivered)")

# ---- phase 4: respawn -> rejoin -> forced certification probe ------
# a rejoined host only turns live once one of its deliveries is
# force-certified, so traffic must keep flowing while we wait (the
# probe rides a routed solve — either picked directly or via the
# re-dispatch of a cert failure on the SDC lane)
deadline = time.monotonic() + 60
states = {}
while time.monotonic() < deadline:
    states = {k: v["state"] for k, v in r.health()["hosts"].items()}
    if all(s == "live" for s in states.values()):
        break
    e = solve(seed=510)
    if e is not None:
        assert isinstance(e, SlateError), f"untyped failure: {e!r}"
    time.sleep(0.05)
assert all(s == "live" for s in states.values()), (
    f"dead host never rejoined live (states={states})")
assert metrics.counters().get("fleet.host_respawned", 0) >= 1, (
    "death was absorbed without a respawn")
for i in range(12):
    e = solve(seed=500 + i)
    assert e is None, f"victim solve failed after rejoin: {e!r}"
print("phase 4: host respawned, probe-certified, serving again")

# ---- phase 5: rpc timeouts + a partition, absorbed by retry --------
faults.configure("rpc_timeout:every=4;host_partition:once")
delivered5 = 0
for i in range(12):
    e = solve(seed=600 + i)
    if e is None:
        delivered5 += 1
    else:
        assert isinstance(e, SlateError), f"untyped failure: {e!r}"
faults.reset()
assert delivered5 >= 9, (
    f"timeouts/partition overwhelmed the fleet: {delivered5}/12")
print(f"phase 5: timeouts/partition absorbed ({delivered5}/12 delivered)")

# ---- fan-in: per-host dumps, stitched trace, orphan gauge ----------
replies = r.dump_hosts(outdir)
assert len(replies) == 2, f"expected both hosts to dump, got {replies}"
router_trace = os.path.join(outdir, "router.trace.json")
spans.export_chrome(router_trace, process_name="router")
traces = [router_trace] + sorted(
    os.path.join(outdir, f) for f in os.listdir(outdir)
    if f.endswith(".trace.json") and not f.startswith("router")
)
out = subprocess.run(
    [sys.executable, os.path.join(repo, "tools", "trace_stitch.py"),
     "--allow-orphans",
     "-o", os.path.join(outdir, "stitched.trace.json"), *traces],
    capture_output=True, text=True,
)
assert out.returncode == 0, out.stdout + out.stderr
line = out.stdout.strip().splitlines()[-1]
note_trace_orphans(int(line.rpartition("orphans=")[2]))
print(line)
r.stop(drain=True)
metrics.dump()
print(f"fleet drill: {checked[0]} reference-checked deliveries")
"""


# Escape leg: the SAME SDC stream with certification off — corrupted
# deliveries now reach the client, the reference check counts them
# (fleet.bad_results), and tools/fleet_report.py MUST exit nonzero.
_FLEET_ESCAPE_DRIVER = """
import sys
import numpy as np
from slate_tpu.aux import metrics
from slate_tpu.fleet.router import FleetRouter, note_bad_result

metrics.on()
metrics.reset()

N = 12
rng = np.random.default_rng(3)
A = (rng.standard_normal((N, N)) + N * np.eye(N)).astype(np.float32)

host0 = {
    "JAX_PLATFORMS": "cpu",
    "SLATE_TPU_FAULTS": "sdc_solve:every=2",
    "SLATE_TPU_METRICS": None,
    "SLATE_TPU_TRACE_RING": None,
}
r = FleetRouter(spawn=1, cert="off", heartbeat_s=0.25,
                rpc_timeout_s=30.0, spawn_env=[host0], seed=7)
r.start()
bad = 0
for i in range(8):
    B = np.random.default_rng(700 + i).standard_normal(
        (N, 2)).astype(np.float32)
    X = r.submit("gesv", A, B, deadline=60.0).result(timeout=120)
    if not np.all(np.abs(A @ X - B) <= 1e-2):
        note_bad_result()
        bad += 1
r.stop(drain=True)
metrics.dump()
print(f"escape leg: {bad} silent wrong answers delivered (cert off)")
assert bad > 0, "sdc stream produced no corrupt delivery to flag"
"""


def fleet_gate() -> int:
    """Cross-process defense gate, three legs: (1) the fleet suite
    (wire framing, router edge cases — exactly-once under host death
    with a hedge twin inflight, drain racing re-dispatch, stats-only
    reports after death, forced rejoin probes — the worker front-end,
    and the stitch/merge/report tools); (2) the 3-process CPU drill —
    router + 2 spawned workers, host0 carrying an SDC stream and host1
    a latency tax, driven through quota abuse, a real SIGKILL host
    death with respawn/rejoin/probe, and injected rpc timeouts +
    partition, its per-host dumps merged (``metrics_merge --tag``) and
    traces stitched (``trace_stitch``), judged by
    tools/fleet_report.py; (3) the escape proof: certification off,
    the same SDC — the report MUST exit nonzero."""
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__)) or "."
    rc = subprocess.call(
        [sys.executable, "-m", "pytest", "tests/test_fleet.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=here,
    )
    if rc != 0:
        return rc
    with tempfile.TemporaryDirectory(prefix="slate_fleet_") as td:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for var in ("SLATE_TPU_FAULTS", "SLATE_TPU_FACTOR_CACHE",
                    "SLATE_TPU_TENANTS", "SLATE_TPU_ADAPTIVE",
                    "SLATE_TPU_INTEGRITY", "SLATE_TPU_WARMUP",
                    "SLATE_TPU_ARTIFACTS", "SLATE_TPU_SCALE",
                    "SLATE_TPU_FLEET", "SLATE_TPU_FLEET_TENANTS"):
            env.pop(var, None)
        outdir = os.path.join(td, "dumps")
        os.makedirs(outdir)
        jsonl = os.path.join(td, "router.jsonl")
        # the drill's router AND both workers run the instrumented
        # sync runtime: every router<->host lock edge in the drill is
        # order-checked against LOCK_ORDER.json
        rc = subprocess.call(
            [sys.executable, "-c", _FLEET_DRIVER, outdir, here],
            env=dict(env, SLATE_TPU_METRICS=jsonl,
                     SLATE_TPU_SYNC_CHECK="1"),
            cwd=here,
        )
        if rc != 0:
            return rc
        host_dumps = sorted(
            os.path.join(outdir, f) for f in os.listdir(outdir)
            if f.endswith(".metrics.jsonl")
        )
        merged = os.path.join(td, "merged.jsonl")
        cmd = [sys.executable, os.path.join("tools", "metrics_merge.py"),
               "-o", merged]
        for tag in ["router"] + [
            os.path.basename(p).split(".")[0] for p in host_dumps
        ]:
            cmd += ["--tag", tag]
        cmd += [jsonl] + host_dumps
        rc = subprocess.call(cmd, cwd=here)
        if rc != 0:
            return rc
        rc = subprocess.call(
            [sys.executable, os.path.join("tools", "fleet_report.py"),
             merged, "--victim", "victim", "--p99-budget", "15",
             "--require-stitch"],
            cwd=here,
        )
        if rc != 0:
            return rc
        esc = os.path.join(td, "escape.jsonl")
        rc = subprocess.call(
            [sys.executable, "-c", _FLEET_ESCAPE_DRIVER],
            env=dict(env, SLATE_TPU_METRICS=esc,
                     SLATE_TPU_SYNC_CHECK="1"),
            cwd=here,
        )
        if rc != 0:
            return rc
        rc = subprocess.call(
            [sys.executable, os.path.join("tools", "fleet_report.py"),
             esc],
            cwd=here,
        )
        if rc == 0:
            print("fleet gate: report failed to flag an undefended "
                  "SDC escape across the fleet")
            return 1
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier1", action="store_true",
                    help="run the exact ROADMAP tier-1 gate (870 s timeout, "
                         "DOTS_PASSED accounting) and exit")
    ap.add_argument("--schedules", action="store_true",
                    help="run the factorization-schedule parity smoke "
                         "(recursive vs flat vs scipy) and exit")
    ap.add_argument("--chaos", action="store_true",
                    help="run the fault-injection suite (slow matrix "
                         "included) + the chaos_report recovery gate")
    ap.add_argument("--refine", action="store_true",
                    help="run the mixed-precision refinement suite + the "
                         "refine_report fallback-rate gate")
    ap.add_argument("--coldstart", action="store_true",
                    help="run the artifact suite + the restart drill "
                         "(fresh-process restore with 0 compiles, "
                         "byte-flip recovery) + the artifact_report "
                         "chaos gate")
    ap.add_argument("--sharded", action="store_true",
                    help="run the placement suite (replica scale-out + "
                         "spmd routing on a forced 8-device CPU mesh) + "
                         "the placement_report starvation gate")
    ap.add_argument("--latency", action="store_true",
                    help="run the span/histogram suites + a traced "
                         "faulty serve stream (Chrome-export chain "
                         "check) + the latency_report p99 gate")
    ap.add_argument("--factor", action="store_true",
                    help="run the factor-cache suite + an "
                         "env-activated repeated-A stream gated by "
                         "tools/factor_report.py (zero hits on a "
                         "repeated-A stream fails)")
    ap.add_argument("--fabric", action="store_true",
                    help="run the factor-fabric gate: the fabric suite "
                         "(arena + streaming sessions) + an "
                         "env-activated repeated-A gels session stream "
                         "(1 factor, >= 20 warmed solves, 0 compiles, "
                         "upload_avoided_bytes > 0; factor_report "
                         "verdict) + an arena-off leg proving "
                         "byte-identical legacy serving")
    ap.add_argument("--adaptive", action="store_true",
                    help="run the admission suite + the bursty "
                         "two-tenant stream (static config misses the "
                         "victim's p99 budget, adaptive holds it and "
                         "sheds the abuser; tenant_report verdict) + "
                         "the tenant_flood chaos join")
    ap.add_argument("--perf", action="store_true",
                    help="run the devmon suite + the bench_diff "
                         "regression sentinel (true pair passes, "
                         "synthetic regression fails) + a devmon "
                         "serve stream classified by roofline_report "
                         "+ a quick bench floored against "
                         "BENCH_FLOOR_CPU.json")
    ap.add_argument("--integrity", action="store_true",
                    help="run the integrity suite + the four-phase SDC "
                         "drill (sdc_factor/sdc_solve over a warmed "
                         "mixed stream: zero silent wrong answers, "
                         "quarantine engage/recover, hedges win) "
                         "judged by tools/integrity_report.py, + the "
                         "escape proof (plane off -> report nonzero)")
    ap.add_argument("--lint", action="store_true",
                    help="run the slate-lint suite + a budgeted "
                         "full-tree static-analysis pass including the "
                         "whole-program race rules (nonzero on any new "
                         "finding; see README 'Static analysis')")
    ap.add_argument("--race", action="store_true",
                    help="run the race/deadlock gate: the race suite, "
                         "the whole-program static rules + lock-order "
                         "graph artifact check, an instrumented "
                         "chaos/hedge/drain stress leg under "
                         "SLATE_TPU_SYNC_CHECK judged by "
                         "tools/race_report.py, and two planted "
                         "fixtures the report MUST flag")
    ap.add_argument("--soak", action="store_true",
                    help="run the trace-driven soak gate: the soak "
                         "suite + ~10^4 replayed requests against a "
                         "fully-armed service under faults with the "
                         "record->replay round trip and determinism "
                         "checks, judged by tools/soak_report.py, + "
                         "the escape proof (defenses off -> report "
                         "nonzero)")
    ap.add_argument("--full", action="store_true",
                    help="with --soak: scale the drill to ~10^6 "
                         "requests (tens of minutes)")
    ap.add_argument("--scale", action="store_true",
                    help="run the elastic-capacity gate: the scale "
                         "suite + one recorded bursty trace replayed "
                         "static (misses p99) then elastic (holds it, "
                         "artifact-warmed lanes, fleet returns to "
                         "min), judged by tools/capacity_report.py")
    ap.add_argument("--fleet", action="store_true",
                    help="run the cross-process defense gate: the "
                         "fleet suite + the 3-process drill (router + "
                         "2 spawned workers under SDC/latency/host "
                         "death/timeouts, per-host dumps merged and "
                         "traces stitched) judged by "
                         "tools/fleet_report.py, + the escape proof "
                         "(certification off -> report nonzero)")
    ap.add_argument("routines", nargs="*", default=[])
    ap.add_argument("--size", default="quick", choices=sorted(PRESETS))
    ap.add_argument("--grid", default="1x1")
    ap.add_argument("--xml", default=None)
    ap.add_argument("--target", default="d")
    ap.add_argument("--type", default=None)
    args = ap.parse_args()

    if args.tier1:
        return tier1()
    if args.schedules:
        return schedules_smoke()
    if args.chaos:
        return chaos()
    if args.refine:
        return refine_gate()
    if args.coldstart:
        return coldstart()
    if args.sharded:
        return sharded()
    if args.latency:
        return latency_gate()
    if args.factor:
        return factor_gate()
    if args.fabric:
        return fabric_gate()
    if args.adaptive:
        return adaptive_gate()
    if args.perf:
        return perf_gate()
    if args.integrity:
        return integrity_gate()
    if args.lint:
        return lint_gate()
    if args.race:
        return race_gate()
    if args.soak:
        return soak_gate(full=args.full)
    if args.scale:
        return scale_gate()
    if args.fleet:
        return fleet_gate()

    # virtual CPU devices for multi-process grids (both must be in the
    # environment before jax is imported)
    p, q = (int(x) for x in args.grid.split("x"))
    if p * q > 1:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={max(8, p * q)}",
        )
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_enable_x64", True)

    from slate_tpu.testing.tester import run

    preset = PRESETS[args.size]
    argv = list(args.routines) if args.routines else ["all"]
    argv += ["--dim", preset["dim"], "--nb", preset["nb"]]
    argv += ["--type", args.type or preset["type"]]
    argv += ["--grid", args.grid, "--target", args.target]
    if args.xml:
        argv += ["--xml", args.xml]
    return run(argv)


if __name__ == "__main__":
    sys.exit(main())

"""Executable cache + on-disk warmup manifest.

The cache maps ``(BucketKey, batch)`` to a compiled, metrics-
instrumented executable over padded global arrays.  Executables are
built lazily on first use; every build is appended to the warmup
manifest (``SLATE_TPU_WARMUP=/path.json`` or an explicit path), so a
deployment's steady-state bucket set accumulates across runs and
``warmup()`` can pre-compile the whole set at startup — after which a
stream of requests in warmed buckets is compile-free (the
``jit.compilations`` counter stays flat).

With ``SLATE_TPU_ARTIFACTS=/dir`` (or an explicit ``artifact_dir``)
the cache also consults a durable
:class:`~slate_tpu.serve.artifacts.ArtifactStore` before every cold
build and persists every build back to it, so a *fresh process*
pointed at the same directory restores the warmed executable set
(``restore()``) instead of recompiling it — the manifest stays the
recipe, the artifact store is the baked result.

With ``SLATE_TPU_DEVMON=1`` (aux/devmon) every cold build and artifact
restore also captures the executable's ``cost_analysis()`` (flops,
bytes accessed) and ``memory_analysis()`` (argument/output/temp/peak
bytes) into a per-``(BucketKey, batch)`` registry, persisted beside
each manifest entry (``"cost"`` field) and surfaced through
``SolverService.health()`` and the metrics JSONL —
``tools/roofline_report.py`` joins it with the execute timers into
compute- vs memory-bound verdicts per bucket.

Executable shape: ``fn(A_batch, B_batch) -> (X_batch, info_batch)``
with ``A: (batch, Mb, Nb)``, ``B: (batch, Mb, nrhs_b)`` — the drivers
vmapped over the leading axis (Matrix construction from the padded
globals happens inside the trace; tile layouts are static per bucket).
Only two batch points exist per key (1 and batch_max, see
``buckets.batch_bucket``), so the executable set stays bounded and
deterministic.  Solve-phase keys (the factor cache's trsm-only
family) take the FACTOR as their first operand, unbatched:
``fn(F: (Mb, Nb), B_batch) -> (X_batch, info_batch)`` via
``vmap(in_axes=(None, 0))`` — one factor serves the whole coalesced
batch without a batch-sized host copy or bb resident device copies.
"""

from __future__ import annotations

import json
import os
import threading
import time
import warnings
from typing import Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from ..aux import devmon, faults, metrics, spans, sync
from ..exceptions import NumericalError
from .artifacts import ArtifactStore, store_from_env
from .buckets import (
    BucketKey,
    manifest_cost_loads,
    manifest_dumps,
    manifest_loads,
    mesh_fits,
    phase_flops,
    solve_factor_shape,
)

WARMUP_ENV = "SLATE_TPU_WARMUP"


def _device_id(device):
    """Stable priming identity of a dispatch placement (None = the
    default placement)."""
    return None if device is None else getattr(device, "id", device)

#: manifest paths already warned about this process (warn once, not per
#: ExecutableCache — a fleet of services sharing one bad path should
#: not spam)
_warned_manifests: Set[str] = set()


def _build_core(key: BucketKey) -> Callable:
    """The unbatched core over padded globals for one bucket.  Driver
    imports are local: serve must stay importable before drivers are
    (the lazy ``serve/__init__`` keeps ``drivers/eig -> serve.buckets``
    acyclic).  The key's factorization schedule is threaded into the
    drivers via Option.Schedule, so a manifest captured from a
    recursive-schedule deployment precompiles the recursion shapes."""
    from ..drivers import chol as _chol
    from ..drivers import lu as _lu
    from ..drivers import qr as _qr
    from ..enums import Option, Uplo
    from ..matrix.matrix import HermitianMatrix, Matrix

    nb = key.nb
    opts = {Option.Schedule: key.schedule}

    if key.mesh:
        # sharded bucket: the core is the explicit spmd program on the
        # key's submesh (parallel/spmd_core — distributed LU/Cholesky +
        # trsm pipelines under shard_map), wrapped to the cache's
        # batched calling convention by an unrolled trace-time loop —
        # never a vmap over shard_map (jax would replicate the mesh
        # axes).  Batch points beyond 1 exist so same-mesh-bucket
        # requests coalesce like the single-device lane; each item
        # still runs the full spmd pipeline, the loop just amortizes
        # the dispatch.
        import jax.numpy as jnp

        from ..parallel import spmd_core

        core1 = spmd_core.serve_core(key)

        def core(Ab, Bb):
            outs = [core1(Ab[i], Bb[i]) for i in range(Ab.shape[0])]
            X = jnp.stack([o[0] for o in outs])
            info = jnp.stack([jnp.reshape(o[1], ()) for o in outs])
            return X, info

        return core

    if key.phase == "solve":
        # trsm-only bucket (the factor cache's hit family): the first
        # operand is the bucket-padded FACTOR ([[LU,0],[0,I]] with the
        # rows of B pre-permuted on host for gesv, [[L,0],[0,I]] for
        # posv), not A — two triangular sweeps, O(n^2 nrhs) against the
        # full family's O(n^3).  Pure lax triangular algebra: no
        # Matrix/tile round trip, and the exported module is custom-
        # call-free on every backend where triangular_solve lowers
        # natively.
        import jax.numpy as jnp

        if key.routine == "gesv":

            def core(Fg, Bg):
                X = _lu.getrs_from_global(Fg, Bg, key.schedule)
                return X, jnp.zeros((), jnp.int32)

            return core

        if key.routine == "posv":

            def core(Fg, Bg):
                X = _chol.potrs_from_global(Fg, Bg, key.schedule)
                return X, jnp.zeros((), jnp.int32)

            return core

        if key.routine == "gels":
            # least squares from the packed QR factor (V/R + cached
            # compact-WY T panels, buckets.solve_factor_shape): blocked
            # Q^H apply + one trsm — O(m n nrhs) per solve against the
            # full family's O(m n^2) refactor
            def core(Fg, Bg):
                X = _qr.gels_solve_from_global(Fg, Bg, key.m, key.nb)
                return X, jnp.zeros((), jnp.int32)

            return core

        raise ValueError(
            f"solve-phase serving supports gesv/posv/gels, "
            f"not {key.routine!r}"
        )

    if key.tag == "abft" and key.routine in ("gesv", "posv"):
        # checksummed bucket (integrity/abft): the same driver pipeline
        # plus in-trace post-factor and post-trsm checksum checks whose
        # per-item verdict rides out as info = ABFT_BAD (< 0) — the
        # service's delivery certification reads it for free.  The
        # "abft" tag is a reserved options-fingerprint value: manifests
        # and artifact fingerprints key the checksummed executable
        # apart from its plain sibling without a BucketKey change.
        from ..integrity import abft as _abft

        return _abft.build_core(key.routine, nb, key.schedule)

    if key.precision == "mixed":
        # mixed-precision bucket: low-precision factor + device-resident
        # IR (drivers/mixed.serve_mixed_core — fully traceable, classical
        # IR only).  Non-converged solves come back NaN-poisoned; the
        # service's corrupt-result validation re-solves those items on
        # the full-precision direct path and the bucket breaker demotes
        # persistently non-converging buckets — the fallback policy
        # lives in the service, never in the executable.
        from ..drivers import mixed as _mixed

        if key.routine not in ("gesv", "posv"):
            raise ValueError(
                "mixed-precision serving supports gesv/posv, "
                f"not {key.routine!r}"
            )

        def core(Ag, Bg):
            return _mixed.serve_mixed_core(
                key.routine, Ag, Bg, nb, key.schedule
            )

        return core

    if key.routine == "gesv":

        def core(Ag, Bg):
            A = Matrix.from_global(Ag, nb)
            B = Matrix.from_global(Bg, nb)
            X, _LU, _piv, info = _lu.gesv(A, B, opts)
            return X.to_global(), info

        return core

    if key.routine == "posv":

        def core(Ag, Bg):
            A = HermitianMatrix.from_global(Ag, nb, uplo=Uplo.Lower)
            B = Matrix.from_global(Bg, nb)
            X, _L, info = _chol.posv(A, B, opts)
            return X.to_global(), info

        return core

    if key.routine == "gels":
        import jax.numpy as jnp

        def core(Ag, Bg):
            A = Matrix.from_global(Ag, nb)
            B = Matrix.from_global(Bg, nb)
            X = _qr.gels(A, B, opts)
            return X.to_global(), jnp.zeros((), jnp.int32)

        return core

    raise ValueError(f"unknown serving routine: {key.routine!r}")


def direct_call(routine: str, A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """Unpadded, unbatched driver call — the reference result and the
    graceful-degradation fallback path.  Raises NumericalError on a
    nonzero info."""
    from ..drivers import chol as _chol
    from ..drivers import lu as _lu
    from ..drivers import qr as _qr
    from ..enums import Uplo
    from ..matrix.matrix import HermitianMatrix, Matrix

    faults.sleep("latency")
    faults.check("execute")
    nb = min(64, A.shape[1])
    if routine == "gesv":
        Bm = Matrix.from_global(B, nb)
        X, _LU, _piv, info = _lu.gesv(Matrix.from_global(A, nb), Bm)
        if int(info) != 0:
            raise NumericalError(
                f"gesv: singular U({int(info)})", int(info)
            ).with_context(routine=routine)
        # sdc_solve on the direct path too: the fallback/re-execution
        # lane is hardware like any other (finite wrong value on the
        # flat first element; certification must catch it)
        return faults.perturb("sdc_solve", np.asarray(X.to_global()))
    if routine == "posv":
        Bm = Matrix.from_global(B, nb)
        X, _L, info = _chol.posv(
            HermitianMatrix.from_global(A, nb, uplo=Uplo.Lower), Bm
        )
        if int(info) != 0:
            raise NumericalError(
                f"posv: not SPD at {int(info)}", int(info)
            ).with_context(routine=routine)
        return faults.perturb("sdc_solve", np.asarray(X.to_global()))
    if routine == "gels":
        nbm = min(64, max(A.shape))
        X = _qr.gels(Matrix.from_global(A, nbm), Matrix.from_global(B, nbm))
        return np.asarray(X.to_global())
    raise ValueError(f"unknown serving routine: {routine!r}")


def _warm_inputs(key: BucketKey, batch: int) -> Tuple[np.ndarray, np.ndarray]:
    """Well-conditioned dummy operands for a warmup compile: identity A
    (SPD, pivot-free, full rank — and a valid LU/Cholesky factor for
    the solve-phase family, whose first operand is the unbatched
    factor; for the gels pack the identity V/R with zero T panels is a
    valid QR of the identity — zero T makes every block reflector the
    identity apply) and zero B."""
    dt = np.dtype(key.dtype)
    d = min(key.m, key.n)
    if key.phase == "solve":
        A = np.zeros(solve_factor_shape(key), dtype=dt)
        A[np.arange(d), np.arange(d)] = 1
    else:
        A = np.zeros((batch, key.m, key.n), dtype=dt)
        A[:, np.arange(d), np.arange(d)] = 1
    B = np.zeros((batch, key.m, key.nrhs), dtype=dt)
    return A, B


class ExecutableCache:
    """(BucketKey, batch) -> compiled executable, with manifest
    persistence and (``artifact_dir`` / ``SLATE_TPU_ARTIFACTS``) an
    :class:`~slate_tpu.serve.artifacts.ArtifactStore` consulted
    *before* every cold build — restore beats recompile.  Thread-safe:
    the service worker, warmup() and restore() may race on first
    build."""

    def __init__(
        self,
        manifest_path: Optional[str] = None,
        artifact_dir: Optional[str] = None,
    ):
        # sync.RLock: plain threading.RLock unless SLATE_TPU_SYNC_CHECK
        # armed the race plane.  The worker pool, warmup() and
        # restore() all race on the tables below — the annotations are
        # ground truth for the lock-discipline / race-guarded-by rules
        self._lock = sync.RLock(name="cache.ExecutableCache._lock")
        self._exes: Dict[Tuple[BucketKey, int], Callable] = {}  # guarded by: _lock
        self._entries: Set[Tuple[BucketKey, int]] = set()  # guarded by: _lock
        # how each live executable came to be: "artifact" (export blob
        # deserialized) or "compile" (built here) — restore() reports it
        self._origin: Dict[Tuple[BucketKey, int], str] = {}  # guarded by: _lock
        # device ids each entry has dispatched on (None = default
        # placement): warmup/restore prime every replica device that is
        # not in here yet, so multi-replica steady state is compile-free
        # on EVERY device, not just the first one traffic happened to hit
        self._primed: Dict[Tuple[BucketKey, int], Set] = {}  # guarded by: _lock
        # single-flight cold builds: (key, batch) -> Event while one
        # thread builds.  The replica worker pool spreads a same-bucket
        # burst across lanes on purpose, so without this every lane
        # would pay the full trace+compile (~10-25 s per f64 shape) for
        # the SAME executable; the pre-placement single worker
        # serialized builds for free
        self._building: Dict[Tuple[BucketKey, int], threading.Event] = {}  # guarded by: _lock
        # per-executable cost/memory registry (aux/devmon build-time
        # capture): (key, batch) -> {"flops", "bytes_accessed",
        # "argument_bytes", "output_bytes", "temp_bytes", "peak_bytes",
        # ...}.  Persisted beside each manifest entry ("cost" field) so
        # a restored process has the evidence without recapturing
        self._costs: Dict[Tuple[BucketKey, int], dict] = {}  # guarded by: _lock
        self.artifacts: Optional[ArtifactStore] = store_from_env(artifact_dir)
        self.manifest_path = (
            manifest_path
            if manifest_path is not None
            else os.environ.get(WARMUP_ENV) or None
        )
        if self.manifest_path and os.path.exists(self.manifest_path):
            try:
                with open(self.manifest_path) as f:
                    doc = json.load(f)  # one parse feeds both loaders
                self._entries.update(manifest_loads(doc))
                self._costs.update(manifest_cost_loads(doc))
            except (OSError, ValueError, KeyError, TypeError) as e:
                # a corrupt manifest must never block serving — but a
                # silently ignored one hides that every bucket will pay
                # a cold compile: count it and warn once per path
                metrics.inc("serve.manifest_corrupt")
                if self.manifest_path not in _warned_manifests:
                    _warned_manifests.add(self.manifest_path)
                    warnings.warn(
                        f"corrupt warmup manifest at {self.manifest_path!r}"
                        f" ({type(e).__name__}: {e}); starting with an "
                        "empty bucket set — steady state will recompile",
                        RuntimeWarning,
                        stacklevel=2,
                    )

    # -- manifest ----------------------------------------------------------

    def entries(self) -> List[Tuple[BucketKey, int]]:
        with self._lock:
            return sorted(self._entries, key=lambda e: (e[0].label, e[1]))

    def _record(self, key: BucketKey, batch: int) -> None:
        with self._lock:
            if (key, batch) in self._entries:
                return
            self._entries.add((key, batch))
            self._flush_locked()

    def ensure_manifest(self, key: BucketKey, batches) -> None:
        """Record every batch point of a bucket's working set (the
        service registers both 1 and batch_max on first traffic, so a
        manifest captured after ANY dispatch warms both — lone and
        coalesced steady state alike)."""
        with self._lock:
            new = [b for b in batches if (key, int(b)) not in self._entries]
            if not new:
                return
            for b in new:
                self._entries.add((key, int(b)))
            self._flush_locked()

    def _flush_locked(self) -> None:
        if not self.manifest_path:
            return
        tmp = f"{self.manifest_path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                f.write(manifest_dumps(self._entries, self._costs) + "\n")
            os.replace(tmp, self.manifest_path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def save_manifest(self, path: Optional[str] = None) -> Optional[str]:
        """Write the current bucket set to ``path`` (or the configured
        manifest path).  Returns the path written."""
        with self._lock:
            if path is not None:
                self.manifest_path = path
            self._flush_locked()
            return self.manifest_path

    # -- cost/memory registry (aux/devmon build-time capture) --------------

    def cost(self, key: BucketKey, batch: int) -> Optional[dict]:
        """The captured cost/memory record of one executable, or None
        when devmon never saw it build (devmon off, capture failure,
        or a pre-cost manifest)."""
        with self._lock:
            c = self._costs.get((key, int(batch)))
            return dict(c) if c else None

    def cost_registry(self) -> Dict[Tuple[BucketKey, int], dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._costs.items()}

    def costs_by_label(self) -> Dict[str, Dict[int, dict]]:
        """Registry re-keyed ``{bucket label: {batch: record}}`` — the
        join shape health() and the report tools consume."""
        out: Dict[str, Dict[int, dict]] = {}
        with self._lock:
            for (key, batch), c in self._costs.items():
                out.setdefault(key.label, {})[int(batch)] = dict(c)
        return out

    def _capture_cost(self, key: BucketKey, batch: int, jitted,
                      name: str) -> None:
        """Devmon build-time capture: AOT lower+compile ``jitted`` at
        this entry's arg specs, read ``cost_analysis`` +
        ``memory_analysis``, record under ``name`` (the metrics cost
        registry -> JSONL) and persist beside the manifest entry.
        One bool when devmon is off; an already-known entry (restored
        from a cost-bearing manifest) is never recaptured — the extra
        backend compile is paid at most once per (bucket, batch) per
        manifest lifetime.  Capture failure degrades to a counted
        miss, never a build error."""
        if not devmon.is_on():
            return
        with self._lock:
            known = self._costs.get((key, batch))
        if known is not None and known.get("device_kind") in (
            None, devmon.default_device_kind()
        ):
            # restored from a cost-bearing manifest on the same device
            # kind: the capture is skipped, but the evidence must
            # still reach THIS process's metrics registry — a
            # warm-restarted replica's JSONL otherwise carries run
            # timers with zero cost rows and roofline_report fails its
            # gate on a healthy stream
            metrics.record_cost(name, known)
            return
        if known is not None:
            # foreign evidence: the manifest was captured on another
            # backend (a CPU dev box feeding a TPU replica) — serving
            # its flops/bytes under this device's roofs would
            # mis-classify every bucket, so recapture and overwrite
            metrics.inc("serve.cost_foreign_recaptured")
        # NOTE: the capture executable cannot replace the dispatch jit
        # (AOT executables are committed to one device; run() needs
        # jit's per-device variants for replica pinning), so this IS a
        # second backend compile — cold-build-only, devmon-gated, and
        # timed below so warmup cost stays attributable.  record=False:
        # the record lands once below, after flops_model is attached
        t0 = time.perf_counter()
        _compiled, cost = devmon.capture_jitted(
            jitted, self._arg_specs(key, batch), name=name, record=False,
        )
        metrics.observe(f"{name}.cost_capture", time.perf_counter() - t0)
        if cost is None:
            metrics.inc("serve.cost_capture_failed")
            if known is not None:
                # a failed recapture must not leave the foreign record
                # live: no evidence beats wrong evidence
                with self._lock:
                    self._costs.pop((key, batch), None)
                    self._flush_locked()
            return
        # the hand-model FLOP count rides along as cross-check AND as
        # the rate fallback: vendor custom calls (CPU trsm/getrf)
        # report no XLA flops, and a warmed solve bucket must still be
        # roofline-classifiable (bench.py keeps the same gflops_model
        # convention)
        try:
            cost.setdefault("flops_model", phase_flops(key, batch))
        except Exception:  # noqa: BLE001 — attribution never breaks a build
            pass
        metrics.record_cost(name, cost)
        metrics.inc("serve.cost_captured")
        with self._lock:
            self._costs[(key, batch)] = cost
            self._flush_locked()

    # -- executables -------------------------------------------------------

    def _arg_specs(self, key: BucketKey, batch: int):
        """ShapeDtypeStructs of one executable's padded batch operands
        (the jax.export symbol table for save/load).  Solve-phase keys
        take the factor unbatched."""
        import jax

        dt = np.dtype(key.dtype)
        A_spec = (
            jax.ShapeDtypeStruct(solve_factor_shape(key), dt)
            if key.phase == "solve"
            else jax.ShapeDtypeStruct((batch, key.m, key.n), dt)
        )
        return (
            A_spec,
            jax.ShapeDtypeStruct((batch, key.m, key.nrhs), dt),
        )

    def is_live(self, key: BucketKey, batch: int) -> bool:
        """Whether the (key, batch) executable is already built —
        a cheap probe (never triggers a build) for callers that must
        stay compile-free, e.g. the sharded lane's coalescer, which
        batches only at batch points a warmup has already realized."""
        with self._lock:
            return (key, batch) in self._exes

    def executable(self, key: BucketKey, batch: int) -> Callable:
        """Get the compiled executable: memory cache, then the artifact
        store (a verified ``jax.export`` blob re-jits without retracing
        the drivers), then a cold build — which is persisted back to
        the store so the *next* replica restores instead.  Every
        artifact-verification failure (stale/corrupt/load_fail) has
        already been counted by the store and lands here on the build
        path: the degradation is a recompile, never an error."""
        while True:
            with self._lock:
                exe = self._exes.get((key, batch))
                if exe is not None:
                    return exe
                ev = self._building.get((key, batch))
                if ev is None:
                    ev = self._building[(key, batch)] = threading.Event()
                    break  # this thread owns the build
            # another thread is already building this executable: wait
            # it out, then re-check.  If that build FAILED the entry is
            # still absent and the loop takes over (a chaos compile
            # fault must not strand the waiters — each raises or builds
            # on its own terms).
            ev.wait()
        name = f"serve.{key.label}.b{batch}"
        try:
            return self._build_locked_out(key, batch, name)
        finally:
            with self._lock:
                self._building.pop((key, batch), None)
            ev.set()

    def _build_locked_out(self, key: BucketKey, batch: int, name: str):
        """The build half of :meth:`executable` — runs OUTSIDE the
        cache lock (compiles are seconds-to-minutes) under the
        single-flight guard the caller holds."""
        sp = spans.start("build", bucket=key.label, batch=batch) \
            if spans.is_on() else None
        try:
            exe, origin = self._build_inner(key, batch, name)
        except BaseException as e:
            spans.end(sp, outcome=type(e).__name__)
            raise
        # origin annotates whether a mid-traffic cold build actually
        # compiled or came from the artifact store
        spans.end(sp, outcome="ok", origin=origin)
        return exe

    def _build_inner(self, key: BucketKey, batch: int, name: str):
        import jax

        origin = "compile"
        jitted = None
        if self.artifacts is not None:
            call = self.artifacts.load(key, batch)
            if call is not None:
                # re-jit of deserialized StableHLO: no Python retrace,
                # no jax lowering; the backend compile is served by the
                # persistent XLA cache where one is configured.  (Donation is not
                # re-applied — exported modules own their buffers.)
                jitted = jax.jit(call)
                origin = "artifact"
        if jitted is None:
            faults.check("compile")  # cold builds only: loads never fire
            core = _build_core(key)
            if key.mesh:
                # sharded core: batching is the core's own unrolled
                # loop; no donation (the spmd program's operands are
                # resharded at the shard_map boundary) and no vmap
                jitted = jax.jit(core)
                jit_kw = {}
            else:
                # donate the padded batch operands on accelerators:
                # run() always builds them fresh from the request's
                # host arrays, so the factorizations work in place
                # instead of paying a batch-sized copy per dispatch
                # (XLA:CPU has no donation and would warn).  Solve-
                # phase cores map over B only: the factor is ONE
                # unbatched operand shared by the whole batch — and
                # possibly the fabric arena's device-resident copy, so
                # it is never donated (donation would invalidate the
                # arena's buffer after one dispatch).
                in_axes = (None, 0) if key.phase == "solve" else (0, 0)
                jit_kw = {}
                if jax.default_backend() != "cpu":
                    jit_kw["donate_argnums"] = (
                        (1,) if key.phase == "solve" else (0, 1)
                    )
                jitted = jax.jit(jax.vmap(core, in_axes=in_axes), **jit_kw)
            if self.artifacts is not None and not (
                self.artifacts.verified_cache_seed(key, batch)
            ):
                # persist for the next replica — exporting a NON-donated
                # jit of the same core (jax.export refuses donated
                # computations, which would demote every accelerator
                # bucket to the cache_seed rung; the loaded artifact
                # re-jits without donation anyway).  A load that just
                # verified a cache_seed entry for this fingerprint is
                # NOT re-saved: the rewrite would be byte-identical and
                # the export attempt is a full retrace on the worker
                # thread.
                export_target = (
                    jax.jit(jax.vmap(core, in_axes=in_axes))
                    if jit_kw else jitted
                )
                self.artifacts.save(
                    key, batch, export_target, self._arg_specs(key, batch)
                )
        # devmon build-time capture (cold build AND artifact restore):
        # flops/bytes + argument/output/temp/peak bytes per (bucket,
        # batch), recorded to metrics and persisted beside the manifest
        # entry.  Gated on devmon (one bool when off) because the AOT
        # lowering is a second backend compile of the program
        self._capture_cost(key, batch, jitted, name)
        # capture_cost=False: instrument_jit's own AOT capture would
        # double every warmup even with devmon off (metrics still
        # splits compile-vs-run wall per bucket; devmon owns cost)
        exe = metrics.instrument_jit(jitted, name, capture_cost=False)
        with self._lock:
            prev = self._exes.setdefault((key, batch), exe)
            if prev is exe:
                self._origin[(key, batch)] = origin
            exe = prev
        self._record(key, batch)
        return exe, origin

    def run(
        self,
        key: BucketKey,
        A_batch: np.ndarray,
        B_batch: np.ndarray,
        device=None,
    ):
        """Execute one padded batch; returns host (X_batch, info_batch).

        ``device`` pins the dispatch (and its per-device compiled
        variant) to one device — the replica-placement path; None runs
        on the default placement exactly as before.

        Fault sites (aux/faults; every check is one bool when off):
        ``latency`` sleeps before dispatch, ``execute`` raises in place
        of the dispatch, ``result_corrupt`` NaN-poisons item 0 of X,
        ``info_nonzero`` forces item 0's info nonzero."""
        import jax
        import jax.numpy as jnp

        faults.sleep("latency")
        faults.check("execute")
        # the batch point: the leading axis of A for the full family,
        # of B for the solve family (whose factor operand is unbatched)
        batch = (
            B_batch.shape[0] if key.phase == "solve" else A_batch.shape[0]
        )
        exe = self.executable(key, batch)
        if device is not None and not key.mesh:
            # straight host -> replica-device transfer: an asarray first
            # would commit the batch to the default device and pay a
            # second device-to-device hop, funneling the whole fleet's
            # traffic through device 0's memory
            A = jax.device_put(A_batch, device)
            B = jax.device_put(B_batch, device)
        else:
            A = jnp.asarray(A_batch)
            B = jnp.asarray(B_batch)
        X, info = exe(A, B)
        with self._lock:
            self._primed.setdefault((key, batch), set()).add(
                _device_id(None if key.mesh else device)
            )
        X = faults.corrupt("result_corrupt", np.asarray(X))
        if key.routine in ("gesv", "posv"):
            # sdc_solve: a device returning FINITE garbage (unlike
            # result_corrupt's NaN) — invisible to the finiteness
            # fence by construction; only delivery certification
            # (integrity/) can catch it.  Scoped to the routines the
            # certificate covers: injecting into gels (whose LS
            # residual admits no cheap fence) would be an escape no
            # configuration can defend, flagging chaos runs forever
            X = faults.perturb("sdc_solve", np.asarray(X))
        info = faults.poison_info(
            "info_nonzero", np.atleast_1d(np.asarray(info))
        )
        return np.asarray(X), info

    # -- warmup / restore (one loop, per-caller error policy) --------------

    def _live_todo(self, batch_max=None, extra_path=None):
        """The sorted (key, batch) work list both :meth:`warmup` and
        :meth:`restore` walk: manifest entries (plus an extra manifest
        file's), minus batch points past ``batch_max`` and minus
        mesh-keyed entries this process cannot realize (a 2x4 entry on
        a 1-device box — counted ``serve.mesh_unfit_skipped``, a
        replica warms only what its mesh can run).  Returns
        ``(todo, mesh_unfit_count)``."""
        with self._lock:  # the workers may add entries concurrently
            todo = list(self._entries)
        if extra_path is not None and os.path.exists(extra_path):
            with open(extra_path) as f:
                for e in manifest_loads(f.read()):
                    if e not in todo:
                        todo.append(e)
        todo.sort(key=lambda e: (e[0].label, e[1]))
        out = []
        unfit = 0
        ndev = None
        for key, batch in todo:
            if key.mesh:
                if batch < 1:
                    # malformed entry (hand-edited / foreign writer)
                    metrics.inc("serve.manifest_bad_batch")
                    continue
                if ndev is None:
                    import jax

                    ndev = len(jax.devices())
                if not mesh_fits(key.mesh, ndev):
                    unfit += 1
                    metrics.inc("serve.mesh_unfit_skipped")
                    continue
            if batch_max is not None and batch > batch_max:
                continue
            out.append((key, batch))
        return out, unfit

    def _bring_live(
        self,
        todo,
        devices=None,
        on_error: Optional[Callable] = None,
        stop_check: Optional[Callable[[], bool]] = None,
        verbose: bool = False,
        tag: str = "warmup",
    ):
        """The ONE loop behind :meth:`warmup` and :meth:`restore` —
        placement plumbing lands here exactly once.  Brings each entry
        live (artifact-first via :meth:`executable`) and primes it with
        one dummy dispatch on every device in ``devices`` it has not
        dispatched on yet (replica pinning: multi-replica steady state
        must be compile-free on EVERY replica device).  Mesh-keyed
        entries prime once — their placement is the mesh itself.

        Per-caller error policy: ``on_error=None`` propagates the
        first failure (warmup semantics — the caller wants to know its
        precompile failed); a callable receives ``(key, batch, exc)``
        and the entry is reported ``failed`` (restore semantics —
        degrade, never crash).  ``stop_check`` is polled between
        entries; True abandons the rest (``serve.restore_stopped``).

        Yields ``(key, batch, outcome, origin)`` rows with outcome in
        ``restored`` (came live from an export artifact) / ``compiled``
        (any other rung) / ``skipped`` (already live and primed
        everywhere requested) / ``failed``."""
        devs = [d for d in (devices if devices else [None])]
        # dedupe while preserving replica order (replicas may share a
        # device when the pool is smaller than the replica count)
        seen: Set = set()
        devs = [
            d for d in devs
            if _device_id(d) not in seen and not seen.add(_device_id(d))
        ]
        for key, batch in todo:
            if stop_check is not None and stop_check():
                metrics.inc("serve.restore_stopped")
                break
            want = [None] if key.mesh else devs
            with self._lock:
                live = (key, batch) in self._exes
                primed = set(self._primed.get((key, batch), ()))
            need = [d for d in want if _device_id(d) not in primed]
            if live and not need:
                yield key, batch, "skipped", None
                continue
            t0 = time.perf_counter()
            sp = spans.start(tag, lane=tag, bucket=key.label, batch=batch) \
                if spans.is_on() else None
            try:
                A, B = _warm_inputs(key, batch)
                for d in (need or want):
                    # loads-or-builds on the first device, then primes
                    # the per-device variants (subclassed caches keep
                    # the legacy 3-arg run() for the default placement)
                    if d is None:
                        self.run(key, A, B)
                    else:
                        self.run(key, A, B, device=d)
            except Exception as e:  # noqa: BLE001 — policy decides
                spans.end(sp, outcome="failed", error=type(e).__name__)
                if on_error is None:
                    raise
                on_error(key, batch, e)
                yield key, batch, "failed", None
                continue
            # under the lock: a worker-thread cold build may be
            # writing _origin concurrently with this pass (a true
            # positive the whole-program guarded-by run surfaced)
            with self._lock:
                origin = self._origin.get((key, batch), "compile")
            if live:
                # the executable predates this pass; only new devices
                # were primed — no fresh restore/compile to report, but
                # the per-device backend compiles are real cold-start
                # budget, so they are counted and printed, not hidden
                outcome = "skipped"
                primes = len(need)
            else:
                outcome = "restored" if origin == "artifact" else "compiled"
                primes = max(0, len(need or want) - 1)
            if primes:
                metrics.inc("serve.device_primes", primes)
            # the artifact-restore outcome rides on the entry's span:
            # restored-vs-compiled-vs-skipped is THE cold-start question
            spans.end(sp, outcome=outcome, origin=origin, primes=primes)
            if verbose:
                extra = f" +{primes} device prime(s)" if primes else ""
                print(
                    f"[serve.{tag}] {key.label} b{batch}: "
                    f"{'primed' if live else origin}"
                    f"{extra} {time.perf_counter() - t0:.2f}s"
                )
            yield key, batch, outcome, origin

    def warmup(
        self,
        path: Optional[str] = None,
        batch_max: Optional[int] = None,
        devices=None,
        verbose: bool = False,
    ) -> int:
        """Pre-compile every manifest entry (plus ``path``'s entries if
        given), priming each ``devices`` entry so a replica pool's
        steady state never compiles.  Returns the number of
        executables compiled — entries that ``executable()`` served
        from the artifact store instead are not counted (zero compiles
        happened; ``restore()`` is the pass that reports restores).
        Errors propagate (the caller asked for a precompile and should
        know it failed).  Per-bucket compile walls land in the
        ``serve.<bucket>.b<batch>.compile`` timers; the whole pass
        under the ``serve.warmup`` timer."""
        todo, _unfit = self._live_todo(batch_max=batch_max, extra_path=path)
        compiled = 0
        with metrics.phase("serve.warmup", always=True) as ph:
            for _k, _b, outcome, _origin in self._bring_live(
                todo, devices=devices, on_error=None, verbose=verbose,
                tag="warmup",
            ):
                if outcome == "compiled":
                    compiled += 1  # an artifact hit compiled nothing
        metrics.gauge("serve.warmup_s", ph.seconds)
        metrics.inc("serve.warmup_compiles", compiled)
        return compiled

    def restore(
        self,
        batch_max: Optional[int] = None,
        verbose: bool = False,
        stop_check: Optional[Callable[[], bool]] = None,
        devices=None,
    ) -> Dict[str, int]:
        """Bring every manifest entry live, artifact-first: load (or,
        where the store has nothing valid, compile) each executable and
        prime it with one dummy dispatch per ``devices`` entry, so a
        subsequent steady-state stream never traces or compiles on any
        replica.  This is the cold-start path a fresh replica runs
        before reporting ``ready``.

        Per-entry failures (a fault-injected load, an execute fault on
        the priming dispatch, a poisoned artifact dir) are counted and
        skipped, never raised — a damaged store degrades the replica to
        recompiles-on-traffic, it does not keep it from coming up.

        Returns ``{"entries", "restored", "compiled", "failed",
        "skipped"}`` (restored = served from an export artifact;
        compiled = any other rung of the ladder, including cache_seed
        recompiles; skipped = already live when the pass reached it —
        e.g. traffic served while restoring built it first — so
        ``entries == restored + compiled + failed + skipped`` always
        holds), plus ``mesh_unfit`` when manifest entries were skipped
        because their mesh shape does not fit this process's devices.

        ``stop_check`` is polled between entries; True abandons the
        rest of the pass (the service passes its stopped flag so a
        replica torn down mid-restore does not keep compiling a large
        manifest for minutes on a daemon thread)."""
        todo, unfit = self._live_todo(batch_max=batch_max)
        out = {
            "entries": 0, "restored": 0, "compiled": 0, "failed": 0,
            "skipped": 0,
        }
        if unfit:
            out["mesh_unfit"] = unfit

        def on_error(key, batch, exc):
            metrics.inc("serve.restore_failed")

        with metrics.phase("serve.restore", always=True) as ph:
            for _k, _b, outcome, _origin in self._bring_live(
                todo, devices=devices, on_error=on_error,
                stop_check=stop_check, verbose=verbose, tag="restore",
            ):
                out["entries"] += 1
                out[outcome] += 1
        metrics.gauge("serve.restore_s", ph.seconds)
        metrics.inc("serve.restore_restored", out["restored"])
        metrics.inc("serve.restore_compiled", out["compiled"])
        return out

    def prime(
        self,
        entries=None,
        devices=None,
        batch_max: Optional[int] = None,
        verbose: bool = False,
        stop_check: Optional[Callable[[], bool]] = None,
        tag: str = "prime",
    ) -> Dict[str, int]:
        """Partial :meth:`_bring_live` by plan: bring a CALLER-ORDERED
        ``(key, batch)`` subset live, artifact-first, priming each
        entry's per-``devices`` dispatch variants.  This is the
        scale-up lane's warm path (``SolverService.add_replica``) and
        the applicator of a predictive
        :class:`~slate_tpu.scale.warmup_plan.WarmupPlan` — the caller's
        order IS the ranking, so a priming deadline truncates from the
        plan's bottom, not alphabetically.

        ``entries=None`` walks the full live manifest (restore
        semantics over the whole working set).  Explicit entries are
        registered into the manifest first — a planned bucket this
        process has never dispatched still warms, and a later
        restart's restore pass inherits it.

        Failures are counted and skipped, never raised (a scale-up
        lane degrades to compile-on-traffic; it does not abort the
        scale-up).  Returns ``{"entries", "restored", "compiled",
        "failed", "skipped"}``."""
        if entries is None:
            todo, _unfit = self._live_todo(batch_max=batch_max)
        else:
            todo = []
            for key, batch in entries:
                batch = int(batch)
                if (batch_max is not None and not key.mesh
                        and batch > batch_max):
                    continue
                self.ensure_manifest(key, (batch,))
                todo.append((key, batch))
        out = {
            "entries": 0, "restored": 0, "compiled": 0, "failed": 0,
            "skipped": 0,
        }

        def on_error(key, batch, exc):
            metrics.inc("serve.prime_failed")

        with metrics.phase("serve.prime", always=True) as ph:
            for _k, _b, outcome, _origin in self._bring_live(
                todo, devices=devices, on_error=on_error,
                stop_check=stop_check, verbose=verbose, tag=tag,
            ):
                out["entries"] += 1
                out[outcome] += 1
        metrics.gauge("serve.prime_s", ph.seconds)
        return out

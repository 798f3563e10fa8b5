"""Durable executable artifacts: the on-disk store that turns a warmed
bucket lattice from a *recipe* (the warmup manifest — a list of shapes
to recompile, minutes of compiles per process) into an *artifact* a
fresh serving replica loads instead of recompiling.

Layout (``SLATE_TPU_ARTIFACTS=/dir`` or ``ArtifactStore(root)``)::

    /dir/
      <routine>.<MxNxR>.<dtype>[...].b<batch>.<content12>.slate_exe
      .lock               # cross-process write lock

Each ``.slate_exe`` file is one JSON header line + ``\\n`` + payload
bytes.  The header carries the full **fingerprint**: the content half
(every BucketKey field including the PR3 ``schedule`` and PR5
``precision``, plus the batch point — ``buckets.content_fields``) and
the runtime half (jax/jaxlib version, backend, device kind, x64 mode —
:func:`runtime_fields`), plus a sha256 checksum of the payload and the
``mode`` the entry took:

* ``"export"`` — the payload is ``jax.export`` serialized StableHLO of
  the jitted bucket executable; load deserializes and re-jits it,
  skipping Python retracing and jax lowering entirely (and, where the
  operator configured JAX's persistent compilation cache, the backend
  compile too).
* ``"cache_seed"`` — ``jax.export`` refused the computation (donated
  or sharded executables are version-dependent), the exported
  module embeds non-portable custom calls (vendor LAPACK on CPU,
  pallas — loading those in a fresh process can segfault, which no
  integrity check can catch), or the bucket is **mesh-sharded**
  (``BucketKey.mesh`` — shard_map programs are never trusted across
  processes; the entry is still keyed by its mesh shape, so it cannot
  collide with the single-device fingerprint); the payload is empty
  and the entry records that the build itself went to JAX's persistent
  compilation cache, so where the operator configured one
  (``JAX_COMPILATION_CACHE_DIR``) a fresh replica's recompile is a
  disk hit instead of a cold backend compile.

Robustness is the design center, because a persisted artifact is a new
thing that can be stale, truncated, or corrupt:

* **Atomic write-then-rename under a cross-process lock** — a reader
  (another replica restoring from the same dir) can never observe a
  torn artifact; the lock serializes writers and is stale-broken by
  age so a crashed writer cannot wedge the fleet.
* **Load-time integrity verification** — magic/header parse, full
  fingerprint match, and payload checksum.  *Any* mismatch degrades to
  a counted recompile and never crashes or serves wrong results:
  corrupt bytes -> ``serve.artifact_corrupt``, a fingerprint from a
  different jaxlib/device/x64/schedule -> ``serve.artifact_stale``,
  a deserialization error on verified bytes ->
  ``serve.artifact_load_fail``; hits and misses count
  ``serve.artifact_hit`` / ``serve.artifact_miss`` (each also emitted
  per bucket as ``serve.artifact.<label>.b<batch>.<outcome>`` for
  ``tools/artifact_report.py``).  A recompiled bucket re-saves,
  overwriting the bad file — the store self-heals.
* **Chaos coverage** — the ``artifact_corrupt`` / ``artifact_stale`` /
  ``artifact_load_fail`` fault sites (aux/faults) are threaded through
  :meth:`ArtifactStore.load`, so ``run_tests.py --coldstart`` can
  inject every failure mode and assert the recovery counters.

The degradation ladder, end to end: artifact hit (zero retrace, zero
compile) -> manifest recompile (warm the shape from the recipe, XLA
cache assisted) -> cold compile (nothing persisted).  Every rung
serves correct results; only the metrics differ.
"""

from __future__ import annotations

import json
import hashlib
import os
import re
import threading
import time
from typing import Callable, Optional, Tuple

from ..aux import faults, metrics, sync
from .buckets import BucketKey, content_fields, fingerprint

ARTIFACTS_ENV = "SLATE_TPU_ARTIFACTS"

MAGIC = "slate-artifact"
SCHEMA = 1
SUFFIX = ".slate_exe"

#: modes an artifact entry can record (header ``mode`` field)
MODE_EXPORT = "export"
MODE_CACHE_SEED = "cache_seed"

#: a .lock older than this is considered abandoned by a crashed writer
#: and broken (seconds); writers touch the lock only for the duration
#: of one tmp-write + rename, far below this
LOCK_STALE_S = 30.0
LOCK_RETRY_S = 0.02
LOCK_TIMEOUT_S = 10.0


#: custom-call targets that are portable across processes (partitioning
#: annotations resolved by the compiler, not function pointers).  Any
#: OTHER custom_call in an exported module — vendor LAPACK kernels on
#: CPU (``lapack_*_ffi``), pallas ``tpu_custom_call``s — is treated as
#: non-exportable and the entry falls back to the cache_seed rung:
#: jax.export nominally guarantees some of these stable, but a
#: deserialized ``lapack_dgetrf_ffi`` segfaults at execution in a
#: fresh process on this jaxlib, and a crash-safe store must not trust
#: a guarantee it can observe being broken.
_PORTABLE_CUSTOM_CALLS = frozenset({
    "Sharding",
    "SPMDFullToShardShape",
    "SPMDShardToFullShape",
    "annotate_device_placement",
})

_CUSTOM_CALL_RE = re.compile(
    r"stablehlo\.custom_call[^\n]*?@([\w.\-]+)"
    r"|call_target_name\s*=\s*\"([^\"]+)\""
)


def nonportable_custom_calls(exported) -> list:
    """Custom-call targets in an exported module that are not on the
    portable allowlist (empty = safe to serialize)."""
    try:
        txt = exported.mlir_module()
    except Exception:  # noqa: BLE001 — unreadable module: do not export it
        return ["<unreadable-module>"]
    targets = {t for pair in _CUSTOM_CALL_RE.findall(txt) for t in pair if t}
    return sorted(t for t in targets if t not in _PORTABLE_CUSTOM_CALLS)


def runtime_fields() -> dict:
    """The runtime half of the artifact fingerprint: serialized
    executables are only valid for the jax/jaxlib pair, backend,
    device kind, and x64 mode they were exported under — any drift
    must read as *stale*, never load."""
    import jax

    try:
        import jaxlib

        jaxlib_ver = getattr(jaxlib, "__version__", "?")
    except Exception:  # noqa: BLE001 — fingerprint must always build
        jaxlib_ver = "?"
    try:
        devs = jax.devices()
        device_kind = devs[0].device_kind if devs else "?"
    except Exception:  # noqa: BLE001
        device_kind = "?"
    return {
        "jax": getattr(jax, "__version__", "?"),
        "jaxlib": jaxlib_ver,
        "backend": jax.default_backend(),
        "device_kind": device_kind,
        "x64": bool(jax.config.jax_enable_x64),
    }


class _FileLock:
    """Cross-process advisory lock via O_CREAT|O_EXCL, with stale-break:
    a lock file older than LOCK_STALE_S belongs to a crashed writer and
    is removed (the subsequent create race is harmless — both writers
    produce whole files via rename; the lock only bounds concurrent
    write amplification, atomicity never depends on it)."""

    def __init__(self, path: str, timeout_s: float = LOCK_TIMEOUT_S,
                 stale_s: float = LOCK_STALE_S):
        self.path = path
        self.timeout_s = timeout_s
        self.stale_s = stale_s
        self._held = False

    def __enter__(self) -> "_FileLock":
        deadline = time.monotonic() + self.timeout_s
        while True:
            try:
                fd = os.open(self.path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                try:
                    os.write(fd, f"{os.getpid()}\n".encode())
                finally:
                    os.close(fd)
                self._held = True
                return self
            except FileExistsError:
                try:
                    age = time.time() - os.path.getmtime(self.path)
                    if age > self.stale_s:
                        os.unlink(self.path)  # crashed writer; break it
                        continue
                except OSError:
                    continue  # holder released between stat and unlink
                if time.monotonic() > deadline:
                    # proceed WITHOUT the lock rather than wedge the
                    # replica: rename keeps every write atomic anyway
                    metrics.inc("serve.artifact_lock_timeout")
                    return self
                time.sleep(LOCK_RETRY_S)

    def __exit__(self, *exc) -> bool:
        if self._held:
            try:
                os.unlink(self.path)
            except OSError:
                pass
            self._held = False
        return False


class ArtifactStore:
    """On-disk store of serialized bucket executables, keyed by content
    fingerprint.  Thread-safe; every public method degrades to "no
    artifact" on any filesystem or serialization trouble — the store
    must never take serving down with it."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        # sync.Lock: plain threading.Lock unless the race plane is on
        self._lock = sync.Lock(name="artifacts.ArtifactStore._lock")
        self._runtime: Optional[dict] = None  # resolved on first use
        # (key, batch) pairs whose load() verified a cache_seed entry
        # this process: the recompile that follows must not pay a
        # redundant export + byte-identical rewrite (see save callers)
        self._cache_seed_verified: set = set()

    # -- identity ----------------------------------------------------------

    def _runtime_fields(self) -> dict:
        with self._lock:
            if self._runtime is None:
                self._runtime = runtime_fields()
            return dict(self._runtime)

    def fingerprint(self, key: BucketKey, batch: int) -> Tuple[str, dict]:
        """(hex digest, field dict) of one entry's full identity."""
        fields = {**content_fields(key, batch), **self._runtime_fields()}
        return fingerprint(fields), fields

    def path_for(self, key: BucketKey, batch: int) -> str:
        """The entry's filename: the human-readable bucket label plus a
        short *content*-only hash.  The runtime half of the fingerprint
        lives in the header, NOT the name — so an artifact written by a
        different jaxlib/device is *found* and diagnosed as stale
        (counted, recompiled) instead of silently missing."""
        chash = fingerprint(content_fields(key, batch))[:12]
        return os.path.join(
            self.root, f"{key.label}.b{int(batch)}.{chash}{SUFFIX}"
        )

    # -- save --------------------------------------------------------------

    def save(self, key: BucketKey, batch: int, jitted, arg_specs) -> str:
        """Persist one built executable.  Tries ``jax.export`` first;
        when export refuses (donated/sharded computations are not
        serializable across versions) or the exported module embeds
        non-portable custom calls (vendor LAPACK on CPU, pallas — see
        :func:`nonportable_custom_calls`), the entry is recorded as
        ``cache_seed`` — the build that just happened has already
        gone to the persistent XLA cache, where one is configured.
        Returns the mode written
        (``"export"`` | ``"cache_seed"``); never raises."""
        try:
            fp, fields = self.fingerprint(key, batch)
            mode = MODE_EXPORT
            payload = b""
            nonportable: list = []
            if getattr(key, "mesh", ""):
                # mesh-sharded executables always take the cache_seed
                # rung: a serialized shard_map program binds a device
                # assignment this jaxlib gives no cross-process
                # stability guarantee for (the same trust boundary as
                # the vendor-LAPACK segfault below).  The entry is
                # still KEYED by its mesh shape (content_fields carries
                # BucketKey.mesh), so it never collides with the
                # single-device fingerprint and its build still goes
                # to the persistent XLA cache for the next replica.
                mode = MODE_CACHE_SEED
                nonportable = [f"sharded-mesh:{key.mesh}"]
            else:
                try:
                    from jax import export as _export

                    exported = _export.export(jitted)(*arg_specs)
                    nonportable = nonportable_custom_calls(exported)
                    if nonportable:
                        # vendor LAPACK / pallas custom calls deserialize
                        # but can segfault at execution in a fresh process
                        # (observed: lapack_dgetrf_ffi on this jaxlib) —
                        # a crash-safe store must not persist them
                        mode = MODE_CACHE_SEED
                    else:
                        payload = exported.serialize()
                except Exception:  # noqa: BLE001 — unsupported computation
                    mode = MODE_CACHE_SEED
                    payload = b""
            header = {
                "magic": MAGIC,
                "schema": SCHEMA,
                "mode": mode,
                "fingerprint": fp,
                "fields": fields,
                "sha256": hashlib.sha256(payload).hexdigest(),
                "payload_bytes": len(payload),
                "created_unix": time.time(),
            }
            if nonportable:
                # why this entry took the cache_seed rung — surfaced
                # by entries()/tools so operators can see which
                # buckets will always recompile on this backend
                header["nonportable"] = nonportable
            blob = (json.dumps(header, sort_keys=True) + "\n").encode() + payload
            path = self.path_for(key, batch)
            tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
            with _FileLock(os.path.join(self.root, ".lock")):
                try:
                    with open(tmp, "wb") as f:
                        f.write(blob)
                        f.flush()
                        os.fsync(f.fileno())
                    os.replace(tmp, path)  # readers see whole files only
                finally:
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
            metrics.inc("serve.artifact_saved")
            if metrics.is_on():
                metrics.inc(f"serve.artifact_saved_{mode}")
            return mode
        except Exception:  # noqa: BLE001 — persistence must never crash serving
            metrics.inc("serve.artifact_save_error")
            return MODE_CACHE_SEED

    # -- load --------------------------------------------------------------

    def _count(self, key: BucketKey, batch: int, outcome: str) -> None:
        if outcome != "cache_seed":
            # any other outcome invalidates a prior cache_seed verdict
            # (e.g. the entry rotted since): the next build must
            # re-save so the store self-heals
            with self._lock:
                self._cache_seed_verified.discard((key, int(batch)))
        if metrics.is_on():
            metrics.inc(f"serve.artifact_{outcome}")
            metrics.inc(f"serve.artifact.{key.label}.b{int(batch)}.{outcome}")

    def load(self, key: BucketKey, batch: int) -> Optional[Callable]:
        """Load one entry; returns the deserialized callable (ready for
        ``jax.jit``) or None when the caller must compile instead.

        The verification ladder — each rung counted, none fatal:
        missing file -> ``miss``; unparsable header or checksum
        mismatch -> ``corrupt``; fingerprint drift (jaxlib, device
        kind, x64, schedule, precision, ...) -> ``stale``;
        deserialization failure of verified bytes -> ``load_fail``;
        a ``cache_seed`` entry -> ``cache_seed`` (recompile, warmed by
        the persistent XLA cache where one is configured).  Fault sites ``artifact_corrupt`` /
        ``artifact_stale`` / ``artifact_load_fail`` inject each rung."""
        path = self.path_for(key, batch)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            self._count(key, batch, "miss")
            return None
        try:
            if faults.fire("artifact_corrupt") is not None:
                blob = self._flip_byte(blob)
            nl = blob.find(b"\n")
            if nl < 0:
                raise ValueError("no header line")
            header = json.loads(blob[:nl].decode())
            payload = blob[nl + 1:]
            if header.get("magic") != MAGIC or header.get("schema") != SCHEMA:
                raise ValueError("bad magic/schema")
            if hashlib.sha256(payload).hexdigest() != header.get("sha256"):
                raise ValueError("payload checksum mismatch")
            if len(payload) != int(header.get("payload_bytes", -1)):
                raise ValueError("payload truncated")
        except (ValueError, KeyError, TypeError, UnicodeDecodeError):
            # torn/truncated/bit-rotted bytes: counted, recompiled;
            # the rebuild's save() overwrites the bad file (self-heal)
            self._count(key, batch, "corrupt")
            return None
        fp, _fields = self.fingerprint(key, batch)
        if faults.fire("artifact_stale") is not None:
            fp += "!stale"  # as if this process ran a different jaxlib
        if header.get("fingerprint") != fp:
            self._count(key, batch, "stale")
            return None
        if header.get("mode") == MODE_CACHE_SEED:
            # nothing to deserialize — the recompile this triggers is
            # served from the persistent XLA cache, where one is set
            with self._lock:
                self._cache_seed_verified.add((key, int(batch)))
            self._count(key, batch, "cache_seed")
            return None
        try:
            faults.check("artifact_load_fail")
            from jax import export as _export

            exported = _export.deserialize(payload)
            self._count(key, batch, "hit")
            return exported.call
        except Exception:  # noqa: BLE001 — verified bytes can still fail to load
            self._count(key, batch, "load_fail")
            return None

    def verified_cache_seed(self, key: BucketKey, batch: int) -> bool:
        """True when a load() this process verified a current-
        fingerprint ``cache_seed`` entry for (key, batch) — the caller
        about to compile can skip a byte-identical re-save."""
        with self._lock:
            return (key, int(batch)) in self._cache_seed_verified

    @staticmethod
    def _flip_byte(blob: bytes) -> bytes:
        """One flipped payload byte (the artifact_corrupt injection —
        past the header so the checksum, not the JSON parse, catches
        it; integrity is the contract under test)."""
        if not blob:
            return blob
        nl = blob.find(b"\n")
        i = min(nl + 1, len(blob) - 1) if nl >= 0 else len(blob) - 1
        out = bytearray(blob)
        out[i] ^= 0x01
        return bytes(out)

    # -- introspection -----------------------------------------------------

    def entries(self) -> list:
        """Header dicts of every artifact in the store (corrupt headers
        reported with ``{"path": ..., "error": ...}``), for tools."""
        out = []
        try:
            names = sorted(os.listdir(self.root))
        except OSError:
            return out
        for name in names:
            if not name.endswith(SUFFIX):
                continue
            path = os.path.join(self.root, name)
            try:
                with open(path, "rb") as f:
                    head = f.readline()
                h = json.loads(head.decode())
                h["path"] = path
                out.append(h)
            except (OSError, ValueError, UnicodeDecodeError) as e:
                out.append({"path": path, "error": str(e)})
        return out


def store_from_env(
    artifact_dir: Optional[str] = None,
) -> Optional[ArtifactStore]:
    """Build the store from an explicit dir or ``SLATE_TPU_ARTIFACTS``;
    None when neither names a directory.  A store that cannot be
    created (read-only fs, ...) degrades to None — serving without
    durability beats not serving."""
    root = (
        artifact_dir if artifact_dir is not None
        else os.environ.get(ARTIFACTS_ENV) or None
    )
    if not root:
        return None
    try:
        return ArtifactStore(root)
    except OSError:
        metrics.inc("serve.artifact_store_error")
        return None

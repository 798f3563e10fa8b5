"""Device telemetry plane (aux/devmon + the serve cost/memory
registry): build-time cost/memory capture, graceful degradation on
backends without the device APIs, manifest persistence, health()
surfacing, the roofline math, and the report/sentinel tools.

The zero-overhead-off criterion rides here too: with devmon off
(the default) the cache captures nothing, the manifest carries no
cost fields, and health() reports devices=None — the PR2 steady-state
compile-free contract is untouched (test_serve keeps asserting it).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from slate_tpu.aux import devmon, metrics
from slate_tpu.serve import buckets as bk
from slate_tpu.serve.cache import ExecutableCache
from slate_tpu.serve.service import SolverService

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _devmon_state():
    """devmon and metrics are process-global; every test starts and
    ends with both off and clean."""
    devmon.off()
    devmon.reset()
    metrics.off()
    metrics.reset()
    yield
    devmon.off()
    devmon.reset()
    metrics.off()
    metrics.reset()


def _key(n=12, nrhs=2, routine="gesv"):
    return bk.bucket_for(routine, n, n, nrhs, np.float64,
                         floor=16, nrhs_floor=4)


# ---------------------------------------------------------------------------
# analyze / capture primitives
# ---------------------------------------------------------------------------


def test_analyze_compiled_reads_cost_and_memory():
    import jax

    def f(a, b):
        return (a @ b).sum()

    c = jax.jit(f).lower(np.ones((32, 32)), np.ones((32, 32))).compile()
    rec = devmon.analyze_compiled(c)
    assert rec is not None
    assert rec["flops"] > 0
    assert rec["bytes_accessed"] > 0
    assert rec["argument_bytes"] > 0
    assert rec["output_bytes"] > 0
    # peak is the runtime's number when reported, else arg+out+temp
    assert rec["peak_bytes"] >= rec["argument_bytes"]


def test_analyze_compiled_output_only_backend_gets_peak():
    class OutputOnlyMem:
        output_size_in_bytes = 512

    class Fake:
        def cost_analysis(self):
            return {}

        def memory_analysis(self):
            return OutputOnlyMem()

    rec = devmon.analyze_compiled(Fake())
    # a backend exposing only output bytes still yields a computable
    # peak (the arg+out+temp fallback must not require arg/temp)
    assert rec["output_bytes"] == 512 and rec["peak_bytes"] == 512


def test_analyze_compiled_peak_fallback_discounts_aliasing():
    class DonatedMem:
        argument_size_in_bytes = 1000
        output_size_in_bytes = 1000
        temp_size_in_bytes = 100
        alias_size_in_bytes = 1000  # donated operands: in arg AND out

    class Fake:
        def cost_analysis(self):
            return {}

        def memory_analysis(self):
            return DonatedMem()

    rec = devmon.analyze_compiled(Fake())
    assert rec["peak_bytes"] == 1100  # not 2100: aliased counted once


def test_capture_jitted_records_into_metrics():
    import jax

    metrics.on()
    compiled, cost = devmon.capture_jitted(
        jax.jit(lambda a: (a * 2.0).sum()), (np.ones((8, 8)),),
        name="devmon.test.cap",
    )
    assert compiled is not None and cost is not None
    assert "device_kind" in cost
    assert metrics.costs()["devmon.test.cap"]["flops"] == cost["flops"]
    # the captured compile is reusable as the executable
    assert float(compiled(np.ones((8, 8)))) == 128.0


def test_capture_jitted_failure_degrades_to_none():
    class Broken:
        def lower(self, *a):
            raise RuntimeError("no lowering")

    compiled, cost = devmon.capture_jitted(Broken(), (np.ones(3),))
    assert compiled is None and cost is None


# ---------------------------------------------------------------------------
# device memory sampling: graceful on backends without the API
# ---------------------------------------------------------------------------


def test_sample_devices_graceful_none_on_cpu():
    rows = devmon.sample_devices()
    assert rows, "at least one device visible"
    for r in rows:
        assert set(r) >= {"id", "platform", "kind", "bytes_in_use",
                          "bytes_limit", "peak_bytes_in_use"}
        # XLA:CPU has no memory_stats: byte fields are None, not a crash
        assert r["bytes_in_use"] is None
        assert r["peak_bytes_in_use"] is None


def test_sample_devices_memory_stats_raising_never_crashes():
    class Weird:
        id = 99
        platform = "weird"
        device_kind = "weird9000"

        def memory_stats(self):
            raise RuntimeError("unsupported")

    [row] = devmon.sample_devices([Weird()])
    assert row["bytes_in_use"] is None


def test_sample_devices_gauges_and_high_water():
    class Fake:
        def __init__(self, use, peak=None):
            self.id = 7
            self.platform = "tpu"
            self.device_kind = "TPU v4"
            self._use, self._peak = use, peak

        def memory_stats(self):
            s = {"bytes_in_use": self._use, "bytes_limit": 1000}
            if self._peak is not None:
                s["peak_bytes_in_use"] = self._peak
            return s

    metrics.on()
    [r1] = devmon.sample_devices([Fake(100)])
    assert r1["bytes_in_use"] == 100 and r1["peak_bytes_in_use"] == 100
    [r2] = devmon.sample_devices([Fake(40)])
    # high-water mark is monotone even when the backend has no peak
    assert r2["peak_bytes_in_use"] == 100
    [r3] = devmon.sample_devices([Fake(40, peak=500)])
    assert r3["peak_bytes_in_use"] == 500
    g = metrics.gauges()
    assert g["serve.device.7.bytes_in_use"] == 40
    assert g["serve.device.7.bytes_in_use_peak"] == 500


# ---------------------------------------------------------------------------
# roofline peaks + attribution
# ---------------------------------------------------------------------------


def test_peaks_for_table_env_and_unknown_kind(monkeypatch):
    # an ambient deployment override must not shift the default-table
    # assertions below
    monkeypatch.delenv(devmon.PEAKS_ENV, raising=False)
    p = devmon.peaks_for("cpu")
    assert p["source"] == "default" and p["ridge"] == pytest.approx(
        p["flops"] / p["bytes_per_s"])
    assert devmon.peaks_for("TPU v4 MegaCore")["flops"] == \
        devmon.DEFAULT_PEAKS["tpu v4"]["flops"]
    # the v5e reports "TPU v5 lite": its own row, ahead of the v5p's
    v5e = devmon.peaks_for("TPU v5 lite")
    assert (v5e["flops"], v5e["bytes_per_s"]) == (1.97e14, 8.19e11)
    assert devmon.peaks_for("TPU v5")["flops"] == 4.59e14
    with pytest.raises(ValueError, match="martian"):
        devmon.peaks_for("martian accelerator")
    monkeypatch.setenv(
        devmon.PEAKS_ENV,
        '{"cpu": {"flops": 1e9, "bytes_per_s": 1e8}}',
    )
    p = devmon.peaks_for("cpu")
    assert p["source"] == "env" and p["flops"] == 1e9 and p["ridge"] == 10.0
    # malformed override degrades to the built-in table, never crashes
    monkeypatch.setenv(devmon.PEAKS_ENV, "{broken")
    assert devmon.peaks_for("cpu")["source"] == "default"
    # zero/negative roofs are malformed too: the ridge and frac-of-
    # roof divisions must never see them
    monkeypatch.setenv(
        devmon.PEAKS_ENV, '{"cpu": {"flops": 0, "bytes_per_s": 1}}'
    )
    p = devmon.peaks_for("cpu")
    assert p["source"] == "default" and p["flops"] > 0
    assert devmon.roofline(
        1e9, 1e9, 0.1,
        {"flops": 0, "bytes_per_s": 0, "ridge": 0, "source": "x",
         "kind": "x"},
    ) is None
    # an env row is how an operator adds a kind the table lacks
    monkeypatch.setenv(
        devmon.PEAKS_ENV, '{"martian": {"flops": 2e11, "bytes_per_s": 8e10}}'
    )
    p = devmon.peaks_for("martian accelerator")
    assert p["source"] == "env" and p["flops"] == 2e11


def test_roofline_classification():
    pk = {"flops": 1e12, "bytes_per_s": 1e11, "ridge": 10.0,
          "source": "test", "kind": "t"}
    mem = devmon.roofline(1e9, 1e9, 0.01, pk)  # AI 1 < ridge 10
    assert mem["bound"] == "memory"
    assert mem["roof_flops"] == pytest.approx(1e11)  # AI * bw
    comp = devmon.roofline(1e12, 1e10, 0.5, pk)  # AI 100 >= ridge
    assert comp["bound"] == "compute"
    assert comp["roof_flops"] == pytest.approx(1e12)
    assert 0 < comp["frac_of_roof"] <= 1e3
    # unrateable inputs are None (the "unclassifiable" signal)
    assert devmon.roofline(0.0, 1e9, 0.01, pk) is None
    assert devmon.roofline(1e9, None, 0.01, pk) is None
    assert devmon.roofline(1e9, 1e9, 0.0, pk) is None
    # the bare SLATE_TPU_PEAKS row shape (no ridge/source) works too
    bare = devmon.roofline(1e9, 1e8, 0.1,
                           {"flops": 1e12, "bytes_per_s": 1e11})
    assert bare["ridge"] == 10.0 and bare["bound"] == "compute"


# ---------------------------------------------------------------------------
# serve cache registry: capture, persistence, restore, off-path
# ---------------------------------------------------------------------------


def test_cache_registry_capture_and_manifest_persist(tmp_path):
    devmon.on()
    man = str(tmp_path / "warmup.json")
    cache = ExecutableCache(manifest_path=man)
    key = _key()
    cache.ensure_manifest(key, (1,))
    cache.warmup(batch_max=1)
    rec = cache.cost(key, 1)
    assert rec is not None
    assert rec["flops"] > 0 and rec["bytes_accessed"] > 0
    assert rec["peak_bytes"] > 0 and rec["argument_bytes"] > 0
    doc = json.loads(open(man).read())
    [entry] = doc["entries"]
    assert entry["cost"]["flops"] == rec["flops"]
    # a fresh cache restores the registry from the manifest — no
    # recapture compile needed for the evidence to exist
    cache2 = ExecutableCache(manifest_path=man)
    assert cache2.cost(key, 1) == rec
    assert cache2.costs_by_label()[key.label][1]["flops"] == rec["flops"]


def test_registry_off_by_default_zero_touch(tmp_path):
    man = str(tmp_path / "warmup.json")
    cache = ExecutableCache(manifest_path=man)
    key = _key()
    cache.ensure_manifest(key, (1,))
    cache.warmup(batch_max=1)
    assert cache.cost(key, 1) is None
    assert cache.cost_registry() == {}
    doc = json.loads(open(man).read())
    assert all("cost" not in e for e in doc["entries"])


def test_registry_no_recapture_when_already_known(tmp_path, monkeypatch):
    devmon.on()
    man = str(tmp_path / "warmup.json")
    cache = ExecutableCache(manifest_path=man)
    key = _key()
    cache.ensure_manifest(key, (1,))
    cache.warmup(batch_max=1)
    # second cache on the same manifest: registry pre-loaded, so the
    # cold build must not call the capture path again
    cache2 = ExecutableCache(manifest_path=man)
    calls = []
    real = devmon.capture_jitted
    monkeypatch.setattr(
        devmon, "capture_jitted",
        lambda *a, **kw: calls.append(1) or real(*a, **kw),
    )
    cache2.warmup(batch_max=1)
    assert calls == []


def test_solve_phase_and_batched_entries_capture(tmp_path):
    devmon.on()
    metrics.on()
    cache = ExecutableCache(manifest_path=str(tmp_path / "m.json"))
    key = _key(routine="posv")
    skey = key.solve_sibling()
    cache.ensure_manifest(key, (1, 4))
    cache.ensure_manifest(skey, (1,))
    cache.warmup(batch_max=4)
    full1, full4 = cache.cost(key, 1), cache.cost(key, 4)
    solve1 = cache.cost(skey, 1)
    assert full1 and full4 and solve1
    # the batched executable does more work than the lone one, and the
    # trsm-only solve family costs an order less than its full sibling
    # (flops_model: the CPU vendor trsm reports no XLA flops — the
    # hand-model fallback is exactly what keeps it classifiable)
    assert full4["flops"] > full1["flops"]
    assert solve1["flops_model"] < full1["flops_model"]
    assert solve1["bytes_accessed"] > 0 and solve1["peak_bytes"] > 0
    # the metrics/JSONL record carries flops_model too — the roofline
    # report's model fallback reads it from there, not from the cache
    mrec = metrics.costs()[f"serve.{skey.label}.b1"]
    assert mrec["flops_model"] == solve1["flops_model"]


def test_registry_restore_mirrors_into_metrics(tmp_path):
    """A warm-restarted process skips the recapture compile but must
    still emit the restored records into ITS metrics registry — the
    JSONL cost rows roofline_report gates on."""
    devmon.on()
    man = str(tmp_path / "warmup.json")
    cache = ExecutableCache(manifest_path=man)
    key = _key()
    cache.ensure_manifest(key, (1,))
    cache.warmup(batch_max=1)
    # fresh-process analogue: clean metrics, registry preloaded from
    # the manifest, build skips capture but mirrors the known record
    metrics.reset()
    metrics.on()
    cache2 = ExecutableCache(manifest_path=man)
    cache2.warmup(batch_max=1)
    rec = metrics.costs().get(f"serve.{key.label}.b1")
    assert rec is not None and rec["flops"] > 0


def test_registry_foreign_device_kind_recaptured(tmp_path):
    """A manifest captured on another backend must not serve stale
    evidence here: a device_kind mismatch forces a recapture on THIS
    device kind (same-kind records are reused without a compile)."""
    devmon.on()
    metrics.on()
    man = str(tmp_path / "warmup.json")
    cache = ExecutableCache(manifest_path=man)
    key = _key()
    cache.ensure_manifest(key, (1,))
    cache.warmup(batch_max=1)
    # forge a foreign record in the manifest (CPU box -> TPU replica)
    doc = json.loads(open(man).read())
    doc["entries"][0]["cost"] = {"flops": 1.0, "bytes_accessed": 1.0,
                                 "peak_bytes": 1, "device_kind": "tpu v9"}
    open(man, "w").write(json.dumps(doc))
    cache2 = ExecutableCache(manifest_path=man)
    assert cache2.cost(key, 1)["device_kind"] == "tpu v9"
    cache2.warmup(batch_max=1)
    rec = cache2.cost(key, 1)
    assert rec["device_kind"] == devmon.default_device_kind()
    assert rec["flops"] > 1.0
    assert metrics.counters()["serve.cost_foreign_recaptured"] == 1


def test_registry_foreign_recapture_failure_drops_record(tmp_path,
                                                         monkeypatch):
    """When the recapture of foreign evidence FAILS, the foreign
    record must be dropped, not kept: no evidence beats wrong
    evidence (health/roofline would join another backend's bytes
    with this device's timers)."""
    devmon.on()
    man = str(tmp_path / "warmup.json")
    cache = ExecutableCache(manifest_path=man)
    key = _key()
    cache.ensure_manifest(key, (1,))
    cache.warmup(batch_max=1)
    doc = json.loads(open(man).read())
    doc["entries"][0]["cost"] = {"flops": 1.0, "device_kind": "tpu v9"}
    open(man, "w").write(json.dumps(doc))
    monkeypatch.setattr(devmon, "capture_jitted",
                        lambda *a, **kw: (None, None))
    cache2 = ExecutableCache(manifest_path=man)
    cache2.warmup(batch_max=1)
    assert cache2.cost(key, 1) is None
    doc = json.loads(open(man).read())
    assert all("cost" not in e for e in doc["entries"])


def test_manifest_cost_loads_ignores_legacy_entries():
    key = _key()
    text = bk.manifest_dumps([(key, 1)])
    assert bk.manifest_cost_loads(text) == {}
    text = bk.manifest_dumps([(key, 1)], {(key, 1): {"flops": 42.0}})
    assert bk.manifest_cost_loads(text) == {(key, 1): {"flops": 42.0}}
    # loads() round-trips regardless (old readers unaffected)
    assert bk.manifest_loads(text) == [(key, 1)]


# ---------------------------------------------------------------------------
# health() surfacing
# ---------------------------------------------------------------------------


def test_health_surfaces_cost_devices_and_peak_bytes():
    devmon.on()
    metrics.on()
    # factor_cache=False: these tests measure the registry surface,
    # not factor routing — an env-armed SLATE_TPU_FACTOR_CACHE would
    # detour the stream off the bucket-build path
    svc = SolverService(cache=ExecutableCache(manifest_path=None),
                        batch_max=4, batch_window_s=0.002,
                        dim_floor=16, nrhs_floor=4, factor_cache=False)
    try:
        rng = np.random.default_rng(0)
        A = rng.standard_normal((12, 12)) + 12 * np.eye(12)
        X = svc.submit("gesv", A, rng.standard_normal((12, 2))).result(
            timeout=300)
        assert np.all(np.isfinite(X))
        h = svc.health()
        key = _key()
        per = h["cost"][key.label]
        assert per[1]["flops"] > 0 and per[1]["peak_bytes"] > 0
        assert h["latency"][key.label]["peak_bytes"] >= per[1]["peak_bytes"]
        assert isinstance(h["devices"], list) and h["devices"]
        assert h["devices"][0]["bytes_in_use"] is None  # CPU: graceful
    finally:
        svc.stop()


def test_health_devmon_off_is_none_and_costless():
    metrics.on()
    # factor_cache=False: these tests measure the registry surface,
    # not factor routing — an env-armed SLATE_TPU_FACTOR_CACHE would
    # detour the stream off the bucket-build path
    svc = SolverService(cache=ExecutableCache(manifest_path=None),
                        batch_max=4, batch_window_s=0.002,
                        dim_floor=16, nrhs_floor=4, factor_cache=False)
    try:
        rng = np.random.default_rng(0)
        A = rng.standard_normal((12, 12)) + 12 * np.eye(12)
        svc.submit("gesv", A, rng.standard_normal((12, 2))).result(
            timeout=300)
        h = svc.health()
        assert h["devices"] is None
        assert h["cost"] is None
        assert "peak_bytes" not in h["latency"][_key().label]
    finally:
        svc.stop()


def test_health_cost_gated_on_devmon_despite_preloaded_registry(tmp_path):
    """A cost-bearing manifest preloads the cache registry regardless,
    but health() must not claim the telemetry plane is armed when it
    is not (and must not pay the registry copy per poll)."""
    devmon.on()
    man = str(tmp_path / "warmup.json")
    cache = ExecutableCache(manifest_path=man)
    key = _key()
    cache.ensure_manifest(key, (1,))
    cache.warmup(batch_max=1)
    devmon.off()
    svc = SolverService(cache=ExecutableCache(manifest_path=man),
                        start=False)
    h = svc.health()
    assert h["cost"] is None and h["devices"] is None
    devmon.on()
    h = svc.health()
    assert h["cost"][key.label][1]["flops"] > 0


# ---------------------------------------------------------------------------
# tools: roofline_report + bench_diff
# ---------------------------------------------------------------------------


def _run_tool(tool, *argv):
    return subprocess.run(
        [sys.executable, os.path.join("tools", tool), *argv],
        cwd=HERE, capture_output=True, text=True,
    )


def _write_jsonl(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def test_roofline_report_classifies_and_gates(tmp_path):
    jsonl = tmp_path / "m.jsonl"
    exe = "gesv.16x16x4.float64.b1"
    _write_jsonl(jsonl, [
        {"type": "cost", "name": f"serve.{exe}", "flops": 2.0e7,
         "bytes_accessed": 1.0e5, "peak_bytes": 40000,
         "device_kind": "cpu"},
        {"type": "timer", "name": f"serve.{exe}.run", "count": 10,
         "total_s": 0.01},
    ])
    r = _run_tool("roofline_report.py", str(jsonl))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "compute" in r.stdout  # AI 200 >> cpu ridge 2.5
    # a warmed bucket with no cost record is unclassifiable -> nonzero
    _write_jsonl(jsonl, [
        {"type": "cost", "name": f"serve.{exe}", "flops": 2.0e7,
         "bytes_accessed": 1.0e5, "device_kind": "cpu"},
        {"type": "timer", "name": "serve.other.b1.run", "count": 3,
         "total_s": 0.01},
    ])
    r = _run_tool("roofline_report.py", str(jsonl))
    assert r.returncode == 1
    assert "unclassifiable" in r.stdout
    # no cost rows at all: nothing to verify -> nonzero
    _write_jsonl(jsonl, [
        {"type": "timer", "name": f"serve.{exe}.run", "count": 1,
         "total_s": 0.01},
    ])
    assert _run_tool("roofline_report.py", str(jsonl)).returncode == 1


def test_roofline_report_memory_bound_verdict(tmp_path):
    jsonl = tmp_path / "m.jsonl"
    exe = "gesv.16x16x4.float64.solve.b1"
    _write_jsonl(jsonl, [
        {"type": "cost", "name": f"serve.{exe}", "flops": 1.0e4,
         "bytes_accessed": 1.0e5, "device_kind": "cpu"},  # AI 0.1
        {"type": "timer", "name": f"serve.{exe}.run", "count": 5,
         "total_s": 0.005},
    ])
    r = _run_tool("roofline_report.py", str(jsonl))
    assert r.returncode == 0 and "memory" in r.stdout


def _bench_doc(scale=1.0, peak_scale=1.0):
    return {
        "metric": "sgemm", "value": 100.0 * scale, "unit": "GFLOP/s",
        "extra": {
            "dgemm": {"gflops": 50.0 * scale,
                      "peak_bytes": int(1e6 * peak_scale)},
            "skippy": {"skipped": "time budget"},
        },
    }


def test_bench_diff_passes_flat_and_fails_regression(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(_bench_doc()))
    b.write_text(json.dumps(_bench_doc(scale=0.9)))
    assert _run_tool("bench_diff.py", str(a), str(b)).returncode == 0
    b.write_text(json.dumps(_bench_doc(scale=0.5)))
    r = _run_tool("bench_diff.py", str(a), str(b))
    assert r.returncode == 1 and "REGRESSION" in r.stdout


def test_bench_diff_flags_memory_growth(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(_bench_doc()))
    b.write_text(json.dumps(_bench_doc(peak_scale=2.0)))
    r = _run_tool("bench_diff.py", str(a), str(b))
    assert r.returncode == 1 and "MEM GROWTH" in r.stdout


def test_bench_diff_floor_mode(tmp_path):
    floor, live = tmp_path / "floor.json", tmp_path / "live.json"
    live.write_text(json.dumps(_bench_doc()))
    # floor rates well below live, peak ceiling generously above it
    floor.write_text(json.dumps(_bench_doc(scale=0.1, peak_scale=4.0)))
    r = _run_tool("bench_diff.py", "--floor", str(floor), str(live))
    assert r.returncode == 0, r.stdout
    live.write_text(json.dumps(_bench_doc(scale=0.01)))
    assert _run_tool(
        "bench_diff.py", "--floor", str(floor), str(live)
    ).returncode == 1


def test_bench_diff_tolerates_malformed_entries(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    base = _bench_doc()
    base["extra"]["weird"] = 5  # non-dict entry: noted, never a crash
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(_bench_doc()))
    r = _run_tool("bench_diff.py", str(a), str(b))
    assert r.returncode == 0 and "baseline entry malformed" in r.stdout
    # candidate-side malformed entry (same label present on both sides)
    base = _bench_doc()
    base["extra"]["weird"] = {"gflops": 1.0}
    cand = _bench_doc()
    cand["extra"]["weird"] = 5
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(cand))
    r = _run_tool("bench_diff.py", str(a), str(b))
    assert r.returncode == 0 and "candidate entry malformed" in r.stdout


def test_bench_diff_nothing_compared_is_unusable(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    # an all-errored sweep still prints a JSON line; diffing it must
    # not report a clean bill of health
    doc = {"metric": "m", "value": None, "unit": "x",
           "extra": {"e1": {"error": "boom"}, "e2": {"skipped": "t"}}}
    a.write_text(json.dumps(_bench_doc()))
    b.write_text(json.dumps(doc))
    assert _run_tool("bench_diff.py", str(b), str(a)).returncode == 2
    assert _run_tool("bench_diff.py", str(a), str(b)).returncode == 2


def test_bench_diff_accepts_wrapped_trajectory_artifacts(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps({"rc": 0, "parsed": _bench_doc()}))
    b.write_text(json.dumps({"rc": 0, "parsed": _bench_doc(scale=1.1)}))
    assert _run_tool("bench_diff.py", str(a), str(b)).returncode == 0
    # an artifact with no parsed payload (a sweep that died) is unusable
    b.write_text(json.dumps({"rc": 124, "tail": "died"}))
    assert _run_tool("bench_diff.py", str(a), str(b)).returncode == 2


def test_checked_in_floor_exists_and_diffs_clean():
    # the --perf gate's input stays in the tree and stays parseable
    assert os.path.exists(os.path.join(HERE, "BENCH_FLOOR_CPU.json"))
    r = _run_tool("bench_diff.py", "BENCH_FLOOR_CPU.json",
                  "BENCH_FLOOR_CPU.json")
    assert r.returncode == 0, r.stdout

"""Fleet-tier tests: wire framing, spec parsing, router edge cases
(exactly-once under host death + hedging, drain racing re-dispatch,
no-resurrection reports, forced rejoin probes), the worker front-end
over a fake service, the stitch/merge/report tools, and a real
spawned-subprocess end-to-end.

Router tests run against a fake ``_rpc`` (no sockets, no processes):
the edge cases under test are lock-ordering and exactly-once
bookkeeping in the ROUTER, which the fake makes deterministic.  The
tools are exercised as subprocesses on hand-built files — they are
stdlib-only by contract.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import types
from concurrent.futures import Future

import numpy as np
import pytest

from slate_tpu.aux import metrics
from slate_tpu.exceptions import NumericalError
from slate_tpu.fleet import (
    FleetError,
    FleetRouter,
    FleetWorker,
    HostDead,
    parse_fleet,
    wire,
)
from slate_tpu.fleet.router import (
    HOST_DEAD,
    HOST_LIVE,
    HOST_REJOINED,
    _rebuild_exc,
    assign_chips,
)
from slate_tpu.integrity.policy import residual_certificate
from slate_tpu.serve.service import Rejected

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO, "tools")


@pytest.fixture(autouse=True)
def metrics_on():
    metrics.off()
    metrics.reset()
    metrics.on()
    yield
    metrics.off()
    metrics.reset()


def _counter(name: str) -> float:
    return float(metrics.counters().get(name, 0.0))


# ---------------------------------------------------------------------------
# wire framing
# ---------------------------------------------------------------------------


class TestWire:
    def test_roundtrip_header_and_arrays(self):
        a, b = socket.socketpair()
        try:
            A = np.arange(12, dtype=np.float32).reshape(3, 4)
            B = np.ones((3, 1), dtype=np.float64)
            wire.send_msg(a, {"op": "solve", "n": 3}, {"A": A, "B": B})
            header, arrays = wire.recv_msg(b)
            assert header == {"op": "solve", "n": 3}
            np.testing.assert_array_equal(arrays["A"], A)
            np.testing.assert_array_equal(arrays["B"], B)
            assert arrays["A"].dtype == np.float32
        finally:
            a.close()
            b.close()

    def test_noncontiguous_array_roundtrips(self):
        a, b = socket.socketpair()
        try:
            A = np.arange(16, dtype=np.float32).reshape(4, 4).T
            wire.send_msg(a, {}, {"A": A})
            _, arrays = wire.recv_msg(b)
            np.testing.assert_array_equal(arrays["A"], A)
        finally:
            a.close()
            b.close()

    def test_eof_mid_frame_raises_connection_error(self):
        a, b = socket.socketpair()
        a.sendall(b"\x00\x00\x10\x00partial")
        a.close()
        try:
            with pytest.raises(ConnectionError):
                wire.recv_msg(b)
        finally:
            b.close()

    def test_oversized_header_refused(self):
        a, b = socket.socketpair()
        import struct

        a.sendall(struct.pack(">I", wire.MAX_HEADER_BYTES + 1))
        try:
            with pytest.raises(wire.ProtocolError):
                wire.recv_msg(b)
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# spec parsing
# ---------------------------------------------------------------------------


class TestParseFleet:
    def test_spawn_and_knobs(self):
        kw = parse_fleet("spawn=3,cert=0.5,hedge=1.5,retries=4,"
                         "redispatch=1,dead_after=2,respawn")
        assert kw == {
            "spawn": 3, "cert": "sample=0.5", "hedge_s": 1.5,
            "rpc_retries": 4, "redispatch_max": 1, "dead_after": 2,
            "respawn": True,
        }

    def test_connect_addrs(self):
        kw = parse_fleet("connect=10.0.0.1:9001+:9002")
        assert kw["connect"] == (("10.0.0.1", 9001), ("127.0.0.1", 9002))

    def test_cert_spellings(self):
        assert parse_fleet("spawn=1,cert=full")["cert"] == "full"
        assert parse_fleet("spawn=1,cert=off")["cert"] == "off"
        assert parse_fleet("spawn=1,cert=sample=0.3")["cert"] == "sample=0.3"

    def test_needs_hosts(self):
        with pytest.raises(ValueError, match="spawn=<n> or connect"):
            parse_fleet("cert=full")

    def test_unknown_key_names_itself(self):
        with pytest.raises(ValueError, match="bogus"):
            parse_fleet("spawn=1,bogus=3")


@pytest.mark.parametrize(
    "platforms,chips,visible",
    [
        (["cpu", "cpu"], 1, [None, None]),  # the CPU drill: no holders
        (["", ""], 0, [None, None]),  # no chip on this host
        (["", "cpu"], 1, [None, None]),  # one holder keeps the only chip
        (["", "tpu"], 4, ["0", "1"]),  # one chip each
        (["", ""], 1, FleetError),  # two holders, one chip: refused
    ],
)
def test_assign_chips(platforms, chips, visible):
    envs = [{"JAX_PLATFORMS": p} if p else {} for p in platforms]
    if visible is FleetError:
        with pytest.raises(FleetError, match="this host has 1"):
            assign_chips(envs, chips)
        return
    assign_chips(envs, chips)
    assert [e.get("TPU_VISIBLE_CHIPS") for e in envs] == visible


# ---------------------------------------------------------------------------
# residual certificate
# ---------------------------------------------------------------------------


class TestResidualCertificate:
    def _spd(self, n=8, dtype=np.float32):
        rng = np.random.default_rng(5)
        A = rng.standard_normal((n, n))
        A = (A @ A.T + n * np.eye(n)).astype(dtype)
        return A

    def test_correct_solve_passes(self):
        A = self._spd()
        X = np.linalg.solve(A, np.ones((8, 2), dtype=np.float32))
        assert residual_certificate("gesv", A, X, np.ones((8, 2)))

    def test_corrupted_solve_fails(self):
        A = self._spd()
        B = np.ones((8, 2), dtype=np.float32)
        X = np.linalg.solve(A, B)
        X[0, 0] += 1.0
        assert not residual_certificate("gesv", A, X, B)

    def test_dtype_rebases_to_delivered_precision(self):
        # float64 operands, float32 solve: the fence must use float32's
        # eps or every correct mixed-precision delivery fails
        A = self._spd(dtype=np.float64)
        B = np.ones((8, 2), dtype=np.float64)
        X = np.linalg.solve(
            A.astype(np.float32), B.astype(np.float32)
        )
        assert residual_certificate("gesv", A, X, B)

    def test_posv_ignores_upper_junk(self):
        A = self._spd()
        B = np.ones((8, 1), dtype=np.float32)
        X = np.linalg.solve(A, B)
        junk = np.array(A)
        junk[np.triu_indices(8, 1)] = 777.0  # posv contract: lower only
        assert residual_certificate("posv", junk, X, B)
        assert not residual_certificate("gesv", junk, X, B)

    def test_gels_vacuous(self):
        assert residual_certificate("gels", np.eye(3), np.zeros(3),
                                    np.ones(3))


# ---------------------------------------------------------------------------
# router edge cases (fake RPC)
# ---------------------------------------------------------------------------


def _fake_router(n=2, **kw):
    """Connect-mode router that never opens a socket: tests install a
    fake ``_rpc`` before any dispatch."""
    kw.setdefault("heartbeat_s", 60.0)  # quiet during the test
    kw.setdefault("cert", "off")
    kw.setdefault("rpc_retries", 0)
    addrs = tuple(("127.0.0.1", 59000 + i) for i in range(n))
    return FleetRouter(connect=addrs, **kw)


def _install_rpc(r, fn):
    r._rpc = types.MethodType(fn, r)


def _ok_reply(X):
    return {"ok": True, "op": "solve"}, {"X": X}


class TestRouterEdgeCases:
    A = np.eye(4, dtype=np.float32)
    B = np.ones((4, 1), dtype=np.float32)
    X = np.ones((4, 1), dtype=np.float32)

    def test_least_loaded_pick_and_exclusion(self):
        r = _fake_router(n=3)
        _install_rpc(r, lambda self, *a, **k: _ok_reply(None))
        r.start()
        try:
            with r._lock:
                r._hosts["0"].inflight = 5
                r._hosts["1"].queue_depth = 1
                r._hosts["2"].queue_depth = 3
                assert r._pick_host_locked().name == "1"
                assert r._pick_host_locked(exclude={"1"}).name == "2"
                r._hosts["2"].state = HOST_DEAD
                assert r._pick_host_locked(exclude={"1"}).name == "0"
                assert r._pick_host_locked(exclude={"0", "1"}) is None
        finally:
            r.stop(drain=False)

    def test_host_death_with_hedge_twin_resolves_exactly_once(self):
        r = _fake_router(n=2, redispatch_max=2)
        gate = threading.Event()
        results = []

        def rpc(self, host, header, arrays=None, **kw):
            if header.get("op") != "solve":
                return {"ok": True}, {}
            if host.name == "0":
                gate.wait(timeout=30)
                raise ConnectionError("host 0 died mid-RPC")
            return _ok_reply(TestRouterEdgeCases.X)

        _install_rpc(r, rpc)
        r.start()
        try:
            fut = r.submit("gesv", self.A, self.B, deadline=30.0)
            with r._lock:
                assert len(r._pending) == 1
                p = next(iter(r._pending.values()))
            # hedge twin onto host 1 while the primary hangs on host 0
            with r._lock:
                p.hedged = True
            r._spawn_run(p, r._hosts["1"], hedge=True)
            results.append(fut.result(timeout=30))
            # the fleet declares host 0 dead while the twin already won
            r._note_host_failure(r._hosts["0"], hard=True)
            gate.set()  # the stuck RPC now fails too — must be a no-op
            time.sleep(0.2)
            assert fut.done() and fut.result() is not None
            np.testing.assert_array_equal(results[0], self.X)
            assert _counter("fleet.delivered") == 1
            assert _counter("fleet.typed_errors") == 0
            assert _counter("fleet.hedge.won") == 1
        finally:
            gate.set()
            r.stop(drain=False)

    def test_host_death_before_hedge_resolution_survivor_delivers(self):
        r = _fake_router(n=2, redispatch_max=2)
        gate0, gate1 = threading.Event(), threading.Event()

        def rpc(self, host, header, arrays=None, **kw):
            if header.get("op") != "solve":
                return {"ok": True}, {}
            if host.name == "0":
                gate0.wait(timeout=30)
                raise ConnectionError("host 0 died")
            gate1.wait(timeout=30)
            return _ok_reply(TestRouterEdgeCases.X)

        _install_rpc(r, rpc)
        r.start()
        try:
            fut = r.submit("gesv", self.A, self.B, deadline=30.0)
            with r._lock:
                p = next(iter(r._pending.values()))
                p.hedged = True
            r._spawn_run(p, r._hosts["1"], hedge=True)
            # both inflight; host 0 dies hard -> fail-fast dooms its
            # member, but the hedge twin is alive: no typed error, the
            # request waits for the survivor
            r._note_host_failure(r._hosts["0"], hard=True)
            gate0.set()
            assert not fut.done()
            gate1.set()
            np.testing.assert_array_equal(fut.result(timeout=30), self.X)
            assert _counter("fleet.delivered") == 1
            assert _counter("fleet.typed_errors") == 0
        finally:
            gate0.set()
            gate1.set()
            r.stop(drain=False)

    def test_redispatch_after_host_death(self):
        r = _fake_router(n=2, redispatch_max=2)
        gate = threading.Event()

        def rpc(self, host, header, arrays=None, **kw):
            if header.get("op") != "solve":
                return {"ok": True}, {}
            if host.name == "0":
                gate.wait(timeout=30)
                raise ConnectionError("host 0 died")
            return _ok_reply(TestRouterEdgeCases.X)

        _install_rpc(r, rpc)
        r.start()
        try:
            fut = r.submit("gesv", self.A, self.B, deadline=30.0)
            time.sleep(0.1)
            # death fail-fast re-dispatches the inflight member to the
            # surviving host WITHOUT waiting for the stuck RPC
            r._note_host_failure(r._hosts["0"], hard=True)
            np.testing.assert_array_equal(fut.result(timeout=30), self.X)
            gate.set()
            assert _counter("fleet.redispatched") == 1
            assert _counter("fleet.host_dead") == 1
        finally:
            gate.set()
            r.stop(drain=False)

    def test_report_after_death_does_not_resurrect(self):
        r = _fake_router(n=2)
        _install_rpc(r, lambda self, *a, **k: ({"ok": True}, {}))
        r.start()
        try:
            h = r._hosts["0"]
            r._note_host_failure(h, hard=True)
            with r._lock:
                assert h.state == HOST_DEAD
            r._note_report(h, {"queue_depth": 0, "burn": 0.1})
            with r._lock:
                assert h.state == HOST_DEAD  # stats only, never state
            # an ANSWERED rpc is the only way back, and it rejoins with
            # a pending certification probe rather than plain live
            r._note_host_ok(h)
            with r._lock:
                assert h.state == HOST_REJOINED
                assert h.probe_pending
        finally:
            r.stop(drain=False)

    def test_drain_racing_redispatch_resolves_typed(self):
        r = _fake_router(n=2, redispatch_max=2)
        entered = threading.Event()
        gate = threading.Event()

        def rpc(self, host, header, arrays=None, **kw):
            if header.get("op") != "solve":
                return {"ok": True}, {}
            entered.set()
            gate.wait(timeout=30)
            raise ConnectionError("failed during drain")

        _install_rpc(r, rpc)
        r.start()
        fut = r.submit("gesv", self.A, self.B, deadline=30.0)
        assert entered.wait(timeout=10)
        stopper = threading.Thread(
            target=r.stop, kwargs={"drain": True, "timeout": 20.0}
        )
        stopper.start()
        time.sleep(0.1)  # stop() is draining; now the member fails
        gate.set()
        with pytest.raises(FleetError):
            fut.result(timeout=30)
        stopper.join(timeout=30)
        assert not stopper.is_alive()
        assert _counter("fleet.redispatched") == 0
        assert _counter("fleet.typed_errors") == 1

    def test_submit_with_no_live_host_fails_typed(self):
        r = _fake_router(n=1)
        _install_rpc(r, lambda self, *a, **k: ({"ok": True}, {}))
        r.start()
        try:
            r._note_host_failure(r._hosts["0"], hard=True)
            fut = r.submit("gesv", self.A, self.B)
            with pytest.raises(HostDead, match="no live fleet host"):
                fut.result(timeout=10)
        finally:
            r.stop(drain=False)

    def test_submit_while_draining_refused(self):
        r = _fake_router(n=1)
        _install_rpc(r, lambda self, *a, **k: ({"ok": True}, {}))
        r.start()
        with r._lock:
            r._draining = True
        with pytest.raises(Rejected, match="draining"):
            r.submit("gesv", self.A, self.B)
        assert _counter("fleet.refused") == 1
        with r._lock:
            r._draining = False
        r.stop(drain=False)

    def test_rejoined_probe_certified_despite_sampling(self):
        # sample=1e-9 would certify ~never; a rejoined host's delivery
        # must be checked anyway, and a wrong probe must not deliver
        r = _fake_router(n=2, cert="sample=0.000000001")
        bad = np.full((4, 1), 7.0, dtype=np.float32)

        def rpc(self, host, header, arrays=None, **kw):
            if header.get("op") != "solve":
                return {"ok": True}, {}
            if host.name == "0":
                return _ok_reply(bad)  # finite but wrong
            return _ok_reply(
                np.linalg.solve(arrays["A"], arrays["B"]).astype(
                    np.float32
                )
            )

        _install_rpc(r, rpc)
        r.start()
        try:
            with r._lock:
                r._hosts["0"].probe_pending = True
                r._hosts["0"].state = HOST_REJOINED
                r._hosts["1"].inflight = 10  # steer the pick to host 0
            fut = r.submit("gesv", self.A, self.B, deadline=30.0)
            X = fut.result(timeout=30)
            np.testing.assert_allclose(X, self.B, atol=1e-5)
            assert _counter("fleet.cert.checked") >= 1
            assert _counter("fleet.cert.fail") >= 1
            assert _counter("fleet.redispatched") == 1
            with r._lock:
                # failed probe: still not recovered
                assert r._hosts["0"].probe_pending
        finally:
            r.stop(drain=False)

    def test_unsampled_delivery_skips_certificate(self):
        r = _fake_router(n=1, cert="sample=0.000000001")
        _install_rpc(
            r,
            lambda self, host, header, arrays=None, **kw:
            _ok_reply(TestRouterEdgeCases.X)
            if header.get("op") == "solve" else ({"ok": True}, {}),
        )
        r.start()
        try:
            r.submit("gesv", self.A, self.B).result(timeout=30)
            assert _counter("fleet.cert.checked") == 0
        finally:
            r.stop(drain=False)

    def test_typed_worker_error_resolves_without_retry(self):
        r = _fake_router(n=2)

        def rpc(self, host, header, arrays=None, **kw):
            if header.get("op") != "solve":
                return {"ok": True}, {}
            return {
                "ok": False, "error": "NumericalError",
                "message": "singular", "context": {"routine": "gesv"},
            }, {}

        _install_rpc(r, rpc)
        r.start()
        try:
            with pytest.raises(NumericalError, match="singular"):
                r.submit("gesv", self.A, self.B).result(timeout=30)
            # deterministic failure: the second host was never tried
            assert _counter("fleet.redispatched") == 0
        finally:
            r.stop(drain=False)

    def test_host_local_rejected_redispatches(self):
        r = _fake_router(n=2)

        def rpc(self, host, header, arrays=None, **kw):
            if header.get("op") != "solve":
                return {"ok": True}, {}
            if host.name == "0":
                return {"ok": False, "error": "Rejected",
                        "message": "queue full", "context": {}}, {}
            return _ok_reply(TestRouterEdgeCases.X)

        _install_rpc(r, rpc)
        r.start()
        try:
            with r._lock:
                r._hosts["1"].inflight = 10
            X = r.submit("gesv", self.A, self.B).result(timeout=30)
            np.testing.assert_array_equal(X, self.X)
            assert _counter("fleet.redispatched") == 1
        finally:
            r.stop(drain=False)

    def test_global_quota_refuses_fleet_wide(self):
        r = _fake_router(
            n=2, tenants="abuser:rate=1,burst=2;victim:rate=50,burst=20",
        )
        _install_rpc(
            r,
            lambda self, host, header, arrays=None, **kw:
            _ok_reply(TestRouterEdgeCases.X)
            if header.get("op") == "solve" else ({"ok": True}, {}),
        )
        r.start()
        try:
            rejected = 0
            for _ in range(10):
                try:
                    r.submit("gesv", self.A, self.B,
                             tenant="abuser").result(timeout=30)
                except Rejected:
                    rejected += 1
            assert rejected > 0
            assert _counter("fleet.rejected_quota") == rejected
            # the victim is untouched by the abuser's quota
            r.submit("gesv", self.A, self.B,
                     tenant="victim").result(timeout=30)
        finally:
            r.stop(drain=False)

    def test_rebuild_exc_maps_taxonomy(self):
        e = _rebuild_exc({
            "error": "Rejected", "message": "queue full",
            "context": {"routine": "gesv", "tenant": "a"},
        })
        assert isinstance(e, Rejected)
        assert e.context()["routine"] == "gesv"
        assert e.context()["tenant"] == "a"
        e = _rebuild_exc({"error": "NoSuchClass", "message": "x"})
        assert isinstance(e, FleetError)

    def test_health_shape(self):
        r = _fake_router(n=2, tenants="a:rate=10,burst=5")
        _install_rpc(r, lambda self, *a, **k: ({"ok": True}, {}))
        r.start()
        try:
            h = r.health()
            assert set(h) == {
                "hosts", "pending", "draining", "admission", "tenants",
            }
            assert h["hosts"]["0"]["state"] == HOST_LIVE
            assert "score" in h["hosts"]["0"]
            assert h["admission"] is not None
        finally:
            r.stop(drain=False)


# ---------------------------------------------------------------------------
# worker front-end (fake service, real sockets)
# ---------------------------------------------------------------------------


class _FakeService:
    def __init__(self, fail=None):
        self.fail = fail
        self.seen = []

    def submit(self, routine, A, B, **kw):
        self.seen.append((routine, dict(kw)))
        fut = Future()
        if self.fail is not None:
            fut.set_exception(self.fail)
        else:
            fut.set_result(np.linalg.solve(A, B))
        return fut

    def health(self):
        return {"phase": "ready", "queue_depth": 2, "inflight": 1,
                "admission": {"burn_ewma": 0.25}}

    def stop(self, **kw):
        self.stopped = True


@pytest.fixture()
def live_worker():
    svc = _FakeService()
    w = FleetWorker(host="127.0.0.1", service=svc)
    w.bind()
    t = threading.Thread(target=w.serve_forever,
                         kwargs={"announce": False}, daemon=True)
    t.start()
    yield w, svc
    w.shutdown()
    t.join(timeout=5)


def _call(w, header, arrays=None):
    with socket.create_connection(("127.0.0.1", w.port), timeout=10) as s:
        wire.send_msg(s, header, arrays)
        return wire.recv_msg(s)


class TestWorker:
    def test_solve_roundtrip_adopts_trace(self, live_worker):
        w, svc = live_worker
        A = np.eye(3, dtype=np.float64)
        B = np.full((3, 1), 2.0)
        reply, arrays = _call(
            w,
            {"op": "solve", "routine": "gesv", "deadline": 5.0,
             "tenant": "a", "trace": "t1-2"},
            {"A": A, "B": B},
        )
        assert reply["ok"]
        np.testing.assert_array_equal(arrays["X"], B)
        routine, kw = svc.seen[0]
        assert routine == "gesv"
        assert kw["trace_id"] == "t1-2"
        assert kw["tenant"] == "a"
        assert kw["deadline"] == 5.0

    def test_typed_error_crosses_by_name(self):
        svc = _FakeService(
            fail=Rejected("full").with_context(routine="gesv")
        )
        w = FleetWorker(host="127.0.0.1", service=svc)
        w.bind()
        t = threading.Thread(target=w.serve_forever,
                             kwargs={"announce": False}, daemon=True)
        t.start()
        try:
            reply, _ = _call(
                w, {"op": "solve", "routine": "gesv"},
                {"A": np.eye(2), "B": np.ones((2, 1))},
            )
            assert reply == {
                "ok": False, "error": "Rejected", "message": "full",
                "context": {"routine": "gesv"},
            }
        finally:
            w.shutdown()
            t.join(timeout=5)

    def test_report_op(self, live_worker):
        w, _ = live_worker
        reply, _ = _call(w, {"op": "report"})
        assert reply["ok"] and reply["phase"] == "ready"
        assert reply["queue_depth"] == 2 and reply["burn"] == 0.25
        assert reply["pid"] == os.getpid()

    def test_unknown_op_is_typed(self, live_worker):
        w, _ = live_worker
        reply, _ = _call(w, {"op": "frobnicate"})
        assert not reply["ok"] and reply["error"] == "ProtocolError"


# ---------------------------------------------------------------------------
# tools: trace_stitch / metrics_merge --tag / fleet_report
# ---------------------------------------------------------------------------


def _run_tool(name, *args):
    return subprocess.run(
        [sys.executable, os.path.join(_TOOLS, name), *args],
        capture_output=True, text=True,
    )


def _chrome(pid, events, pname=None):
    rows = []
    if pname:
        rows.append({"name": "process_name", "ph": "M", "pid": pid,
                     "args": {"name": pname}})
    for name, tid, ts, args in events:
        rows.append({"name": name, "ph": "X", "pid": pid, "tid": tid,
                     "ts": ts, "dur": 10.0, "cat": "span", "args": args})
    return {"traceEvents": rows}


class TestTraceStitch:
    def test_joined_chain_no_orphans(self, tmp_path):
        router = _chrome(100, [
            ("request", 0, 0.0, {"span": 1, "trace": "t64-1"}),
            ("dispatch", 1, 2.0,
             {"span": 2, "parent": 1, "trace": "t64-1"}),
        ], pname="router")
        host = _chrome(200, [
            ("request", 0, 0.0, {"span": 1, "trace": "t64-1"}),
            ("execute", 1, 1.0,
             {"span": 2, "parent": 1, "trace": "t64-1"}),
        ], pname="host0")
        rp, hp = tmp_path / "r.json", tmp_path / "h.json"
        rp.write_text(json.dumps(router))
        hp.write_text(json.dumps(host))
        out = tmp_path / "stitched.json"
        res = _run_tool("trace_stitch.py", str(rp), str(hp),
                        "-o", str(out))
        assert res.returncode == 0, res.stdout + res.stderr
        assert "cross=1 orphans=0" in res.stdout
        doc = json.loads(out.read_text())
        spans_args = [
            e["args"] for e in doc["traceEvents"]
            if e.get("ph") == "X"
        ]
        # per-process span namespacing: two hosts' sid 1 never alias
        sids = {a["span"] for a in spans_args}
        assert sids == {"100:1", "100:2", "200:1", "200:2"}
        # process_name metadata preserved
        names = {
            e["args"]["name"] for e in doc["traceEvents"]
            if e.get("name") == "process_name"
        }
        assert names == {"router", "host0"}

    def test_orphan_chain_flags_nonzero(self, tmp_path):
        # trace minted by pid 0x3e7, but no file from that process
        host = _chrome(200, [
            ("request", 0, 0.0, {"span": 1, "trace": "t3e7-9"}),
        ])
        hp = tmp_path / "h.json"
        hp.write_text(json.dumps(host))
        res = _run_tool("trace_stitch.py", str(hp))
        assert res.returncode == 2
        assert "orphans=1" in res.stdout
        res = _run_tool("trace_stitch.py", str(hp), "--allow-orphans")
        assert res.returncode == 0

    def test_pid_collision_rekeyed(self, tmp_path):
        a = _chrome(100, [("x", 0, 0.0, {"span": 1, "trace": "t64-1"})])
        b = _chrome(100, [("y", 0, 0.0, {"span": 1, "trace": "t64-2"})])
        ap, bp = tmp_path / "a.json", tmp_path / "b.json"
        ap.write_text(json.dumps(a))
        bp.write_text(json.dumps(b))
        out = tmp_path / "s.json"
        res = _run_tool("trace_stitch.py", str(ap), str(bp), "-o",
                        str(out), "--allow-orphans")
        assert res.returncode == 0
        doc = json.loads(out.read_text())
        pids = {e["pid"] for e in doc["traceEvents"]}
        assert len(pids) == 2


class TestMetricsMergeTag:
    def test_tagged_rows_precede_preserved_globals(self, tmp_path):
        a = [{"type": "counter", "name": "fleet.delivered", "value": 5},
             {"type": "gauge", "name": "g", "value": 1}]
        b = [{"type": "counter", "name": "fleet.delivered", "value": 3},
             {"type": "gauge", "name": "g", "value": 9}]
        ap, bp = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        ap.write_text("\n".join(json.dumps(r) for r in a))
        bp.write_text("\n".join(json.dumps(r) for r in b))
        res = _run_tool("metrics_merge.py", "--tag", "host0", "--tag",
                        "host1", str(ap), str(bp))
        assert res.returncode == 0
        rows = [json.loads(x) for x in res.stdout.splitlines()]
        tagged = [r for r in rows if "src" in r]
        plain = [r for r in rows if "src" not in r and
                 r["type"] == "counter"]
        assert {(r["name"], r["src"], r["value"]) for r in tagged
                if r["type"] == "counter"} == {
            ("fleet.delivered", "host0", 5),
            ("fleet.delivered", "host1", 3),
        }
        assert plain == [
            {"type": "counter", "name": "fleet.delivered", "value": 8.0}
        ]
        # tagged rows come FIRST so last-wins loaders land on globals
        assert rows.index(tagged[0]) < rows.index(plain[0])

    def test_tag_count_mismatch_fails(self, tmp_path):
        ap = tmp_path / "a.jsonl"
        ap.write_text("")
        res = _run_tool("metrics_merge.py", "--tag", "x", "--tag", "y",
                        str(ap))
        assert res.returncode != 0
        assert "pair positionally" in res.stderr

    def test_untagged_output_unchanged(self, tmp_path):
        ap = tmp_path / "a.jsonl"
        ap.write_text(json.dumps(
            {"type": "counter", "name": "c", "value": 1}
        ))
        res = _run_tool("metrics_merge.py", str(ap))
        rows = [json.loads(x) for x in res.stdout.splitlines()]
        assert all("src" not in r for r in rows if r["type"] != "timeline")


class TestFleetReport:
    def _write(self, tmp_path, rows):
        p = tmp_path / "m.jsonl"
        p.write_text("\n".join(json.dumps(r) for r in rows))
        return str(p)

    def _base(self, **over):
        rows = {
            "fleet.submitted": 10, "fleet.delivered": 8,
            "fleet.typed_errors": 2, "fleet.bad_results": 0,
        }
        rows.update(over)
        return [{"type": "counter", "name": k, "value": v}
                for k, v in rows.items()]

    def test_reconciled_run_passes(self, tmp_path):
        rows = self._base() + [
            {"type": "gauge", "name": "fleet.trace_orphans", "value": 0},
        ]
        res = _run_tool("fleet_report.py",
                        self._write(tmp_path, rows), "--require-stitch")
        assert res.returncode == 0, res.stdout

    def test_hung_future_fails(self, tmp_path):
        rows = self._base(**{"fleet.delivered": 7})
        res = _run_tool("fleet_report.py", self._write(tmp_path, rows))
        assert res.returncode == 1
        assert "FAIL  no hung futures" in res.stdout

    def test_bad_result_fails(self, tmp_path):
        rows = self._base(**{"fleet.bad_results": 1})
        res = _run_tool("fleet_report.py", self._write(tmp_path, rows))
        assert res.returncode == 1
        assert "FAIL  no silent wrong answers" in res.stdout

    def test_sdc_without_recovery_fails(self, tmp_path):
        rows = self._base(**{
            "faults.injected.sdc_solve": 3, "fleet.cert.fail": 2,
            "fleet.quarantined": 1, "fleet.unquarantined": 0,
        })
        res = _run_tool("fleet_report.py", self._write(tmp_path, rows))
        assert res.returncode == 1
        assert "FAIL  sdc quarantined + probe-recovered" in res.stdout

    def test_victim_p99_judged_from_tenant_hist(self, tmp_path):
        rows = self._base(**{"fleet.rejected_quota": 4}) + [
            {"type": "hist", "name": "fleet.latency.tenant.v.total",
             "count": 5, "p99": 0.4},
        ]
        res = _run_tool("fleet_report.py", self._write(tmp_path, rows),
                        "--victim", "v", "--p99-budget", "1.0")
        assert res.returncode == 0, res.stdout
        res = _run_tool("fleet_report.py", self._write(tmp_path, rows),
                        "--victim", "v", "--p99-budget", "0.1")
        assert res.returncode == 1

    def test_missing_stitch_gauge_fails_when_required(self, tmp_path):
        res = _run_tool("fleet_report.py",
                        self._write(tmp_path, self._base()),
                        "--require-stitch")
        assert res.returncode == 1
        assert "gauge missing" in res.stdout

    def test_non_fleet_jsonl_refused(self, tmp_path):
        rows = [{"type": "counter", "name": "serve.dispatches",
                 "value": 1}]
        res = _run_tool("fleet_report.py", self._write(tmp_path, rows))
        assert res.returncode == 2


# ---------------------------------------------------------------------------
# serve.api zero-overhead-off wiring
# ---------------------------------------------------------------------------


class TestApiWiring:
    def test_fleet_off_is_none_branch(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("SLATE_TPU_FLEET", None)
        out = subprocess.run(
            [sys.executable, "-c",
             "from slate_tpu.serve import api; "
             "print(api._fleet, api.get_fleet())"],
            capture_output=True, text=True, env=env, cwd=_REPO,
        )
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == ["None", "None"]

    def test_fleet_env_builds_router_at_import(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   SLATE_TPU_FLEET="spawn=2,cert=full")
        out = subprocess.run(
            [sys.executable, "-c",
             "from slate_tpu.serve import api; "
             "print(type(api._fleet).__name__, api._fleet.spawn, "
             "api._fleet.policy.describe())"],
            capture_output=True, text=True, env=env, cwd=_REPO,
        )
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == ["FleetRouter", "2", "full"]


# ---------------------------------------------------------------------------
# spawned-subprocess end-to-end
# ---------------------------------------------------------------------------


class TestEndToEnd:
    def test_spawned_worker_solves_and_drains(self, tmp_path):
        r = FleetRouter(
            spawn=1, cert="full", heartbeat_s=0.3, rpc_timeout_s=60,
            spawn_env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": _REPO},
        )
        r.start()
        try:
            rng = np.random.default_rng(0)
            A = (rng.standard_normal((8, 8))
                 + 8 * np.eye(8)).astype(np.float32)
            B = rng.standard_normal((8, 2)).astype(np.float32)
            futs = [r.submit("gesv", A, B, deadline=90.0)
                    for _ in range(3)]
            for f in futs:
                X = f.result(timeout=120)
                assert np.max(np.abs(A @ X - B)) < 1e-3
            assert _counter("fleet.delivered") == 3
            assert _counter("fleet.cert.checked") == 3
        finally:
            r.stop(drain=True)
        # drained, reaped: the worker process is gone
        with r._lock:
            procs = [h.proc for h in r._hosts.values()]
        assert all(p.poll() is not None for p in procs)

    @pytest.mark.slow
    def test_sigkill_mid_stream_every_future_resolves(self):
        r = FleetRouter(
            spawn=2, cert="sample=0.25", heartbeat_s=0.2,
            rpc_timeout_s=60, dead_after=2, respawn=True,
            spawn_env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": _REPO},
        )
        r.start()
        try:
            rng = np.random.default_rng(0)
            A = (rng.standard_normal((16, 16))
                 + 16 * np.eye(16)).astype(np.float32)
            B = rng.standard_normal((16, 2)).astype(np.float32)
            for f in [r.submit("gesv", A, B, deadline=90.0)
                      for _ in range(4)]:
                f.result(timeout=120)
            futs = [r.submit("gesv", A, B, deadline=90.0)
                    for _ in range(8)]
            with r._lock:
                proc = r._hosts["0"].proc
            proc.kill()
            for f in futs:
                X = f.result(timeout=120)  # value or typed, never hung
                assert np.max(np.abs(A @ X - B)) < 1e-3
            # the killed host came back: respawn -> rejoin -> probe
            deadline = time.time() + 60
            state = None
            while time.time() < deadline:
                state = r.health()["hosts"]["0"]["state"]
                if state in ("live", "rejoined"):
                    break
                time.sleep(0.3)
            assert state in ("live", "rejoined")
            assert _counter("fleet.host_dead") >= 1
            assert _counter("fleet.redispatched") >= 1
            assert _counter("fleet.host_respawned") >= 1
        finally:
            r.stop(drain=True)

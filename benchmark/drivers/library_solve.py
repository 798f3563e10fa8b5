"""Library traffic: whole public-driver solves, one after another, on
operands resident on the device, as a user of the library calls them.

The configuration gives the routine, ``n``, ``nrhs``, ``grid`` (1x1:
one chip) and ``options`` (Option names -> values passed to the driver).  The
traffic file gives ``operands`` (how many distinct operand pairs the
window cycles through, so that no answer is the previous call's) and
``trace_solves`` (solves under the profiler in a ``--trace 1`` run) and
``check_answers`` (how many of the window's answers are compared).

The window runs whole solves, each ended by ``block_until_ready``,
until ``--seconds`` have passed; ``solve_s`` is the window's wall time
over the solves it completed.  Every answer is kept on the device; a
sample drawn from the seed is compared with the reference once the
window has closed.
"""

from __future__ import annotations

import random
import time

import numpy as np

import gen
import harness
import reference


def solve_fn(st, cfg, opts, root=harness.ROOT):
    """The timed program, as a function of global (n, n) and (n, nrhs)
    device arrays on one chip: the call of ``routines/<routine>.py``."""
    call = harness.routine(cfg["routine"], root).call
    nb = int(cfg["nb"])

    def solve(A, B):
        return call(st, A, B, nb, opts)

    return solve


def options(st, cfg) -> dict:
    return {getattr(st.Option, k): v
            for k, v in cfg.get("options", {}).items()}


class Run:
    def __init__(self, cell, seed: int, devices, tracer=None):
        self.cell = cell
        self.cfg = cell.config
        self.tr = cell.traffic
        self.seed = int(seed)
        self.devices = devices
        self.tracer = tracer
        self.n = int(self.cfg["n"])
        self.nrhs = int(self.cfg["nrhs"])
        self.p, self.q = (int(x) for x in self.cfg["grid"])
        self.K = int(self.tr["operands"])
        self.outs = []  # (operand index, X on the device)
        self.traced_solves = 0

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        import slate_tpu as st

        if self.p * self.q != 1:
            raise ValueError("library_solve runs on one chip (grid 1x1)")
        dt = jnp.dtype(self.cfg["dtype"])
        self._make = self._local_maker(jax, jnp, dt)
        solve = solve_fn(st, self.cfg, options(st, self.cfg), self.cell.root)
        # one program, as a user jits the call (the driver called eagerly
        # re-lowers its pieces on every call)
        jsolve = jax.jit(solve)
        self.call = lambda k: jsolve(*self.ops[k])
        self.reseed(self.seed)
        for k in range(self.K):  # compile, and touch every operand once
            self.call(k).block_until_ready()

    def reseed(self, seed: int) -> None:
        """The run's operands for ``seed`` (made on the device)."""
        import jax.numpy as jnp

        self.seed = int(seed)
        self.ops = None
        self.ops = [self._make(jnp.uint32(gen.key(self.seed, 2 * k)),
                               jnp.uint32(gen.key(self.seed, 2 * k + 1)))
                    for k in range(self.K)]

    def _local_maker(self, jax, jnp, dt):
        n, nrhs = self.n, self.nrhs
        spd = self.cfg["matrix"] == "tester_spd"
        dev = self.devices[0]

        # keys enter as traced uint32 scalars: one program for all seeds
        @jax.jit
        def make(ka, kb):
            A = (gen.spd(jnp, ka, n, dt) if spd
                 else gen.general(jnp, ka, n, n, dt))
            return A, gen.general(jnp, kb, n, nrhs, dt)

        def on_device(ka, kb):
            with jax.default_device(dev):
                return make(ka, kb)

        return on_device

    # -- the window -----------------------------------------------------

    def window(self, seconds: float) -> dict:
        want_trace = self.tracer is not None
        t_start = time.perf_counter()
        i = 0
        while True:
            k = i % self.K
            if want_trace and i == 0:
                self.tracer.start()
            X = self.call(k)
            X.block_until_ready()
            self.outs.append((k, X))
            i += 1
            if want_trace and i == int(self.tr["trace_solves"]):
                self.tracer.stop()
                self.traced_solves = i
                want_trace = False
            t_end = time.perf_counter()
            if t_end - t_start >= seconds and not want_trace:
                break
        return {"solve_s": (t_end - t_start) / i}

    # -- after the window ----------------------------------------------

    def layer_inputs(self) -> dict:
        w = self.cell.work()
        item = np.dtype(self.cfg["dtype"]).itemsize
        return {
            "solves": self.traced_solves,
            "ops_per_solve": w.ops(self.n, self.nrhs),
            "bytes_per_solve": w.bytes_moved(self.n, self.nrhs, item),
            "chips": self.p * self.q,
            "config": self.cfg,
        }

    def check(self):
        """Compare a sample of the window's answers, drawn from the seed,
        with the reference solve of their operand pair: ``check_answers``
        of them, the window's first and last always among them."""
        outs = self.outs
        m = int(self.tr["check_answers"])
        rest = list(range(1, len(outs) - 1))
        pick = sorted({0, len(outs) - 1} | set(random.Random(
            self.seed ^ 0x5EED).sample(rest, max(0, min(len(rest), m - 2)))))
        answers = [(outs[i][0], np.asarray(outs[i][1])) for i in pick]
        self.outs, self.ops = [], None
        worst = {"residual": 0.0, "gap": 0.0}
        failed = 0
        lim = reference.limits(self.cfg)
        for k in sorted({k for k, _ in answers}):
            A, B = reference.host_operands(self.cfg, self.n, self.nrhs,
                                           self.seed, k)
            X_ref = reference.solve(A, B)
            for kk, X in answers:
                if kk != k:
                    continue
                got = reference.numbers(self.cfg, A, X, B, X_ref)
                failed += any(not got[m] <= lim[m] for m in lim)
                for name in worst:
                    worst[name] = max(worst[name], got[name])
        return reference.judge(worst, lim), len(outs), failed

"""The two ways a traffic file loads the service, selected by its ``loop``.

``open``: independent clients submit what-if requests on a schedule fixed
in advance (:func:`traffic.open_loop`); each request is timed from the
moment it was due to the moment its ``Report`` is held.  ``closed_mc``: one
caller sends ``query_mc`` back to back, a new seed per call.

Each driver builds the service and warms it (set-up), runs the window,
keeps what the window served as plain arrays, and hands the reference
check the scenarios it must re-solve.
"""

from __future__ import annotations

import threading
import time

import numpy as np

import deploy
import traffic as gen

#: seconds a request may take past the window's close before it counts lost
GRACE_S = 60.0


class Served:
    """The rows one answer carried, as arrays (no program objects kept)."""

    def __init__(self, rep):
        self.makespans = np.array(rep.makespans, np.float64)
        self.finish = {n: np.array(rep.finish[n], np.float64)
                       for n in rep.order}
        kinds: dict = {}
        for j, (proc, kind, _name) in enumerate(rep.factors):
            kinds.setdefault((proc, kind), []).append(j)
        self.share = {k: np.asarray(rep.share_seconds)[:, js].sum(1)
                      for k, js in kinds.items()}
        self.backends = list(rep.backends)


def _service(wf, traffic):
    from repro.analysis import AnalysisService

    return AnalysisService(wf, backend="jax", **traffic.get("service", {}))


class OpenLoop:
    def __init__(self, config, traffic, seed, seconds, span):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.span = seed, seconds, span
        self.dkeys = deploy.data_keys(config)

    def setup(self) -> None:
        self.svc = _service(deploy.build_workflow(self.config), self.traffic)
        mb = self.svc.max_batch
        for batch in gen.warm_batches(self.traffic, self.config, self.seed, mb):
            self.svc.query([deploy.program_scenario(o, self.dkeys)
                            for o in batch], timeout=1200)
        self.schedule(self.seed)

    def schedule(self, seed: int) -> None:
        """The window's requests for ``seed``, built into program objects
        before the window opens."""
        self.seed = seed
        self.requests = gen.open_loop(self.traffic, self.config, seed,
                                      self.seconds)
        self.specs = [[deploy.program_scenario(o, self.dkeys) for o in scs]
                      for _due, scs in self.requests]

    def window(self) -> None:
        n = len(self.requests)
        self.done = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.served: list = [None] * n
        self.errors: list = []
        left = threading.Semaphore(0)
        self.stats0 = self.svc.snapshot()

        futs: list = [None] * n

        def finished(i, fut):
            self.done[i] = time.perf_counter()
            futs[i] = fut
            left.release()

        with self.span("bench.window"):
            self.t0 = time.perf_counter() + 0.01
            for i, (due, _scs) in enumerate(self.requests):
                wait = self.t0 + due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                self.sent[i] = time.perf_counter()
                with self.span("bench.submit"):
                    fut = self.svc.submit(self.specs[i])
                fut.add_done_callback(lambda f, i=i: finished(i, f))
            close = self.t0 + self.seconds
            for _ in range(n):
                if not left.acquire(timeout=max(close + GRACE_S
                                                - time.perf_counter(), 0.0)):
                    break
        self.t1 = time.perf_counter()
        self.stats1 = self.svc.snapshot()
        for i, fut in enumerate(futs):
            if fut is None:
                continue
            try:
                self.served[i] = Served(fut.result())
            except Exception as e:  # noqa: BLE001 — a lost request is data
                self.errors.append(repr(e))

    def close(self) -> None:
        self.svc.close(drain=False)

    # -- results ---------------------------------------------------------
    def attempted(self) -> int:
        return len(self.requests)

    def lost(self) -> list:
        return [i for i in range(len(self.requests))
                if self.served[i] is None]

    def failed(self) -> int:
        off_path = sum(1 for s in self.served
                       if s is not None and set(s.backends) != {"jax"})
        return len(self.lost()) + off_path

    def off_path(self) -> int:
        """Served rows that the fused engine did not answer (degraded to
        the host twin, or run by another backend)."""
        return sum(b != "jax" for s in self.served if s is not None
                   for b in s.backends)

    def end_to_end(self) -> dict:
        due = self.t0 + np.array([r[0] for r in self.requests])
        lat = (self.done - due) * 1e3
        lat[[i for i in self.lost()]] = np.inf
        return {"whatif_p50_ms": float(np.percentile(lat, 50)),
                "whatif_p95_ms": float(np.percentile(lat, 95))}

    def lags_ms(self) -> np.ndarray:
        due = self.t0 + np.array([r[0] for r in self.requests])
        return (self.sent - due) * 1e3

    def to_check(self, k: int) -> list:
        """``(overrides, served, row)`` of the sampled requests: ``k``
        drawn from the seed among those served, plus the largest."""
        ok = [i for i in range(len(self.requests)) if self.served[i] is not None]
        if not ok:
            return []
        rng = np.random.default_rng([int(self.seed), 3])
        pick = set(rng.choice(ok, size=min(k, len(ok)), replace=False).tolist())
        pick.add(max(ok, key=lambda i: len(self.requests[i][1])))
        return [(o, self.served[i], j) for i in sorted(pick)
                for j, o in enumerate(self.requests[i][1])]


class ClosedMC:
    def __init__(self, config, traffic, seed, seconds, span):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.span = seed, seconds, span
        self.dkeys = deploy.data_keys(config)
        self.n = int(traffic["draws"])

    def setup(self) -> None:
        self.svc = _service(deploy.build_workflow(self.config), self.traffic)
        self.spec = deploy.program_mc_spec(self.traffic["dists"], self.dkeys)
        for s in gen.mc_seeds(self.seed, 2, warm=True):
            self.svc.query_mc(self.spec, self.n, seed=s, timeout=1200)

    def window(self) -> None:
        seeds = gen.mc_seeds(self.seed, 10_000)
        self.calls: list = []        # (seed, start, end, Served, samples, q)
        self.errors: list = []
        self.stats0 = self.svc.snapshot()
        with self.span("bench.window"):
            self.t0 = time.perf_counter()
            while time.perf_counter() - self.t0 < self.seconds:
                s = seeds[len(self.calls) + len(self.errors)]
                start = time.perf_counter()
                try:
                    with self.span("bench.query_mc"):
                        mc = self.svc.query_mc(self.spec, self.n, seed=s,
                                               timeout=self.seconds + GRACE_S)
                except Exception as e:  # noqa: BLE001 — a lost call is data
                    self.errors.append(repr(e))
                    continue
                end = time.perf_counter()
                q = {lv: mc.quantile(lv) for lv in mc.quantile_levels}
                self.calls.append((s, start, end, Served(mc.report),
                                   {k: np.array(v) for k, v in mc.samples.items()},
                                   q))
        self.t1 = time.perf_counter()
        self.stats1 = self.svc.snapshot()

    def close(self) -> None:
        self.svc.close(drain=False)

    def attempted(self) -> int:
        return len(self.calls) + len(self.errors)

    def failed(self) -> int:
        off_path = sum(1 for c in self.calls if set(c[3].backends) != {"jax"})
        return len(self.errors) + off_path

    def off_path(self) -> int:
        """Draws that the fused engine did not answer (scalar fallbacks,
        rows degraded to the host twin)."""
        return sum(b != "jax" for c in self.calls for b in c[3].backends)

    def end_to_end(self) -> dict:
        if not self.calls:
            return {}
        span = self.calls[-1][2] - self.calls[0][1]
        return {"mc_draws_per_s": self.n * len(self.calls) / span}

    def checked_calls(self, count: int) -> list:
        rng = np.random.default_rng([int(self.seed), 3])
        idx = rng.choice(len(self.calls), size=min(count, len(self.calls)),
                         replace=False)
        return [self.calls[i] for i in sorted(idx.tolist())]


DRIVERS = {"open": OpenLoop, "closed_mc": ClosedMC}

"""Fan-in joins in the batched engines: a process fed by several upstream
processes follows the MINIMUM of its edge-fed data ceilings, and finishes
only when every input allows.

Each case is a workflow of ``n`` source processes feeding one sink through
``n`` edges, built from a deployment file as the benchmark's
(``perfbench/deploy.py``), so the benchmark's independent reference
(``perfbench/reference.py``) solves the same thing:

* ``tied``: every source starts at t0 and needs a different CPU time, so
  the sink's ceilings all start at 0 at the same instant with different
  slopes (the ceiling that is lower just after t0 must win the tie);
* ``crossover``: the sources' inputs arrive later but faster one after the
  other, so the lowest ceiling changes hands after t0;
* ``burst``: as ``crossover``, with the sink's first edge a burst input (the
  whole upstream output before any progress).

The numpy and fused (``jax``) engines are held to the scalar solver
(``backend="loop"``) and to the reference at 1e-9 on finish times,
makespans and per-process data / resource seconds; then the differentiable
fixed-trip driver (``make_diff_run``) on a three-way join.  Every process
of the Montage deployment is checked in ``test_fan_in_montage.py`` (a file
of its own, so a worker can take it alongside these cases).
"""

from __future__ import annotations

import contextlib
import importlib.util
import signal
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.sweep.jax_engine import DEFAULT_ITER_CAP, JaxSweepEngine

BENCH = Path(__file__).resolve().parent.parent / "perfbench"
RTOL = 1e-9


def _load(name: str):
    spec = importlib.util.spec_from_file_location(f"perfbench_{name}",
                                                  BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


deploy, reference, sampler = _load("deploy"), _load("reference"), \
    _load("sampler")


@contextlib.contextmanager
def time_limit(seconds: int):
    """Fail the test (``TimeoutError``) if its body runs past ``seconds``."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def expire(_sig, _frame):
        raise TimeoutError(f"test body exceeded its {seconds} s limit")

    old = signal.signal(signal.SIGALRM, expire)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _source(i: int, case: str) -> dict:
    if case == "tied":     # input all there at t0; CPU time 1 + i seconds
        arrive = {"starts": [0.0], "values": [10.0], "slopes": [0.0]}
        cpu = 1.0 + i
    else:                  # arrives later but faster for larger i
        d, r = 0.1 * (i + 1) + 0.013 * i * i, 2.0 + 0.7 * i
        arrive = {"starts": [0.0, d, d + 10.0 / r], "values": [0.0, 0.0, 10.0],
                  "slopes": [0.0, r, 0.0]}
        cpu = 0.25
    return {"name": f"s{i}", "total_progress": 10.0,
            "data": [{"name": "in", "kind": "stream", "input_bytes": 10.0,
                      "input": arrive}],
            "resources": [{"name": "cpu", "kind": "stream", "amount": cpu,
                           "alloc": {"starts": [0.0], "rates": [1.0]}}]}


def fan_in(n: int, case: str) -> dict:
    """``n`` sources -> one sink ``c``; the sink's CPU caps it at 20/3
    progress per second, so it is data-limited part of the time."""
    sink = {"name": "c", "total_progress": 10.0,
            "data": [{"name": f"d{i}",
                      "kind": "burst" if case == "burst" and i == 0
                      else "stream",
                      "input_bytes": 10.0, "from": f"s{i}"} for i in range(n)],
            "resources": [{"name": "cpu", "kind": "stream", "amount": 1.5,
                           "alloc": {"starts": [0.0], "rates": [1.0]}}]}
    return {"processes": [_source(i, case) for i in range(n)] + [sink]}


def scenarios(n: int, case: str) -> list:
    """The base scenario and three that move one input of the join.  (The
    last one's 1.25 was 1.5, at which the reference stalls on the two-way
    crossover: ``perfbench/tests/witness_reference_stall.py``.)"""
    key = "cpu" if case == "tied" else "in"
    return [{}, {f"s0.{key}": ("scale", 2.0)},
            {f"s{n - 1}.{key}": ("scale", 0.5)},
            {"c.cpu": ("scale", 3.0), f"s{n // 2}.{key}": ("scale", 1.25)}]


def sweep(cfg: dict, overrides: list, backend: str):
    plan = deploy.build_workflow(cfg).compile()
    specs = [deploy.program_scenario(o, deploy.data_keys(cfg))
             for o in overrides]
    rep = plan.sweep(plan.prepare(specs) if backend == "jax" else specs,
                     backend=backend)
    assert set(rep.backends) == {"jax" if backend == "jax" else
                                 {"numpy": "batched"}.get(backend, backend)}
    return plan, specs, rep


def kind_seconds(rep) -> dict:
    """``{(process, "data" | "resource"): (B,) seconds}``."""
    out: dict = {}
    for j, (proc, kind, _name) in enumerate(rep.factors):
        out[(proc, kind)] = out.get((proc, kind), 0.0) \
            + np.asarray(rep.share_seconds)[:, j]
    return out


def assert_same(got, want, procs):
    """Finish times and makespans at ``RTOL``; data / resource seconds
    within ``RTOL`` of the makespan.  ``want`` is a loop-backend report or
    a list of the reference's solutions."""
    if isinstance(want, list):
        w_ms = np.array([s["makespan"] for s in want])
        w_fin = {p: np.array([s["finish"][p] for s in want]) for p in procs}
        w_sh = {(p, k): np.array([s["share"][(p, k)] for s in want])
                for p in procs for k in ("data", "resource")}
    else:
        w_ms, w_fin, w_sh = want.makespans, want.finish, kind_seconds(want)
    np.testing.assert_allclose(got.makespans, w_ms, rtol=RTOL, atol=0)
    for p in procs:
        np.testing.assert_allclose(got.finish[p], w_fin[p], rtol=RTOL, atol=0,
                                   err_msg=p)
    g_sh = kind_seconds(got)
    for key in set(g_sh) | set(w_sh):
        a = g_sh.get(key, np.zeros(len(w_ms)))
        b = w_sh.get(key, np.zeros(len(w_ms)))
        assert np.all(np.abs(a - b) <= RTOL * w_ms), (key, a, b)


@pytest.mark.parametrize("case", ["tied", "crossover", "burst"])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("n", [2, 3, 23])
def test_join_follows_the_lowest_ceiling(n, backend, case):
    cfg = fan_in(n, case)
    ovs = scenarios(n, case)
    with time_limit(120):
        _plan, _specs, got = sweep(cfg, ovs, backend)
        _plan, _specs, loop = sweep(cfg, ovs, "loop")
    procs = [p["name"] for p in cfg["processes"]]
    assert_same(got, loop, procs)
    ref = reference.Reference(cfg)
    assert_same(got, [ref.solve(o) for o in ovs], procs)
    # the sink waits for its slowest input: never before any source
    src = np.max([got.finish[f"s{i}"] for i in range(n)], 0)
    assert np.all(got.finish["c"] >= src * (1 - RTOL))


def test_diff_run_makespan_on_a_three_way_join():
    """The fixed-trip scan driver (``plan.optimize``'s engine) runs the same
    level body: its makespans match the loop backend's.  The sink's CPU
    drops to a tenth at 2.5 s, so the sink, which must wait for its slowest
    input, is the last to finish."""
    cfg = fan_in(3, "tied")
    cfg["processes"][-1]["resources"][0]["alloc"] = {"starts": [0.0, 2.5],
                                                     "rates": [1.0, 0.1]}
    ovs = scenarios(3, "tied")
    with time_limit(120):
        plan, specs, loop = sweep(cfg, ovs, "loop")
        pack = plan.prepare(specs)
        eng = JaxSweepEngine(plan)
        B = pack.B_batched
        dev = eng.device_args(eng.level_args(pack.host_args(), B, pack.ramps),
                              B)
        run = jax.jit(eng.make_diff_run(B, DEFAULT_ITER_CAP, pack.ramps))
        ms, overflow = run(dev, None)
    assert not bool(overflow)
    assert np.all(loop.makespans == loop.finish["c"])
    np.testing.assert_allclose(np.asarray(ms), loop.makespans, rtol=RTOL,
                               atol=0)

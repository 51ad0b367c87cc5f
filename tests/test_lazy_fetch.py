"""The fused engine's fetch: finish times, shares and trip counts come back
in one tree; progress stays on the device until something reads it, and
then every process's progress of that solve comes back at once.

Checked: lazily fetched progress is bit for bit what reading every output
array with ``np.asarray`` gives (the paper workflow, a ramps workflow, the
Montage deployment at small B, and the ``pmap``-sharded path on four forced
CPU devices in a subprocess); a served Monte Carlo call fetches no
progress; ``fetch_bytes`` counts the finish, share and trip-count arrays;
the curve queries read the same values as over the eager arrays, with one
lazy fetch per solve, also when many threads read at once; a dropped
report lets its device progress go without the cyclic garbage collector;
the gate-never-finishes error is unchanged.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import threading
import weakref

import numpy as np
import pytest

from repro import sweep
from repro.analysis import AnalysisService
from repro.configs.paper_workflow import (build_workflow, mc_spec,
                                          sweep_scenarios)
from repro.core import PPoly

from test_fan_in import BENCH, deploy, sampler, time_limit
from test_quadratic_class import RNG, _ramp_scenarios, _random_workflow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FRACS = [0.2, 0.35, 0.5, 0.65, 0.8]


def eager_out(plan, pack):
    """Run the solve's executable again on its device inputs and read every
    output array with ``np.asarray``, as the fetch did before it was lazy:
    ``{process: {"finish", "share", "iterations", "progress"}}``."""
    eng = plan._jax_engine
    B, D = pack.B_batched, pack.shards
    Bp = -(-B // D) * D
    cap = eng._proven_caps[(Bp, D, pack.ramps)]
    out = eng._get_compiled(Bp, D, cap, pack.ramps)(pack._cache[("dev", Bp, D)])

    def host(x):
        a = np.asarray(x)
        if D > 1:
            a = a.reshape((-1,) + a.shape[2:])
        return a[:B]

    return {ps.name: {"finish": host(out[ps.name]["finish"]),
                      "share": host(out[ps.name]["share"]),
                      "iterations": np.asarray(out[ps.name]["iterations"]),
                      "progress": [host(a) for a in out[ps.name]["progress"]]}
            for ps in eng.spec.procs}


def assert_progress_is(rep, want):
    for name, w in want.items():
        p = rep.proc_results[name].progress
        fields = [p.starts, p.c0, p.c1] + ([p.c2] if p.c2 is not None else [])
        assert len(fields) == len(w["progress"])
        for got, exp in zip(fields, w["progress"]):
            assert got.dtype == exp.dtype == np.float64
            np.testing.assert_array_equal(got, exp)


def paper_case():
    plan = build_workflow(0.5).compile()
    return plan, plan.prepare(sweep_scenarios(FRACS))


def ramps_case():
    rng = RNG(2)
    wf = _random_workflow(rng)
    plan = wf.compile()
    pack = plan.prepare(_ramp_scenarios(rng, wf, 6))
    assert pack.ramps
    return plan, pack


def montage_case():
    cfg = json.loads((BENCH / "configs" / "montage_pegasus_3x4.json")
                     .read_text())
    traffic = json.loads((BENCH / "traffic" / "montage_mc10k.json")
                         .read_text())
    dkeys = deploy.data_keys(cfg)
    draws = sampler.draws(traffic["dists"], dkeys, 3, 3_000_000_019)
    plan = deploy.build_workflow(cfg).compile()
    specs = [deploy.program_scenario(
        {k: ("scale", float(v[i])) for k, v in draws.items()}, dkeys)
        for i in range(3)]
    return plan, plan.prepare(specs)


@pytest.mark.parametrize("case", [paper_case, ramps_case, montage_case],
                         ids=["paper", "ramps", "montage"])
def test_lazy_progress_is_the_eager_fetch(case):
    with time_limit(300):
        plan, pack = case()
        plan.sweep(pack, backend="jax")       # compiles, ratchets the cap
        rep = plan.sweep(pack, backend="jax")
        assert set(rep.backends) == {"jax"}
        eng = plan._jax_engine
        assert eng.progress_fetches == 0
        want = eager_out(plan, pack)
        assert_progress_is(rep, want)
        assert eng.progress_fetches == 1
        for name, w in want.items():
            r = rep.proc_results[name]
            np.testing.assert_array_equal(r.finish, w["finish"])
            assert r.iterations == int(w["iterations"].max())


def test_fetch_bytes_are_finish_share_and_one_trip_count_per_level():
    plan, pack = paper_case()
    plan.sweep(pack, backend="jax")           # compiles, ratchets the cap
    eng = plan._jax_engine
    b0 = eng.fetch_bytes
    rep = plan.sweep(pack, backend="jax")
    want = eager_out(plan, pack)
    got = eng.fetch_bytes - b0
    assert got == (sum(w["finish"].nbytes + w["share"].nbytes
                       for w in want.values())
                   + sum(want[lv[0]]["iterations"].nbytes
                         for lv in plan.levels))
    progress = sum(a.nbytes for w in want.values() for a in w["progress"])
    rep.proc_results["task3"].progress
    assert eng.fetch_bytes - b0 == got + progress


def test_query_mc_fetches_no_progress():
    plan = build_workflow(0.5).compile()
    with AnalysisService(plan, backend="jax", max_batch=32) as svc:
        svc.query_mc(mc_spec(), 64, seed=5, timeout=600)
        s0 = svc.snapshot()
        b0 = plan._jax_engine.fetch_bytes
        mc = svc.query_mc(mc_spec(), 64, seed=7, timeout=600)
        s1 = svc.snapshot()
    assert set(mc.report.backends) == {"jax"}
    assert s1["sweeps"] - s0["sweeps"] == 2
    assert s1["engine_progress_fetches"] == 0
    assert plan._jax_engine.progress_fetches == 0
    eng = plan._jax_engine
    per_sweep = (sum(32 * 8 * (1 + len(ps.data_names) + len(ps.res_names))
                     for ps in eng.spec.procs)
                 + len(plan.levels) * np.dtype(np.int32).itemsize)
    delta = s1["engine_fetch_bytes"] - s0["engine_fetch_bytes"]
    assert delta == eng.fetch_bytes - b0 == 2 * per_sweep


def test_curve_queries_read_the_eager_values_with_one_fetch_per_solve():
    plan, pack = paper_case()
    plan.sweep(pack, backend="jax")
    lazy = plan.sweep(pack, backend="jax")
    eager = plan.sweep(pack, backend="jax")
    eng = plan._jax_engine
    for name, w in eager_out(plan, pack).items():
        eager.proc_results[name].progress = sweep.BPL(*w["progress"])
    n0 = eng.progress_fetches
    ts = np.linspace(0.0, float(lazy.makespans.max()), 7)

    def queries(rep):
        return [rep.sample_progress("task3", ts),
                rep.kernel_finish_times("task1"),
                *rep.data_ceiling("task3", ts)]

    got, want = queries(lazy), queries(eager)
    assert eng.progress_fetches == n0 + 1
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    queries(lazy)
    assert eng.progress_fetches == n0 + 1


def test_threads_reading_one_report_fetch_once():
    """16 threads read progress of one report at once, with a short switch
    interval: one fetch, counted once, and every reader gets the kept BPL."""
    plan, pack = paper_case()
    plan.sweep(pack, backend="jax")
    rep = plan.sweep(pack, backend="jax")
    eng = plan._jax_engine
    n0, b0 = eng.progress_fetches, eng.fetch_bytes
    names = [rep.order[i % len(rep.order)] for i in range(16)]
    barrier = threading.Barrier(len(names))
    got: list = [None] * len(names)

    def read(i):
        barrier.wait(timeout=60)
        got[i] = rep.proc_results[names[i]].progress

    threads = [threading.Thread(target=read, args=(i,))
               for i in range(len(names))]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert eng.progress_fetches == n0 + 1
    want = eager_out(plan, pack)
    assert eng.fetch_bytes - b0 == sum(a.nbytes for w in want.values()
                                       for a in w["progress"])
    assert_progress_is(rep, want)
    for name, p in zip(names, got):
        assert p is rep.proc_results[name].progress


def test_dropped_report_frees_its_progress_without_the_cyclic_gc():
    """No reference cycle holds a solve's results (the lazy ceilings capture
    upstream results, not the whole mapping), so the device buffers go when
    the last report does, not at the next full collection."""
    plan, pack = paper_case()
    plan.sweep(pack, backend="jax")
    enabled = gc.isenabled()
    gc.disable()
    try:
        rep = plan.sweep(pack, backend="jax")
        holder = weakref.ref(
            rep.proc_results["task3"].__dict__["_progress"].func.__self__)
        assert holder() is not None
        del rep
        assert holder() is None
    finally:
        if enabled:
            gc.enable()


def test_gate_never_finishes_error_is_unchanged():
    plan = build_workflow(0.5).compile()
    scs = [sweep.Scenario(label=f"s{i}", resource_inputs={
        ("task1", "cpu"): PPoly.constant(r)})
        for i, r in enumerate([1.0, 0.0, 2.0])]
    msg = r"^gate 'task1' of 'task3' never finishes \(scenario 1\)$"
    for backend in ("numpy", "jax"):
        with pytest.raises(ValueError, match=msg):
            plan.sweep(plan.prepare(scs), backend=backend)


def test_sharded_lazy_progress_is_the_eager_fetch_subprocess():
    """B=6 over 4 forced CPU devices: the lazy fetch un-shards and drops
    the padding rows as the eager reads do."""
    code = """
import jax
assert jax.local_device_count() == 4, jax.local_device_count()
from repro.configs.paper_workflow import build_workflow, sweep_scenarios
from test_lazy_fetch import FRACS, assert_progress_is, eager_out
plan = build_workflow(0.5).compile()
pack = plan.prepare(sweep_scenarios(FRACS + [0.9])).shard(4)
plan.sweep(pack, backend="jax")
rep = plan.sweep(pack, backend="jax")
assert rep.B == 6 and set(rep.backends) == {"jax"}
assert_progress_is(rep, eager_out(plan, pack))
assert plan._jax_engine.progress_fetches == 1
print("LAZY-SHARD-OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"),
                                           os.path.join(ROOT, "tests")]))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LAZY-SHARD-OK" in out.stdout

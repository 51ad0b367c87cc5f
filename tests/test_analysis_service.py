"""Analysis-as-a-service (ISSUE 6): coalescing, plan cache, online loop.

Contracts under test:

* >= 16 concurrent what-if requests queued on a paused service coalesce
  into ONE fused sweep, and every client's rows are identical to a
  sequential ``plan.sweep`` of just its scenarios,
* the plan cache returns the SAME plan for identical workflows, and plans
  of structurally identical workflows (same level signature, different
  base inputs) share one fused engine — one XLA trace,
* ``OnlineReanalysis.ingest`` (override-driven re-analysis) matches a
  fresh ``plan.prepare`` of the edited scenario list, including
  monitoring-shaped deltas (measured-progress ``PPoly``, 0-d numpy
  scalars),
* a poisoned query fails only its own future — batch neighbors are
  re-run solo and still succeed,
* concurrent load smoke: many client threads, correct results, no
  deadlock (this is the tier-1 service load test).
"""

from __future__ import annotations

import threading
import time
import warnings

import numpy as np
import pytest

from repro.analysis import (AnalysisService, OnlineReanalysis, scenarios)
from repro.analysis.serve import workflow_fingerprint
from repro.configs.paper_workflow import build_workflow, sweep_scenarios
from repro.core import DataDep, PPoly, Process, ResourceDep, Workflow


@pytest.fixture(scope="module")
def plan():
    return build_workflow(0.5).compile()


def _small_workflow(link_rate: float = 10.0) -> Workflow:
    n = 1000.0
    wf = Workflow()
    wf.add(Process("dl", data={"file": DataDep.stream(n, n)},
                   resources={"link": ResourceDep.stream(n, n)},
                   total_progress=n).identity_output(),
           resources={"link": PPoly.constant(link_rate)})
    wf.set_data_input("dl", "file", PPoly.constant(n))
    return wf


# ------------------------------------------------------------- coalescing --
def test_coalesces_16_requests_into_one_fused_sweep(plan):
    scs = sweep_scenarios(np.linspace(0.1, 0.9, 18))
    svc = AnalysisService(autostart=False)
    futs = [svc.submit([sc], plan=plan) for sc in scs]
    svc.start()
    reps = [f.result(timeout=600) for f in futs]
    svc.close()
    snap = svc.snapshot()
    assert snap["sweeps"] == 1, snap
    assert snap["coalesced_batches"] == 1
    assert snap["max_coalesced"] == 18 >= 16
    assert snap["max_batch_B"] == 18
    # per-request parity vs sequential plan.sweep of ONLY that scenario
    for sc, rep in zip(scs, reps):
        seq = plan.sweep(plan.prepare([sc]))
        assert rep.B == 1
        assert rep.labels == seq.labels
        np.testing.assert_array_equal(rep.makespans, seq.makespans)
        for n in rep.order:
            np.testing.assert_array_equal(rep.finish[n], seq.finish[n])
        assert rep.factors == seq.factors
        np.testing.assert_array_equal(rep.share_seconds, seq.share_seconds)


def test_multi_scenario_requests_slice_correctly(plan):
    reqs = [sweep_scenarios([0.2, 0.4]), sweep_scenarios([0.6]),
            sweep_scenarios([0.7, 0.8, 0.9])]
    svc = AnalysisService(autostart=False)
    futs = [svc.submit(scs, plan=plan) for scs in reqs]
    svc.start()
    reps = [f.result(timeout=600) for f in futs]
    svc.close()
    assert svc.snapshot()["sweeps"] == 1
    assert [r.B for r in reps] == [2, 1, 3]
    ref = plan.sweep(plan.prepare([sc for scs in reqs for sc in scs]))
    lo = 0
    for rep in reps:
        np.testing.assert_array_equal(rep.makespans,
                                      ref.makespans[lo:lo + rep.B])
        assert rep.labels == ref.labels[lo:lo + rep.B]
        lo += rep.B


def test_poisoned_request_fails_alone(plan):
    good = sweep_scenarios([0.4])
    bad = [scenarios.ScenarioSpec(label="ghost",
                                  resources={("ghost", "cpu"): 2.0})]
    svc = AnalysisService(autostart=False)
    f_good = svc.submit(good, plan=plan)
    f_bad = svc.submit(bad, plan=plan)
    svc.start()
    rep = f_good.result(timeout=600)
    with pytest.raises(ValueError):
        f_bad.result(timeout=600)
    svc.close()
    np.testing.assert_array_equal(
        rep.makespans, plan.sweep(plan.prepare(good)).makespans)
    assert svc.snapshot()["solo_retries"] == 2


@pytest.mark.parametrize("kind", ["submit", "submit_pack"])
def test_queue_wait_counts_time_before_each_sweep(plan, kind):
    """``queue_wait_s`` sums, over requests, submit -> start of the
    request's own sweep; ``queue_waits`` counts the requests."""
    svc = AnalysisService(autostart=False)
    for x in (0.3, 0.6):
        if kind == "submit":
            fut = svc.submit(sweep_scenarios([x]), plan=plan)
        else:
            fut = svc.submit_pack(plan.prepare(sweep_scenarios([x])))
    time.sleep(0.2)
    svc.start()
    fut.result(timeout=600)
    svc.close()
    snap = svc.snapshot()
    assert snap["queue_wait_s"] >= 0.4
    assert snap["queue_waits"] == 2


# -------------------------------------------------------------- plan cache --
def test_plan_cache_hit_on_identical_workflows():
    svc = AnalysisService(autostart=False)
    p1 = svc.compile(build_workflow(0.5))
    p2 = svc.compile(build_workflow(0.5))
    assert p1 is p2
    snap = svc.snapshot()
    assert snap["plan_hits"] == 1 and snap["plan_misses"] == 1
    assert workflow_fingerprint(build_workflow(0.5)) == \
        workflow_fingerprint(build_workflow(0.5))
    assert workflow_fingerprint(build_workflow(0.5)) != \
        workflow_fingerprint(build_workflow(0.7))
    svc.close()


def test_structurally_identical_plans_share_one_trace():
    """Different base inputs, same level signature -> ONE engine, and the
    second plan's sweep reuses the first's compiled executable."""
    svc = AnalysisService(autostart=False)
    p1 = svc.compile(build_workflow(0.5))
    p3 = svc.compile(build_workflow(0.7))
    svc.start()
    assert p3 is not p1
    assert p1.level_signature == p3.level_signature
    assert p3._jax_engine is p1._jax_engine
    assert svc.snapshot()["trace_hits"] == 1
    # warm the (B=1) shape twice: the first solve compiles at the default
    # iteration cap, the second pays the engine's one-time proven-cap
    # down-ratchet recompile — after that the jit cache is stable
    svc.query(sweep_scenarios([0.3]), plan=p1, timeout=600)
    svc.query(sweep_scenarios([0.4]), plan=p1, timeout=600)
    compiled = dict(p1._jax_engine._compiled)
    assert compiled, "warm sweeps should have populated the jit cache"
    r = svc.query(sweep_scenarios([0.3]), plan=p3, timeout=600)
    assert dict(p3._jax_engine._compiled) == compiled, \
        "structurally identical plan recompiled instead of sharing the trace"
    svc.close()
    # and the shared trace still computes p3's own answer
    np.testing.assert_array_equal(
        r.makespans, p3.sweep(p3.prepare(sweep_scenarios([0.3]))).makespans)


def test_level_signature_differs_for_different_structure():
    p_small = _small_workflow().compile()
    p_paper = build_workflow(0.5).compile()
    assert p_small.level_signature != p_paper.level_signature


# -------------------------------------------------------- online re-analysis --
def test_online_reanalysis_matches_fresh_prepare(plan):
    base = sweep_scenarios([0.3, 0.6, 0.9])
    live = OnlineReanalysis(plan, base, backend="numpy")
    r = live.ingest({"dl1.link": 0.7, ("task1", "cpu"): 1.5})
    edited = []
    for spec in sweep_scenarios([0.3, 0.6, 0.9]):
        sc = spec.resolve(plan.workflow)
        sc.resource_inputs[("dl1", "link")] = plan.base_res[("dl1", "link")] * 0.7
        sc.resource_inputs[("task1", "cpu")] = plan.base_res[("task1", "cpu")] * 1.5
        edited.append(sc)
    ref = plan.sweep(plan.prepare(edited), backend="numpy")
    np.testing.assert_array_equal(r.makespans, ref.makespans)
    np.testing.assert_array_equal(r.share_seconds, ref.share_seconds)
    assert live.updates == 1
    # second delta re-packs from the SAME pack, still against base inputs
    r2 = live.ingest({"dl1.link": 0.7})
    assert live.updates == 2
    edited2 = []
    for spec in sweep_scenarios([0.3, 0.6, 0.9]):
        sc = spec.resolve(plan.workflow)
        sc.resource_inputs[("dl1", "link")] = plan.base_res[("dl1", "link")] * 0.7
        sc.resource_inputs[("task1", "cpu")] = plan.base_res[("task1", "cpu")] * 1.5
        edited2.append(sc)
    np.testing.assert_array_equal(
        r2.makespans, plan.sweep(plan.prepare(edited2), backend="numpy").makespans)


def test_online_reanalysis_ingests_monitoring_shapes(plan):
    """The ingestion path the ISSUE motivates: a measured-progress PPoly
    (pw-linear, ProgressMonitor-shaped) and a 0-d numpy scalar rate."""
    from repro.runtime.monitor import ProgressMonitor

    mon = ProgressMonitor()
    assert mon.record_step(0) is None  # auto-start (no start() call)
    mon.record_step(1)
    mon.record_step(2)
    measured = mon.measured_progress()
    assert measured.is_piecewise_linear

    live = OnlineReanalysis(plan, sweep_scenarios([0.5]), backend="numpy")
    # measured input-rate delta as a 0-d numpy scalar (np.isscalar is False!)
    r_nd = live.ingest({"dl1.link": np.array(0.7)})
    ref = OnlineReanalysis(plan, sweep_scenarios([0.5]), backend="numpy") \
        .ingest({"dl1.link": 0.7})
    np.testing.assert_array_equal(r_nd.makespans, ref.makespans)
    # a measured progress function as a replacement data input stays in-class
    scaled = PPoly(measured.starts,
                   measured.coeffs * plan.base_data[("dl1", "remote")](1e9))
    r_fn = live.ingest({"dl1.remote": scaled})
    assert np.isfinite(r_fn.makespans).all()


def test_service_track_runs_on_worker(plan):
    with AnalysisService() as svc:
        live = svc.track(sweep_scenarios([0.5]), plan=plan)
        r0 = live.refresh()
        r1 = live.ingest({"dl1.link": np.float64(0.5)})
        assert float(r1.makespans[0]) > float(r0.makespans[0])
        assert svc.snapshot()["sweeps"] >= 2


# ------------------------------------------------------------- load smoke --
def test_concurrent_load_smoke():
    """Tier-1 service load test: 24 client threads hammer one service; all
    futures resolve with correct makespans and the queue drains clean."""
    plan = _small_workflow().compile()
    rates = [2.0, 4.0, 5.0, 8.0, 10.0, 40.0]
    expect = {r: 1000.0 / r for r in rates}
    n_threads, per_thread = 24, 3
    results: dict[tuple[int, int], tuple[float, float]] = {}
    errors: list[BaseException] = []
    barrier = threading.Barrier(n_threads)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with AnalysisService(plan) as svc:
            def client(ci: int) -> None:
                try:
                    barrier.wait(timeout=120)
                    for qi in range(per_thread):
                        rate = rates[(ci + qi) % len(rates)]
                        sc = scenarios.override(
                            {"dl.link": PPoly.constant(rate)},
                            label=f"c{ci}q{qi}")
                        rep = svc.query([sc], timeout=600)
                        results[(ci, qi)] = (rate, float(rep.makespans[0]))
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            snap = svc.snapshot()
    assert not errors, errors[:3]
    assert len(results) == n_threads * per_thread
    for (rate, ms) in results.values():
        assert ms == pytest.approx(expect[rate], rel=1e-9)
    assert snap["requests"] == n_threads * per_thread
    assert snap["sweeps"] <= snap["requests"]


def test_submit_validation(plan):
    svc = AnalysisService(autostart=False, max_batch=4)
    with pytest.raises(ValueError, match="at least one"):
        svc.submit([], plan=plan)
    with pytest.raises(ValueError, match="max_batch"):
        svc.submit(sweep_scenarios(np.linspace(0.1, 0.9, 5)), plan=plan)
    with pytest.raises(ValueError, match="no plan"):
        svc.submit(sweep_scenarios([0.5]))
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(sweep_scenarios([0.5]), plan=plan)


def test_service_with_default_workflow_and_context_manager():
    with AnalysisService(_small_workflow()) as svc:
        rep = svc.query([scenarios.override(
            {"dl.link": PPoly.constant(20.0)}, label="2x")], timeout=600)
        assert float(rep.makespans[0]) == pytest.approx(50.0, rel=1e-9)
        assert rep.labels == ["2x"]

"""Chaos suite (ISSUE 8): every FaultPlan mode must end in a typed error
or a numpy-degraded Report — never a stranded future.

Contracts under test, one per FaultPlan hook plus the service-level
guarantees they exercise:

* **kill-worker**: in-flight futures fail with ``ServiceCrashed`` carrying
  the injected cause, the supervisor restarts (``stats.restarts == 1``),
  and a resubmit round-trips bit-identically to a fresh service,
* **fail-Nth-sweep**: a transient engine error is absorbed by the seeded
  exponential-backoff retry and the client still gets the exact answer,
* **NaN injection**: poisoned rows are re-run on the numpy reference twin
  (``backends == "degraded"``), row-parity-checked against a clean numpy
  run, with ONE aggregated warning — including through ``shard(n)`` packs,
* **delay past deadline**: expired requests fail ``DeadlineExceeded``
  BEFORE being packed (a fresh neighbor still succeeds),
* **malformed override**: fails alone with the client-input error type;
  batch neighbors survive,
* **backpressure**: the queue bound sheds the newest request with
  ``Overloaded``,
* **close/crash races**: ``close(drain=False)`` with queued ``submit_mc``
  chunks resolves the aggregate future (the PR-7 close-race), and a
  worker crash mid-MC fails it typed.

Every ``result()`` call is bounded — a stranded future fails the test by
timeout, not by hanging CI.
"""

from __future__ import annotations

import warnings
from concurrent.futures import CancelledError

import numpy as np
import pytest

from repro.analysis import (AnalysisService, DeadlineExceeded, FaultPlan,
                            Overloaded, ServiceClosed, ServiceCrashed)
from repro.analysis.faults import FaultInjected
from repro.configs.paper_workflow import build_workflow, sweep_scenarios

T = 120  # per-future timeout: generous for CI, fatal for a stranded future


@pytest.fixture(scope="module")
def plan():
    return build_workflow(0.5).compile()


@pytest.fixture(scope="module")
def ref(plan):
    """Clean numpy-reference answer for the standard scenario set."""
    return plan.sweep(plan.prepare(_scenarios()), backend="numpy")


def _scenarios():
    return sweep_scenarios([0.3, 0.5, 0.7, 0.9])


# ------------------------------------------------------------ supervision --
def test_kill_worker_fails_typed_and_recovers(plan, ref):
    svc = AnalysisService(autostart=False, faults=FaultPlan(kill_worker_at=1))
    doomed = svc.submit(_scenarios(), plan=plan)
    svc.start()
    with pytest.raises(ServiceCrashed) as exc:
        doomed.result(timeout=T)
    assert isinstance(exc.value.cause, FaultInjected)
    # the supervisor restarted the worker: the NEXT submit round-trips
    rep = svc.submit(_scenarios(), plan=plan).result(timeout=T)
    snap = svc.snapshot()
    svc.close()
    assert snap["restarts"] == 1, snap
    np.testing.assert_array_equal(rep.makespans, ref.makespans)
    fresh = AnalysisService(autostart=True)
    try:
        clean = fresh.submit(_scenarios(), plan=plan).result(timeout=T)
    finally:
        fresh.close()
    np.testing.assert_array_equal(rep.makespans, clean.makespans)
    for n in rep.order:
        np.testing.assert_array_equal(rep.finish[n], clean.finish[n])


def test_worker_crash_fails_every_inflight_request(plan):
    svc = AnalysisService(autostart=False, faults=FaultPlan(kill_worker_at=1))
    futs = [svc.submit([sc], plan=plan) for sc in _scenarios()]
    svc.start()
    for f in futs:
        with pytest.raises(ServiceCrashed):
            f.result(timeout=T)
    svc.close()
    assert svc.snapshot()["restarts"] == 1


# ----------------------------------------------------------------- retries --
def test_transient_sweep_failure_retried_to_success(plan, ref):
    svc = AnalysisService(faults=FaultPlan(fail_sweep=1),
                          retry_backoff_s=1e-4)
    try:
        rep = svc.submit(_scenarios(), plan=plan).result(timeout=T)
        snap = svc.snapshot()
    finally:
        svc.close()
    assert snap["retries"] >= 1, snap
    np.testing.assert_array_equal(rep.makespans, ref.makespans)


def test_malformed_override_fails_alone(plan, ref):
    svc = AnalysisService(autostart=False, retry_backoff_s=1e-4,
                          faults=FaultPlan(malformed_request=1))
    poisoned = svc.submit(_scenarios(), plan=plan)
    neighbor = svc.submit(_scenarios(), plan=plan)
    svc.start()
    # the injected malformed override is a CLIENT error: original type,
    # not a ServiceError — and only the poisoned future sees it
    with pytest.raises(ValueError):
        poisoned.result(timeout=T)
    rep = neighbor.result(timeout=T)
    svc.close()
    np.testing.assert_array_equal(rep.makespans, ref.makespans)


# ------------------------------------------------------------- degradation --
def test_nan_rows_degrade_to_numpy_with_parity(plan, ref):
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        svc = AnalysisService(faults=FaultPlan(nan_rows=(1, 3),
                                               nan_sweep=None))
        try:
            rep = svc.submit(_scenarios(), plan=plan).result(timeout=T)
            snap = svc.snapshot()
        finally:
            svc.close()
    assert rep.backends == ["jax", "degraded", "jax", "degraded"]
    assert rep.degraded_indices == [1, 3]
    # row parity vs the clean numpy reference: the degraded rows carry the
    # reference answer, the healthy rows the (equal) fused answer
    np.testing.assert_allclose(rep.makespans, ref.makespans, rtol=1e-9)
    for n in rep.order:
        np.testing.assert_allclose(rep.finish[n], ref.finish[n], rtol=1e-9)
    assert snap["degraded"] == 2, snap
    assert snap["top_degrade_reasons"], snap
    degrade_warns = [w for w in caught
                     if "degraded to the numpy reference engine"
                     in str(w.message)]
    assert len(degrade_warns) == 1  # ONE aggregated warning, not per-row


def test_degradation_composes_with_sharded_packs(plan, ref):
    import jax

    pack = plan.prepare(_scenarios()).shard(jax.local_device_count())
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        svc = AnalysisService(faults=FaultPlan(nan_rows=(0, 2),
                                               nan_sweep=None))
        try:
            rep = svc.submit_pack(pack).result(timeout=T)
        finally:
            svc.close()
    assert rep.degraded_indices == [0, 2]
    np.testing.assert_allclose(rep.makespans, ref.makespans, rtol=1e-9)


def test_degraded_rows_survive_coalescing(plan, ref):
    """Poisoned rows inside a coalesced batch degrade without disturbing
    the per-client row slicing."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        svc = AnalysisService(autostart=False,
                              faults=FaultPlan(nan_rows=(0, 5),
                                               nan_sweep=None))
        futs = [svc.submit([sc], plan=plan) for sc in _scenarios()]
        svc.start()
        try:
            reps = [f.result(timeout=T) for f in futs]
            snap = svc.snapshot()
        finally:
            svc.close()
    assert snap["sweeps"] == 1, snap  # still ONE fused sweep
    assert snap["degraded"] == 1, snap  # row 5 lies past the 4-row batch
    for i, rep in enumerate(reps):
        assert rep.B == 1
        np.testing.assert_allclose(rep.makespans, ref.makespans[i:i + 1],
                                   rtol=1e-9)
    assert reps[0].backends == ["degraded"]  # row 0 was poisoned
    assert reps[1].backends == ["jax"]


def test_pack_subset_matches_full_numpy_rows(plan):
    pack = plan.prepare(_scenarios())
    full = plan.sweep(pack, backend="numpy")
    sub = plan.sweep(pack.subset([2, 0]), backend="numpy")
    np.testing.assert_array_equal(sub.makespans, full.makespans[[2, 0]])
    assert sub.labels == [full.labels[2], full.labels[0]]
    for n in full.order:
        np.testing.assert_array_equal(sub.finish[n], full.finish[n][[2, 0]])


# -------------------------------------------------- deadlines/backpressure --
def test_delay_past_deadline_fails_before_packing(plan, ref):
    svc = AnalysisService(autostart=False, faults=FaultPlan(delay_s=0.25))
    doomed = svc.submit(_scenarios(), plan=plan, deadline_s=0.02)
    patient = svc.submit(_scenarios(), plan=plan)
    svc.start()
    with pytest.raises(DeadlineExceeded):
        doomed.result(timeout=T)
    rep = patient.result(timeout=T)
    snap = svc.snapshot()
    svc.close()
    assert snap["deadline_expired"] == 1, snap
    np.testing.assert_array_equal(rep.makespans, ref.makespans)


def test_overload_sheds_newest_request(plan):
    svc = AnalysisService(autostart=False, max_pending=2)
    kept = [svc.submit(_scenarios(), plan=plan) for _ in range(2)]
    with pytest.raises(Overloaded):
        svc.submit(_scenarios(), plan=plan)
    assert svc.snapshot()["shed"] == 1
    svc.start()
    for f in kept:  # admitted requests still serve normally
        assert f.result(timeout=T).B == len(_scenarios())
    svc.close()


# ------------------------------------------------------- close/crash races --
def test_submit_mc_close_race_resolves_aggregate(plan):
    """The PR-7 close-race: close(drain=False) cancels queued MC chunks —
    the aggregate future must resolve typed, not strand."""
    from repro.analysis import dist

    svc = AnalysisService(autostart=False, max_batch=64)
    spec = {"task1.cpu": dist.lognormal(sigma=0.2)}
    agg = svc.submit_mc(spec, n=256, plan=plan)  # 4 queued chunks
    svc.close(drain=False)
    with pytest.raises(ServiceCrashed, match="cancelled"):
        agg.result(timeout=T)


def test_submit_mc_worker_crash_fails_aggregate(plan):
    from repro.analysis import dist

    svc = AnalysisService(autostart=False, max_batch=64,
                          faults=FaultPlan(kill_worker_at=1))
    agg = svc.submit_mc({"task1.cpu": dist.uniform(0.8, 1.2)}, n=256,
                        plan=plan)
    svc.start()
    with pytest.raises(ServiceCrashed):
        agg.result(timeout=T)
    svc.close()


def test_close_never_strands_unstarted_queue(plan):
    svc = AnalysisService(autostart=False)
    fut = svc.submit(_scenarios(), plan=plan)
    svc.close()
    with pytest.raises(CancelledError):
        fut.result(timeout=T)
    with pytest.raises(ServiceClosed):
        svc.submit(_scenarios(), plan=plan)
    with pytest.raises(ServiceClosed):
        svc.start()


def test_snapshot_reports_fault_census(plan):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        svc = AnalysisService(max_pending=None,
                              faults=FaultPlan(nan_rows=(0,), nan_sweep=1))
        try:
            svc.submit(_scenarios(), plan=plan).result(timeout=T)
            snap = svc.snapshot()
        finally:
            svc.close()
    assert snap["degraded"] == 1
    (reason, count), = snap["top_degrade_reasons"]
    assert count == 1 and "NaN" in reason
    for key in ("restarts", "retries", "shed", "deadline_expired",
                "latency_p50_s", "latency_p99_s"):
        assert key in snap

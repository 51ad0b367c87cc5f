"""Compile-once Analysis API: the new front door vs the legacy paths.

Contracts under test:

* ``CompiledWorkflow.solve()`` == ``Workflow.analyze()`` exactly,
* ``CompiledWorkflow.sweep()`` == the scalar loop, to float tolerance,
* repeated sweeps on one plan are deterministic (the plan caches are pure),
* the unified ``Report`` accessors behave the same across scalar/batched,
* ``whatif``/``gain``/``gains`` agree with the legacy ``core.bottleneck``
  helpers,
* ``bottleneck_fn`` tiles ``[0, makespan]`` with the critical path,
* the scenario DSL resolves factors against the base workflow,
* mixed-class sweeps route per scenario and warn once.
"""

import warnings

import numpy as np
import pytest

from repro import sweep
from repro.analysis import CompiledWorkflow, Report, scenarios
from repro.configs.paper_workflow import build_workflow, sweep_scenarios
from repro.core import (DataDep, PPoly, Process, ResourceDep, Workflow,
                        potential_gains, whatif_scale_resource)


@pytest.fixture(scope="module")
def plan() -> CompiledWorkflow:
    return build_workflow(0.5).compile()


# ---------------------------------------------------------------- solve ----
def test_solve_matches_legacy_analyze(plan):
    rep = plan.solve()
    legacy = build_workflow(0.5).analyze()
    assert isinstance(rep, Report) and rep.is_scalar
    assert rep.makespan == pytest.approx(legacy.makespan, rel=1e-12)
    for name in legacy.order:
        assert rep.finish(name) == pytest.approx(
            legacy.results[name].finish_time, rel=1e-12)
    # scalar timeline == legacy bottleneck timeline
    assert rep.timeline() == legacy.bottleneck_timeline()
    assert rep.backend == "scalar"
    # solve() is cached: same object back
    assert plan.solve() is rep


def test_scalar_report_accessors(plan):
    rep = plan.solve()
    assert isinstance(rep.makespan, float)
    assert isinstance(rep.finish("task3"), float)
    # mapping access stays available next to the call form
    assert rep.finish["task3"].shape == (1,)
    (idx, label, ms), = rep.top_k(1)
    assert (idx, label) == (0, "base") and ms == rep.makespan
    rows = rep.shares()
    assert rows and rows[0].seconds >= rows[-1].seconds


# ---------------------------------------------------------------- sweep ----
def test_sweep_matches_legacy_and_loop(plan):
    scs = sweep_scenarios(np.linspace(0.1, 0.9, 9))
    rb = plan.sweep(scs, backend="batched")
    rl = plan.sweep(scs, backend="loop")
    np.testing.assert_allclose(rb.makespan, rl.makespan, rtol=1e-9)
    assert rb.backends == ["batched"] * 9
    assert rl.backends == ["loop"] * 9 and rl.backend == "loop"
    for n in rb.order:
        np.testing.assert_allclose(rb.finish[n], rl.finish[n], rtol=1e-9)


def test_repeated_sweeps_are_deterministic(plan):
    scs = sweep_scenarios([0.3, 0.6, 0.9])
    a = plan.sweep(scs, backend="batched")
    b = plan.sweep(scs, backend="batched")
    np.testing.assert_array_equal(a.makespan, b.makespan)
    np.testing.assert_array_equal(a.share_seconds, b.share_seconds)


def test_sweep_timeline_drills_into_scalar(plan):
    scs = sweep_scenarios([0.5, 0.95])
    rb = plan.sweep(scs, backend="batched")
    tl = rb.timeline(0)
    legacy = build_workflow(0.5).analyze().bottleneck_timeline()
    assert len(tl) == len(legacy)
    for got, exp in zip(tl, legacy):
        assert got[2:] == exp[2:]
        assert got[0] == pytest.approx(exp[0], abs=1e-9)
        assert got[1] == pytest.approx(exp[1], rel=1e-9)
    # default timeline() is the best scenario
    assert rb.timeline() == rb.timeline(rb.best())


# ------------------------------------------------------------ what-ifs ----
def test_whatif_matches_legacy_scale(plan):
    legacy = whatif_scale_resource(build_workflow(0.5), "task1", "cpu", 2.0)
    rep = plan.whatif(**{"task1.cpu": 2.0})
    assert rep.makespan == pytest.approx(legacy.makespan, rel=1e-12)
    # explicit PPoly replacement takes the same path
    rep2 = plan.whatif({"task1.cpu": PPoly.constant(2.0)})
    assert rep2.makespan == pytest.approx(legacy.makespan, rel=1e-12)


def test_whatif_unknown_input_actionable(plan):
    with pytest.raises(ValueError, match=r"unknown process 'ghost'"):
        plan.whatif(**{"ghost.cpu": 2.0})
    with pytest.raises(ValueError, match=r"'task1' has no input 'gpu'"):
        plan.whatif(**{"task1.gpu": 2.0})
    with pytest.raises(ValueError, match=r"produced by 'dl1'"):
        plan.whatif(**{"task1.video": 2.0})


def test_gain_and_gains_match_potential_gains(plan):
    base = build_workflow(0.5)
    legacy = potential_gains(base, factor=2.0)
    got = plan.gains(factor=2.0)
    assert [(p, r) for p, r, *_ in got] == [(p, r) for p, r, *_ in legacy]
    for (gp, gr, gm, gg), (lp, lr, lm, lg) in zip(got, legacy):
        assert gm == pytest.approx(lm, rel=1e-12)
        assert gg == pytest.approx(lg, rel=1e-12)
    top = legacy[0]
    assert plan.gain((top[0], top[1])) == pytest.approx(top[3], rel=1e-12)


def test_gain_accepts_bottleneck_objects(plan):
    bfn = plan.bottleneck_fn()
    dom = bfn.dominant()
    g = plan.gain(dom)
    assert np.isfinite(g)
    # relaxing an edge-fed data bottleneck speeds up the producer
    data_iv = next(iv for iv in bfn if iv.kind == "data")
    assert data_iv.source == "dl1"
    assert plan.gain(data_iv) > 0.0


# ------------------------------------------------------ bottleneck_fn ----
def test_bottleneck_fn_tiles_runtime(plan):
    bfn = plan.bottleneck_fn()
    assert bfn.makespan == pytest.approx(plan.solve().makespan)
    ivs = bfn.intervals
    assert ivs[0].t_start == pytest.approx(0.0)
    assert ivs[-1].t_end == pytest.approx(bfn.makespan)
    for a, b in zip(ivs, ivs[1:]):
        assert b.t_start == pytest.approx(a.t_end, abs=1e-9)
    # the paper workflow at 50 %: download-fed data limits task1 first, then
    # task1's cpu, then task3's cpu finishes the makespan
    assert [(iv.process, iv.kind, iv.name) for iv in ivs] == [
        ("task1", "data", "video"), ("task1", "resource", "cpu"),
        ("task3", "resource", "cpu")]
    mid = ivs[1]
    assert bfn(0.5 * (mid.t_start + mid.t_end)) == mid
    assert bfn(bfn.makespan + 1.0) is None


# ---------------------------------------------------------- DSL ----------
def test_scenarios_scale_resource_resolves_base(plan):
    scs = scenarios.scale_resource("task1", "cpu", [0.5, 1.0, 2.0])
    rep = plan.sweep(scs)
    assert rep.labels == ["task1.cpux0.5", "task1.cpux1", "task1.cpux2"]
    legacy = [whatif_scale_resource(build_workflow(0.5), "task1", "cpu", f).makespan
              for f in (0.5, 1.0, 2.0)]
    np.testing.assert_allclose(rep.makespan, legacy, rtol=1e-9)


def test_scenarios_grid_cartesian(plan):
    scs = scenarios.grid({"task1.cpu": [1.0, 2.0],
                          "dl1.link": [0.5, 1.0, 2.0]})
    assert len(scs) == 6
    rep = plan.sweep(scs)
    assert rep.B == 6
    # the all-ones cell reproduces the base makespan
    i = rep.labels.index("task1.cpu=1,dl1.link=1")
    assert rep.makespan[i] == pytest.approx(plan.solve().makespan, rel=1e-9)


def test_scenarios_override_strings_and_tuples():
    a = scenarios.override({"dl1.link": 2.0, ("task1", "cpu"): 3.0}, label="x")
    assert a.label == "x"
    assert set(a.resources) == {("dl1", "link"), ("task1", "cpu")}
    with pytest.raises(ValueError, match="one dot"):
        scenarios.override({"dl1": 2.0})


def test_speed_up_data_semantics():
    fn = PPoly.linear(0.0, 10.0)  # 10 B/s arrival
    fast = scenarios.speed_up_data(fn, 2.0)
    ts = np.linspace(0.0, 50.0, 11)
    np.testing.assert_allclose(fast(ts), fn(2.0 * ts))


# ------------------------------------------- per-scenario backend routing ----
def _ramp_workflow():
    n = 1000.0
    wf = Workflow()
    wf.add(Process("dl", data={"file": DataDep.stream(n, n)},
                   resources={"link": ResourceDep.stream(n, n)},
                   total_progress=n).identity_output(),
           resources={"link": PPoly.constant(10.0)})
    wf.set_data_input("dl", "file", PPoly.constant(n))
    return wf


def test_mixed_sweep_routes_per_scenario_and_warns_once():
    wf = _ramp_workflow()
    quad = PPoly(np.array([0.0]), [np.array([5.0, 0.1, 0.01])])  # degree 2
    scs = [sweep.Scenario(label="fast", resource_inputs={("dl", "link"): PPoly.constant(20.0)}),
           sweep.Scenario(label="quad", resource_inputs={("dl", "link"): quad}),
           sweep.Scenario(label="slow", resource_inputs={("dl", "link"): PPoly.constant(5.0)})]
    plan = wf.compile()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        rep = plan.sweep(scs, backend="auto")
    assert rep.backends == ["batched", "loop", "batched"]
    assert rep.backend == "mixed"
    summary = [w for w in caught if "fell back to the scalar loop" in str(w.message)]
    assert len(summary) == 1 and "1/3" in str(summary[0].message)
    # mixed results agree with the all-loop reference
    ref = plan.sweep(scs, backend="loop")
    np.testing.assert_allclose(rep.makespan, ref.makespan, rtol=1e-9)
    for n in rep.order:
        np.testing.assert_allclose(rep.finish[n], ref.finish[n], rtol=1e-9)
    bmap = {k: j for j, k in enumerate(rep.factors)}
    lmap = {k: j for j, k in enumerate(ref.factors)}
    for k in set(bmap) | set(lmap):
        sb = rep.share_seconds[:, bmap[k]] if k in bmap else np.zeros(3)
        sl = ref.share_seconds[:, lmap[k]] if k in lmap else np.zeros(3)
        np.testing.assert_allclose(sb, sl, rtol=1e-6, atol=1e-9)
    # curve queries need the full batch on the fast path
    with pytest.raises(ValueError, match="fully-batched"):
        rep.sample_progress("dl", np.linspace(0, 10, 4))


def test_explicit_batched_raises_for_mixed():
    wf = _ramp_workflow()
    quad = PPoly(np.array([0.0]), [np.array([5.0, 0.1, 0.01])])
    scs = [sweep.Scenario(resource_inputs={("dl", "link"): quad})]
    with pytest.raises(sweep.UnsupportedScenario, match="piecewise-linear"):
        wf.compile().sweep(scs, backend="batched")


def test_plan_snapshot_is_immune_to_later_mutation():
    wf = _ramp_workflow()
    plan = wf.compile()
    before = plan.solve().makespan
    wf.resource_alloc["dl"]["link"] = PPoly.constant(1e-3)  # mutate original
    assert plan.solve().makespan == pytest.approx(before)
    assert wf.compile().solve().makespan > before  # fresh compile sees it

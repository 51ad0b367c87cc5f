"""Differentiable makespan + ``plan.optimize`` acceptance tests.

The contract of the API redesign PR:

* gradients of the fused sweep agree with CENTRAL finite differences to
  rtol 1e-4 on the paper workflow, a ramped (quadratic-path) variant and a
  wide fan-in DAG — including on both sides of an event-order change;
* ``plan.optimize`` recovers the Fig. 7 grid optimum (same argmax
  allocation, makespan within 1e-6 relative) in <= 50 sweep evaluations
  where the paper's grid spends 600;
* the risk-aware ``mc_quantile`` objective is bit-reproducible for a fixed
  seed (common random numbers);
* ``AnalysisService.submit_optimize`` returns a result IDENTICAL to a local
  ``plan.optimize`` call.
"""

import numpy as np
import pytest

from repro import analysis
from repro.analysis import cap_space, mc_quantile, optimize
from repro.analysis.optimize import _DiffObjective
from repro.analysis.pack import ThetaMap
from repro.analysis.scenarios import override, ramp_resource
from repro.analysis.serve import AnalysisService
from repro.configs.paper_workflow import (build_workflow, compile_paper_plan,
                                          fig7_space, mc_spec,
                                          sweep_scenarios)
from repro.core import DataDep, PPoly, Process, ResourceDep, Workflow

pytest.importorskip("jax")


@pytest.fixture(scope="module")
def plan():
    return compile_paper_plan(0.5)


def _diff_obj(plan_, space, scenarios=None):
    tm = ThetaMap(plan_, space.axes)
    pack = plan_.prepare(scenarios or [override(label="base")])
    return tm, _DiffObjective(plan_, tm, pack, 1, None)


def _assert_grad_matches_fd(plan_, space, theta, scenarios=None,
                            rtol=1e-4, h=1e-5):
    """jax.grad through the fused sweep == central finite differences."""
    tm, f = _diff_obj(plan_, space, scenarios)
    th = np.asarray(theta, np.float64)[None, :]
    K = space.K
    v, g = f.value_grad(th)
    assert np.isfinite(v[0]) and np.all(np.isfinite(g[0]))
    eye = np.eye(K) * h
    pts = np.concatenate([th + eye, th - eye], axis=0)   # one fused sweep
    vv = f.values(pts)
    fd = (vv[:K] - vv[K:]) / (2.0 * h)
    np.testing.assert_allclose(g[0], fd, rtol=rtol,
                               atol=1e-6 * max(1.0, abs(float(v[0]))))
    return float(v[0]), g[0]


# ------------------------------------------------------ gradient parity ----
def test_grad_matches_fd_paper_workflow(plan):
    space = cap_space(["task1.cpu", "dl1.link"], lo=0.25, hi=4.0)
    _assert_grad_matches_fd(plan, space, [1.31, 0.73])


def test_grad_matches_fd_ramp_workflow(plan):
    """Quadratic-path class: the base scenario carries a pw-linear resource
    ramp, so progress pieces are degree 2 and the diff run takes the ramps
    trace."""
    ramp = ramp_resource("dl1", "link", [0.0, 120.0], [1.6e6, 0.6e6],
                         label="ramp")
    space = cap_space(["task1.cpu", "task2.cpu"], lo=0.25, hi=4.0)
    _assert_grad_matches_fd(plan, space, [1.37, 0.81], scenarios=[ramp])


def _wide_workflow(width=4, n=1000.0):
    """``width`` parallel downloads fanning into one join task."""
    wf = Workflow()
    for i in range(width):
        p = Process(f"dl{i}", data={"d": DataDep.stream(n, n)},
                    resources={"link": ResourceDep.stream(n, n)},
                    total_progress=n).identity_output()
        wf.add(p, resources={"link": PPoly.constant(8.0 + 2.0 * i)})
        wf.set_data_input(f"dl{i}", "d", PPoly.constant(n))
    join = Process("join",
                   data={f"in{i}": DataDep.stream(n, n) for i in range(width)},
                   resources={"cpu": ResourceDep.stream(30.0, n)},
                   total_progress=n).identity_output()
    wf.add(join, resources={"cpu": PPoly.constant(1.0)})
    for i in range(width):
        wf.connect(f"dl{i}", "join", f"in{i}")
    return wf


def test_grad_matches_fd_wide_dag():
    wide = analysis.compile(_wide_workflow())
    space = cap_space(["dl0.link", "dl2.link", "join.cpu"], lo=0.25, hi=4.0)
    _assert_grad_matches_fd(wide, space, [0.93, 1.41, 1.18])


def test_grad_matches_fd_across_event_order_change(plan):
    """Scaling task1.cpu far enough flips which dependency is the bottleneck
    (a different event order inside the lockstep loop).  The gradient is
    discontinuous across the kink but must match FD on EACH side."""
    space = cap_space(["task1.cpu"], lo=0.1, hi=8.0)
    _, g_lo = _assert_grad_matches_fd(plan, space, [0.41])
    _, g_hi = _assert_grad_matches_fd(plan, space, [3.63])
    # cpu-bound side: more cpu buys real makespan; link-bound side: it can't
    assert abs(g_lo[0]) > 10.0 * abs(g_hi[0])


def test_diff_values_match_plan_sweep(plan):
    """The differentiable forward path is the SAME number plan.sweep gives
    for the materialized scenario (not merely close)."""
    space = cap_space(["task1.cpu", "dl2.link"], lo=0.25, hi=4.0)
    tm, f = _diff_obj(plan, space)
    thetas = np.array([[0.62, 1.0], [1.0, 1.0], [1.73, 0.55]])
    got = f.values(thetas)
    scs = [tm.materialize(t, label=f"t{i}") for i, t in enumerate(thetas)]
    ref = plan.sweep(scs, backend="batched").makespan
    np.testing.assert_allclose(got, ref, rtol=1e-9)


# ---------------------------------------------------------- fig 7 search ----
def test_optimize_recovers_fig7_grid_optimum(plan):
    """<= 50 fused-sweep evaluations where the paper's grid spends 600."""
    fracs = np.linspace(0.02, 0.98, 600)
    grid_ms = plan.sweep(sweep_scenarios(fracs), backend="batched").makespan
    gi = int(np.argmin(grid_ms))
    spacing = fracs[1] - fracs[0]

    opt = plan.optimize(space=fig7_space(), max_evals=50)
    assert opt.evals <= 50
    assert abs(float(opt.theta[0]) - fracs[gi]) <= spacing + 1e-12
    assert opt.value <= grid_ms[gi] * (1.0 + 1e-6)
    # provenance: the report re-verifies the optimum through plan.sweep
    assert opt.report.makespan[0] == pytest.approx(opt.value, rel=1e-9)
    assert opt.gain > 0.0 and opt.baseline > opt.value
    assert "frac_task1" in opt.summary()


def test_optimize_multistart_and_trajectory(plan):
    space = cap_space(["task1.cpu"], lo=0.25, hi=4.0)
    opt = plan.optimize(space=space, starts=2, max_iters=3)
    assert opt.thetas.shape[1] == 1 and len(opt.trajectory) == opt.iters
    # trajectory tracks the incumbent: monotone non-increasing
    assert np.all(np.diff(opt.trajectory) <= 1e-12)


# ------------------------------------------------------------- risk-aware ----
def test_mc_quantile_objective_bit_reproducible(plan):
    obj = mc_quantile(mc_spec(), q=0.9, n=24, seed=5)
    space = cap_space(["task1.cpu"], lo=0.5, hi=2.0)
    a = plan.optimize(obj, space, max_iters=2)
    b = plan.optimize(obj, space, max_iters=2)
    np.testing.assert_array_equal(a.theta, b.theta)
    assert a.value == b.value and a.evals == b.evals
    np.testing.assert_array_equal(a.trajectory, b.trajectory)
    assert "p90" in a.objective and "seed=5" in a.objective


def test_pw_axis_rejected_on_mc_perturbed_key(plan):
    """fig7_space rebuilds dl1.link/dl2.link wholesale; an MC spec that
    perturbs those same keys would be silently overwritten — reject it."""
    with pytest.raises(ValueError, match="perturb"):
        plan.optimize(mc_quantile(mc_spec(), n=4), fig7_space(), max_iters=1)


# ------------------------------------------------------------ service path ----
def test_submit_optimize_identical_to_local(plan):
    space = cap_space(["task1.cpu"], lo=0.5, hi=2.0)
    local = plan.optimize(space=space, max_iters=2)
    svc = AnalysisService(plan)
    try:
        served = svc.query_optimize(space=space, max_iters=2)
    finally:
        svc.close()
    np.testing.assert_array_equal(served.theta, local.theta)
    assert served.value == local.value
    assert served.evals == local.evals and served.sweeps == local.sweeps
    np.testing.assert_array_equal(served.trajectory, local.trajectory)


# ------------------------------------------------------- guardrails & API ----
def test_optimize_requires_space(plan):
    with pytest.raises(ValueError, match="Space"):
        plan.optimize()


def test_optimize_unknown_objective(plan):
    with pytest.raises(ValueError, match="objective"):
        plan.optimize("latency", cap_space(["task1.cpu"]))


def test_cap_space_unknown_resource(plan):
    with pytest.raises(KeyError):
        _diff_obj(plan, cap_space(["task1.gpu"]))


def test_optimize_deadline(plan):
    with pytest.raises(TimeoutError):
        plan.optimize(space=cap_space(["task1.cpu"]), deadline_s=-1.0)


def test_constraints_projection_is_enforced(plan):
    space = cap_space(["task1.cpu"], lo=0.25, hi=4.0)
    cap = 1.1

    def proj(x):
        return np.minimum(x, cap)

    opt = plan.optimize(space=space, constraints=proj, max_iters=4)
    assert float(opt.theta[0]) <= cap + 1e-12


def test_optimize_report_summary_fields(plan):
    opt = plan.optimize(space=cap_space(["task1.cpu"]), max_iters=2)
    s = opt.summary()
    assert "task1.cpu" in s and "evals" in s and "baseline" in s


# ------------------------------------------------ keyword-only knobs ----
def _sweep_positional_backend(plan):
    plan.sweep(sweep_scenarios([0.5]), "jax")


def _sample_spec_positional_seed(plan):
    from repro.analysis.uncertainty import sample_spec
    sample_spec(plan, mc_spec(), 4, 7)


@pytest.mark.parametrize("call", [_sweep_positional_backend,
                                  _sample_spec_positional_seed],
                         ids=["sweep-backend", "sample_spec-seed"])
def test_positional_unified_kwarg_raises(plan, call):
    with pytest.raises(TypeError, match="positional argument"):
        call(plan)


def test_front_door_exports():
    for name in ("compile", "Report", "MCReport", "OptimizeReport", "dist",
                 "grid", "override", "ramp_resource", "AnalysisService",
                 "FaultPlan", "cap_space", "mc_quantile", "optimize"):
        assert name in analysis.__all__, name
        assert hasattr(analysis, name), name
    assert analysis.compile is analysis.compile_workflow
    assert optimize.OptimizeReport is analysis.OptimizeReport

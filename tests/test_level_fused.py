"""Level-fused jax engine: topology-level stacking vs the numpy reference.

ISSUE 5 contracts:

* ``CompiledWorkflow.levels`` groups processes by longest-path depth over
  edges AND gates; processes in one level share no dependencies.
* The compiled trace contains ONE ``lax.while_loop`` per topology level —
  the paper workflow (5 processes, 3 levels) is pinned to <= 3 loops.
* jax-vs-numpy parity — makespans, finish times, progress curves AND
  ``share_seconds`` attribution — holds on DAGs with WIDE levels (many
  processes stacked into one loop), diamond joins, level-internal padding
  (different ceiling/resource counts per process, no-data processes), and
  mixed linear/ramp function classes inside one level.
* The proven iteration budget down-ratchets once after the first solve, so
  re-sweeps run with tight record buffers; results stay identical.
"""

import numpy as np
import pytest

from repro import sweep
from repro.configs.paper_workflow import build_workflow, sweep_scenarios
from repro.core import DataDep, PPoly, Process, ResourceDep, Workflow

from test_sweep import _assert_match

B_SMALL = 6


def _jax_vs_numpy(wf, scenarios):
    plan = wf.compile()
    rj = plan.sweep(plan.prepare(scenarios), backend="jax")
    rn = plan.sweep(scenarios, backend="numpy")
    assert set(rj.backends) == {"jax"}
    _assert_match(rj, rn)
    return plan, rj, rn


def _diamond(n_mid: int = 4, burst: bool = False) -> Workflow:
    """src -> n_mid parallel consumers (one WIDE level) -> gated join.

    The join consumes two of the middle outputs through edges and is gated
    on a third, so the level grouping must honour edges AND gates; one
    middle process has TWO resources and another has none, so the stacked
    level exercises resource-slot padding and the synthetic ceiling.
    """
    n = 1000.0
    wf = Workflow()
    src = Process("src", data={"d": DataDep.stream(n, n)},
                  resources={"link": ResourceDep.stream(n, n)},
                  total_progress=n).identity_output()
    wf.add(src, resources={"link": PPoly.constant(25.0)})
    wf.set_data_input("src", "d", PPoly.constant(n))
    mids = [f"m{i}" for i in range(n_mid)]
    for i, name in enumerate(mids):
        res = {"cpu": ResourceDep.stream(20.0 + 5.0 * i, 500.0)}
        if burst and i == 1:
            res["mem"] = ResourceDep.burst_at(250.0, 10.0, 500.0)
        dep = (DataDep.burst(n, 500.0) if burst and i == 0
               else DataDep.stream(n, 500.0))
        p = Process(name, data={"in": dep}, resources=res,
                    total_progress=500.0).identity_output()
        wf.add(p, resources={r: PPoly.constant(1.0 + 0.3 * i) for r in res})
        wf.connect("src", name, "in")
    # a process with NO data dependency rides in the wide level too
    tick = Process("tick", data={},
                   resources={"cpu": ResourceDep.stream(30.0, 300.0)},
                   total_progress=300.0).identity_output()
    wf.add(tick, resources={"cpu": PPoly.constant(2.0)})
    join = Process("join",
                   data={"a": DataDep.stream(500.0, 300.0),
                         "b": DataDep.stream(500.0, 300.0)},
                   resources={"cpu": ResourceDep.stream(10.0, 300.0)},
                   total_progress=300.0).identity_output()
    wf.add(join, resources={"cpu": PPoly.constant(1.0)},
           start_after=[mids[2]] if n_mid > 2 else None)
    wf.connect(mids[0], "join", "a")
    wf.connect(mids[1], "join", "b")
    return wf


# ------------------------------------------------------------- grouping ----
def test_paper_workflow_levels():
    plan = build_workflow(0.5).compile()
    assert [sorted(lv) for lv in plan.levels] == [
        ["dl1", "dl2"], ["task1", "task2"], ["task3"]]
    assert sorted(n for lv in plan.levels for n in lv) == sorted(plan.order)


def test_diamond_levels_honour_edges_and_gates():
    plan = _diamond().compile()
    assert len(plan.levels) == 3
    assert sorted(plan.levels[0]) == ["src", "tick"]
    assert sorted(plan.levels[1]) == ["m0", "m1", "m2", "m3"]
    assert plan.levels[2] == ["join"]


# ----------------------------------------------------- while_loop pinning ---
def test_paper_workflow_traces_to_three_while_loops():
    """The tentpole claim: 5 processes compile to <= 3 stacked loops."""
    from repro.sweep.jax_engine import trace_report

    plan = build_workflow(0.5).compile()
    pack = plan.prepare(sweep_scenarios(np.linspace(0.1, 0.9, 4)))
    rep = trace_report(plan, pack)
    assert rep["while_loops"] == 3
    assert rep["while_loops"] == len(plan.levels)
    # the per-iteration op budget: 20 % over the 1247 loop-body equations
    # of the first level-fused engine; the count does not depend on B
    assert rep["body_eqns"] <= 1496, rep


def test_diamond_traces_to_one_loop_per_level():
    from repro.sweep.jax_engine import trace_report

    plan = _diamond().compile()
    pack = plan.prepare([sweep.Scenario()])
    assert trace_report(plan, pack)["while_loops"] == 3  # 7 processes


# ------------------------------------------------------------- parity -------
def test_wide_level_matches_numpy():
    wf = _diamond()
    scs = [sweep.Scenario(label=f"s{v}",
                          resource_inputs={("src", "link"): PPoly.constant(v)})
           for v in (10.0, 25.0, 60.0, 200.0)]
    _jax_vs_numpy(wf, scs)


def test_wide_level_with_bursts_and_stalls_matches_numpy():
    wf = _diamond(burst=True)
    scs = [sweep.Scenario(label=f"m{m}",
                          resource_inputs={("m1", "mem"): PPoly.constant(m),
                                           ("src", "link"): PPoly.step(
                                               [0, 15], [40.0, 10.0 * m])})
           for m in (0.5, 1.0, 4.0)]
    _jax_vs_numpy(wf, scs)


def test_mixed_linear_and_ramp_classes_in_one_level():
    """One process of the wide level gets a RAMPED (pw-linear) resource while
    its level-mates stay constant — the stacked quadratic trace must agree
    with the numpy engine for every process, including attribution."""
    wf = _diamond()
    scs = [sweep.Scenario(
        label=f"r{f}",
        resource_inputs={("m0", "cpu"): PPoly.pwlinear([0.0, 40.0],
                                                       [0.2 * f, 3.0]),
                         ("m3", "cpu"): PPoly.constant(0.7),
                         ("tick", "cpu"): PPoly.pwlinear([0.0, 30.0],
                                                         [2.0, f])})
        for f in (0.5, 1.0, 2.0)]
    plan, rj, _rn = _jax_vs_numpy(wf, scs)
    pack = plan.prepare(scs)
    assert pack.ramps  # the widened trace, not the linear one


def test_gated_chain_across_levels():
    """Gate start times flow level to level (join waits on m2's finish)."""
    wf = _diamond()
    plan, rj, rn = _jax_vs_numpy(wf, [sweep.Scenario()])
    m2_fin = rj.finish["m2"][0]
    assert rj.proc_results["join"].t_start[0] >= m2_fin - 1e-6


@pytest.mark.parametrize("seed", [0, 2, 5])
def test_randomized_wide_dags_match_numpy(seed):
    """Randomized DAGs with wide levels and random diamond edges/gates."""
    rng = np.random.default_rng(seed)
    n = float(rng.integers(300, 1500))
    wf = Workflow()
    n_src = int(rng.integers(1, 3))
    for i in range(n_src):
        p = Process(f"s{i}", data={"d": DataDep.stream(n, n)},
                    resources={"link": ResourceDep.stream(
                        float(rng.uniform(10, 60)), n)},
                    total_progress=n).identity_output()
        wf.add(p, resources={"link": PPoly.constant(float(rng.uniform(5, 40)))})
        wf.set_data_input(f"s{i}", "d", PPoly.constant(n))
    n_mid = int(rng.integers(2, 5))
    for i in range(n_mid):
        p2 = float(rng.integers(100, 600))
        dep = (DataDep.burst(n, p2) if rng.random() < 0.3
               else DataDep.stream(n, p2))
        p = Process(f"w{i}", data={"in": dep},
                    resources={"cpu": ResourceDep.stream(
                        float(rng.uniform(5, 40)), p2)},
                    total_progress=p2).identity_output()
        gate = [f"s{rng.integers(0, n_src)}"] if rng.random() < 0.3 else None
        wf.add(p, resources={"cpu": PPoly.constant(float(rng.uniform(0.5, 3)))},
               start_after=gate)
        wf.connect(f"s{rng.integers(0, n_src)}", f"w{i}", "in")
    scs = []
    for b in range(B_SMALL):
        ov = {}
        for pn, allocs in wf.resource_alloc.items():
            for res in allocs:
                style = rng.random()
                if style < 0.4:
                    fn = PPoly.constant(float(rng.uniform(0.3, 6.0)))
                elif style < 0.7:
                    ts = np.sort(rng.uniform(1.0, 90.0, 2))
                    fn = PPoly.step([0.0, *ts], list(rng.uniform(0.0, 6.0, 3)))
                else:  # non-negative ramp: the quadratic class
                    fn = PPoly.pwlinear(
                        [0.0, float(rng.uniform(10, 80))],
                        [float(rng.uniform(0.1, 3.0)),
                         float(rng.uniform(0.1, 5.0))])
                ov[(pn, res)] = fn
        scs.append(sweep.Scenario(label=f"s{b}", resource_inputs=ov))
    _jax_vs_numpy(wf, scs)


# ---------------------------------------------------- iteration budget ------
def test_proven_cap_down_ratchets_once():
    """The first solve tightens the proven budget to the actual event depth;
    the re-sweep (tight recompile) returns identical results."""
    from repro.sweep.jax_engine import DEFAULT_ITER_CAP

    plan = build_workflow(0.5).compile()
    pack = plan.prepare(sweep_scenarios(np.linspace(0.1, 0.9, 4)))
    r1 = plan.sweep(pack, backend="jax")
    cap = plan._jax_engine._proven_caps[(4, 1, False)]
    assert cap < DEFAULT_ITER_CAP  # paper workflow needs ~2 events per level
    r2 = plan.sweep(pack, backend="jax")
    np.testing.assert_array_equal(r1.makespans, r2.makespans)
    np.testing.assert_array_equal(r1.share_seconds, r2.share_seconds)
    assert plan._jax_engine._proven_caps[(4, 1, False)] == cap  # stable


def test_new_batch_size_starts_at_a_proven_cap():
    """A batch size the engine has not solved starts at the budget proven
    for another one: one compile, no ladder, the same rows as the numpy
    engine."""
    from repro.sweep.jax_engine import JaxSweepEngine

    plan = build_workflow(0.5).compile()
    plan._jax_engine = eng = JaxSweepEngine(plan, iter_cap=1)
    plan.sweep(plan.prepare(sweep_scenarios(np.linspace(0.1, 0.9, 4))),
               backend="jax")
    cap = eng._proven_caps[(4, 1, False)]
    assert cap > 1 and len(eng._compiled) >= 2     # climbed from 1
    n = len(eng._compiled)
    scs = sweep_scenarios(np.linspace(0.2, 0.8, 2))
    rj = plan.sweep(plan.prepare(scs), backend="jax")
    assert eng._proven_caps[(2, 1, False)] <= cap
    assert len(eng._compiled) == n + 1 and (2, 1, cap, False) in eng._compiled
    _assert_match(rj, plan.sweep(scs, backend="numpy"))

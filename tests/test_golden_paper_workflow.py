"""Golden regression: pinned numbers for the paper's Sect. 5 workflow.

These literals were produced by the exact event-driven solver on the Fig. 5
workflow with the Sect. 5.1 constants and cross-checked against the DES twin.
They pin task finish times and Fig. 8-style bottleneck shares at 50 % / 95 %
so refactors of the solver, the workflow engine, or the sweep engine cannot
silently drift.  Tolerances are tight (1e-9 relative): any change that moves
these numbers is a behavior change and must update this file deliberately.
"""

import numpy as np
import pytest

from repro.configs.paper_workflow import build_workflow, sweep_scenarios
from repro.core import bottleneck_report

REL = 1e-9

#: dl finish at 50 % allocation: VIDEO_BYTES / (0.5 * LINK_BPS)
T_DL_50 = 186.64531785457902

GOLDEN_FINISH = {
    0.50: {"dl1": 186.64531785457902, "dl2": 186.64531785457902,
           "task1": 294.645317854579, "task2": 186.64531785457902,
           "task3": 297.645317854579},
    0.95: {"dl1": 98.23437781819949, "dl2": 186.64531785457902,
           "task1": 206.23437781819948, "task2": 186.64531785457902,
           "task3": 209.23437781819948},
}
GOLDEN_MAKESPAN = {0.50: 297.645317854579, 0.95: 209.23437781819948}

#: Fig. 8-style structure: fraction of each process's runtime per bottleneck
GOLDEN_SHARES = {
    0.50: {("dl1", "resource", "link"): 1.0,
           ("dl2", "resource", "link"): 1.0,
           ("task1", "data", "video"): 0.633457607,
           ("task1", "resource", "cpu"): 0.366542393,
           ("task2", "data", "video"): 1.0,
           ("task3", "resource", "cpu"): 1.0},
    0.95: {("dl1", "resource", "link"): 1.0,
           ("dl2", "resource", "link"): 1.0,
           ("task1", "data", "video"): 0.476323971,
           ("task1", "resource", "cpu"): 0.523676029,
           ("task2", "data", "video"): 1.0,
           ("task3", "resource", "cpu"): 1.0},
}


@pytest.mark.parametrize("frac", [0.50, 0.95])
def test_golden_finish_times_scalar(frac):
    wr = build_workflow(frac).analyze()
    assert wr.makespan == pytest.approx(GOLDEN_MAKESPAN[frac], rel=REL)
    for name, expect in GOLDEN_FINISH[frac].items():
        assert wr.results[name].finish_time == pytest.approx(expect, rel=REL), name


@pytest.mark.parametrize("frac", [0.50, 0.95])
def test_golden_bottleneck_shares_scalar(frac):
    wr = build_workflow(frac).analyze()
    shares = {(b.process, b.kind, b.name): b.fraction
              for b in bottleneck_report(wr)}
    assert set(shares) == set(GOLDEN_SHARES[frac])
    for key, expect in GOLDEN_SHARES[frac].items():
        assert shares[key] == pytest.approx(expect, rel=1e-6), key


def test_golden_sweep_engine_reproduces_both_points():
    """The batched engine reproduces the same pinned numbers in one pass."""
    rb = build_workflow(0.5).compile().sweep(sweep_scenarios([0.50, 0.95]),
                                             backend="batched")
    for i, frac in enumerate((0.50, 0.95)):
        assert rb.makespan[i] == pytest.approx(GOLDEN_MAKESPAN[frac], rel=REL)
        for name, expect in GOLDEN_FINISH[frac].items():
            assert rb.finish[name][i] == pytest.approx(expect, rel=REL), name
        shares = {(r.process, r.kind, r.name): r.fraction
                  for r in rb.bottleneck_report(i)}
        for key, expect in GOLDEN_SHARES[frac].items():
            assert shares[key] == pytest.approx(expect, rel=1e-6), key


def test_golden_fig7_improvement():
    """Paper Fig. 7 headline: ~32 % makespan reduction from 50 % -> 93 %."""
    rb = build_workflow(0.5).compile().sweep(sweep_scenarios([0.50, 0.93]),
                                             backend="batched")
    improvement = 1.0 - rb.makespan[1] / rb.makespan[0]
    assert improvement == pytest.approx(0.28994, abs=1e-4)


def test_golden_compiled_api_reproduces_pinned_numbers():
    """The compile-once front door hits the same pinned numbers as
    ``Workflow.analyze()``, through ``solve()`` and ``sweep()``."""
    plan = build_workflow(0.5).compile()
    rep = plan.solve()
    assert rep.makespan == pytest.approx(GOLDEN_MAKESPAN[0.50], rel=REL)
    for name, expect in GOLDEN_FINISH[0.50].items():
        assert rep.finish(name) == pytest.approx(expect, rel=REL), name
    shares = {(r.process, r.kind, r.name): r.fraction for r in rep.shares()}
    for key, expect in GOLDEN_SHARES[0.50].items():
        assert shares[key] == pytest.approx(expect, rel=1e-6), key

    swept = plan.sweep(sweep_scenarios([0.50, 0.95]), backend="batched")
    for i, frac in enumerate((0.50, 0.95)):
        assert swept.makespan[i] == pytest.approx(GOLDEN_MAKESPAN[frac], rel=REL)
        for name, expect in GOLDEN_FINISH[frac].items():
            assert swept.finish[name][i] == pytest.approx(expect, rel=REL), name
        got = {(r.process, r.kind, r.name): r.fraction
               for r in swept.bottleneck_report(i)}
        for key, expect in GOLDEN_SHARES[frac].items():
            assert got[key] == pytest.approx(expect, rel=1e-6), key

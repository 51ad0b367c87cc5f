"""A run with the timed path broken underneath must come out not correct:
one planted fault per kind that the cells can have, on the CPU at a tiny
size, through the whole harness past its look for a chip.

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests/test_faults.py

No fault is about an exchange between chips: every cell runs on one chip.
"""

import numpy as np
import pytest

CELLS = ["paper_fig5.whatif_open", "paper_fig5.mc10k"]


def alter_first_answer(monkeypatch):
    """One answer per fused sweep altered where the engine produces it."""
    from repro.sweep.jax_engine import JaxSweepEngine

    orig = JaxSweepEngine._wrap

    def wrap(self, *a, **kw):
        res = orig(self, *a, **kw)
        r = res[self.spec.procs[-1].name]
        r.finish = r.finish.copy()          # device arrays are read-only
        r.finish[0] *= 1.0 + 1e-6
        return res

    monkeypatch.setattr(JaxSweepEngine, "_wrap", wrap)


def drop_half_the_batch(monkeypatch):
    """The engine solves the first half of each batch and hands its rows to
    the second half too."""
    from repro.sweep.jax_engine import JaxSweepEngine

    orig = JaxSweepEngine._wrap

    def wrap(self, out, B, *a, **kw):
        res = orig(self, out, B, *a, **kw)
        h = B - B // 2
        for r in res.values():
            r.finish, r.share_seconds = r.finish.copy(), r.share_seconds.copy()
            r.finish[h:] = r.finish[:B - h]
            r.share_seconds[h:] = r.share_seconds[:B - h]
        return res

    monkeypatch.setattr(JaxSweepEngine, "_wrap", wrap)


def stale_state(monkeypatch, workload):
    """Work that returns its earlier state: the what-if service answers with
    the first report it built for each batch width; the Monte Carlo sampler
    ignores each call's seed."""
    import repro.analysis.serve as serve

    if workload.endswith("mc10k"):
        orig = serve.sample_spec
        monkeypatch.setattr(serve, "sample_spec",
                            lambda plan, spec, n, seed=0: orig(plan, spec, n,
                                                               seed=0))
        return
    first = {}
    orig = serve.AnalysisService._do_sweep

    def do_sweep(self, plan, pack, B_real):
        return first.setdefault(pack.B, orig(self, plan, pack, B_real))

    monkeypatch.setattr(serve.AnalysisService, "_do_sweep", do_sweep)


def degrade_first_row(monkeypatch):
    """The fused engine returns garbage (NaN) in the first row of every
    sweep, which the service re-runs on its host twin: right answers, but
    not from the path under test."""
    import drivers
    from repro.analysis.faults import FaultPlan

    orig = drivers._service

    def service(wf, traffic):
        svc = orig(wf, traffic)
        svc._faults = FaultPlan(nan_rows=[0], nan_sweep=None)
        return svc

    monkeypatch.setattr(drivers, "_service", service)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(run_cell, workload):
    out = run_cell(workload)
    assert out["correct"] and out["failed"] == 0


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", ["altered_answer", "half_batch", "stale",
                                   "degraded"])
def test_planted_fault_is_caught(run_cell, monkeypatch, workload, fault):
    if fault == "altered_answer":
        alter_first_answer(monkeypatch)
    elif fault == "half_batch":
        drop_half_the_batch(monkeypatch)
    elif fault == "degraded":
        degrade_first_row(monkeypatch)
    else:
        stale_state(monkeypatch, workload)
    out = run_cell(workload)
    assert not out["correct"], out["checks"]
    assert any(c["value"] > c["limit"] or not np.isfinite(c["value"])
               for c in out["checks"].values())

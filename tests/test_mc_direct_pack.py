"""Monte Carlo packs built straight from the factor arrays
(``ScenarioPack.from_draws``) against the per-draw object path
(``plan.prepare(samples.scenarios)``).

Contracts under test:

* same routing (``bat_idx``, ``loop_idx``, ``loop_reasons``), the same
  ``host_args()`` shapes and dtypes and the same ``ramps`` flag, so the
  fused engine's compile keys do not change;
* planes equal up to rounding: ``starts`` within 4 ulp, coefficients within
  1e-12 relative, and makespans, finishes and shares of both the numpy and
  the jax backend within 1e-12 relative;
* ``samples.scenarios[i]`` builds exactly the ``Scenario`` the per-draw
  loop used to build (digests taken from that loop);
* ``query_mc`` packs every draw from the arrays (``mc_draws_direct``) and
  builds no ``Scenario`` (``mc_draws_materialized``), and its chunks are
  padded to their pow2 bucket by replicating the last draw.
"""

from __future__ import annotations

import hashlib
import warnings

import numpy as np
import pytest

from repro.analysis import AnalysisService, OnlineReanalysis, dist, scenarios
from repro.analysis.pack import ScenarioPack
from repro.analysis.uncertainty import sample_spec
from repro.configs.paper_workflow import build_workflow, mc_spec
from repro.core import DataDep, PPoly, Process, ResourceDep, Workflow

N, SEED = 48, 2_147_483_659
CUBIC = PPoly(np.array([0.0]), [np.array([0.0, 0.0, 0.0, 1e-9])])


def link_plan():
    """One download of 1000 units over a 10/s link; its file arrives along
    a two-piece ramp, so a data speed-up moves breakpoints and slopes."""
    n = 1000.0
    wf = Workflow()
    wf.add(Process("dl", data={"file": DataDep.stream(n, n)},
                   resources={"link": ResourceDep.stream(n, n)},
                   total_progress=n).identity_output(),
           resources={"link": PPoly.constant(10.0)})
    wf.set_data_input("dl", "file",
                      PPoly.pwlinear([0.0, 40.0, 80.0], [0.0, 300.0, n]))
    return wf.compile()


@pytest.fixture(scope="module")
def plans():
    return {"paper": build_workflow(0.5).compile(), "link": link_plan()}


def online_samples(plan):
    """The draws ``OnlineReanalysis.mc`` packs: a data speed-up around a
    tracked state whose link was measured as a falling ramp."""
    live = OnlineReanalysis(plan, scenarios.override({"dl.link": 1.0}))
    live.ingest({"dl.link": PPoly.pwlinear([0.0, 30.0], [10.0, 4.0])})
    spec = scenarios.override(data={"dl.file": dist.uniform(0.8, 1.25)})
    return live, spec, sample_spec(plan, spec, N, seed=SEED).around(
        live.pack.scenarios[0])


CASES = {
    "mc_spec": ("paper", lambda p: sample_spec(p, mc_spec(), N, seed=SEED)),
    "dist_ramp": ("link", lambda p: sample_spec(
        p, scenarios.ramp_resource("dl", "link", [0.0, 20.0, 50.0],
                                   [10.0, dist.uniform(2.0, 20.0),
                                    dist.lognormal(5.0, 0.5)]),
        N, seed=SEED)),
    "grid_fixed_cubic": ("link", lambda p: sample_spec(
        p, [scenarios.override({"dl.link": dist.uniform(0.5, 2.0)},
                               data={"dl.file": dist.uniform(0.9, 1.1)},
                               label="good"),
            scenarios.override({"dl.link": dist.uniform(0.5, 2.0)},
                               data={("dl", "file"): CUBIC}, label="bad")],
        N, seed=SEED)),
    "resource_sign": ("link", lambda p: sample_spec(
        p, scenarios.override({"dl.link": dist.uniform(-0.5, 1.5)}), N,
        seed=SEED)),
    "online_template": ("link", lambda p: online_samples(p)[2]),
}


def close(a, b, rtol=1e-12):
    np.testing.assert_allclose(a, b, rtol=rtol,
                               atol=rtol * max(1.0, float(np.max(np.abs(
                                   np.where(np.isfinite(b), b, 0.0)),
                                   initial=0.0))))


def rows_of(rep):
    return [rep.makespans, rep.share_seconds] + [rep.finish[n]
                                                 for n in rep.order]


@pytest.mark.parametrize("case", sorted(CASES))
def test_direct_pack_matches_object_path(plans, case):
    name, make = CASES[case]
    plan = plans[name]
    samples = make(plan)
    direct = ScenarioPack.from_draws(plan, samples)
    ref = plan.prepare(samples.scenarios)

    assert direct.bat_idx == ref.bat_idx
    assert direct.loop_idx == ref.loop_idx
    assert direct.loop_reasons == ref.loop_reasons
    assert direct.ramps == ref.ramps
    assert direct.labels == ref.labels
    ha, hb = direct.host_args(), ref.host_args()
    assert ha.keys() == hb.keys()
    for proc in hb:
        for grp in hb[proc]:
            assert ha[proc][grp].keys() == hb[proc][grp].keys()
            for key, planes in hb[proc][grp].items():
                mine = ha[proc][grp][key]
                assert [(a.shape, a.dtype) for a in mine] == \
                    [(b.shape, b.dtype) for b in planes]
                np.testing.assert_array_max_ulp(mine[0], planes[0], maxulp=4)
                for a, b in zip(mine[1:], planes[1:]):
                    close(a, b)

    if direct.bat_idx:
        sub = direct.bat_idx
        for a, b in zip(
                rows_of(plan.sweep(direct.subset(sub), backend="numpy")),
                rows_of(plan.sweep(ref.subset(sub), backend="numpy"))):
            close(a, b)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # loop-routed rows warn once
        got, want = plan.sweep(direct), plan.sweep(ref)
    assert got.backends == want.backends
    assert ("jax" in got.backends) == bool(direct.bat_idx)
    for a, b in zip(rows_of(got), rows_of(want)):
        close(a, b)


def test_routing_follows_the_factor_sign(plans):
    samples = CASES["resource_sign"][1](plans["link"])
    pack = ScenarioPack.from_draws(plans["link"], samples)
    neg = np.flatnonzero(samples.values["dl.link"] < 0.0).tolist()
    assert neg and pack.loop_idx == neg
    assert all("dl.link" in why for why in pack.loop_reasons.values())


def test_non_positive_data_speed_up_is_refused_on_the_column(plans):
    spec = scenarios.override(data={"dl.file": dist.uniform(-1.0, 1.0)})
    with pytest.raises(ValueError, match="non-positive data speed-up"):
        sample_spec(plans["link"], spec, N, seed=SEED)


def test_online_mc_matches_object_path(plans):
    plan = plans["link"]
    live, spec, samples = online_samples(plan)
    mc = live.mc(spec, n=N, seed=SEED)
    want = plan.sweep(plan.prepare(samples.scenarios), backend="numpy")
    for a, b in zip(rows_of(mc.report), rows_of(want)):
        close(a, b)
    assert mc.report.scenarios[3].resource_inputs[("dl", "link")] is \
        live.pack.scenarios[0].resource_inputs[("dl", "link")]


def _digest(scs) -> str:
    h = hashlib.sha256()
    for sc in scs:
        h.update(sc.label.encode())
        for inputs in (sc.resource_inputs, sc.data_inputs):
            for (proc, name), fn in inputs.items():
                h.update(f"{proc}.{name}".encode())
                h.update(fn.starts.tobytes())
                h.update(fn.coeffs.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("case,digest", [
    # digests of the Scenario list the per-draw loop built before the
    # draws were packed from their arrays
    ("mc_spec",
     "f31006997ef3ff4bdc8932728332bf9d146240511086ff50c05458a98a2fae9a"),
    ("speed_up",
     "94a71138d69dca71b5260e8ebe09a819310dd221ceb9849001090652c5c0de78"),
    ("dist_ramp",
     "d4b92546ce91728b27ae6584861b42e4307b947a2172bc4463040f8439ca62f3"),
])
def test_lazy_scenarios_are_the_per_draw_scenarios(plans, case, digest):
    spec = {"mc_spec": mc_spec(),
            "speed_up": scenarios.override(
                {"dl.file": dist.uniform(0.5, 2.0)}, label="r"),
            "dist_ramp": scenarios.ramp_resource(
                "dl", "link", [0.0, 20.0, 50.0],
                [10.0, dist.uniform(2.0, 20.0), dist.lognormal(5.0, 0.5)]),
            }[case]
    plan = plans["paper" if case == "mc_spec" else "link"]
    scs = sample_spec(plan, spec, N, seed=SEED).scenarios
    assert len(scs) == N
    assert _digest(scs) == digest
    assert _digest(scs[5:9]) == _digest([scs[i] for i in range(5, 9)])


def test_query_mc_packs_every_draw_from_the_arrays(plans):
    with AnalysisService(plans["paper"], backend="jax", max_batch=32) as svc:
        s0 = svc.snapshot()
        mc = svc.query_mc(mc_spec(), 80, seed=SEED, timeout=600)
        s1 = svc.snapshot()
        assert s1["mc_draws_direct"] - s0["mc_draws_direct"] == 80
        assert s1["mc_draws_materialized"] == s0["mc_draws_materialized"]
        assert set(mc.report.backends) == {"jax"}
        mc.scenarios[7]                  # a drill-down builds one Scenario
        assert svc.snapshot()["mc_draws_materialized"] == \
            s0["mc_draws_materialized"] + 1


def test_chunks_pad_to_their_bucket_and_retrace_nothing(plans, monkeypatch):
    plan = build_workflow(0.5).compile()
    packs = []
    orig = ScenarioPack.from_draws

    def spy(*args, **kw):
        packs.append((args[2], kw.get("pad_to"), orig(*args, **kw)))
        return packs[-1][2]

    monkeypatch.setattr(ScenarioPack, "from_draws", staticmethod(spy))
    with AnalysisService(plan, backend="jax", max_batch=64) as svc:
        first = svc.query_mc(mc_spec(), 100, seed=SEED, timeout=600)
        assert [(len(rows), pad, p.B) for rows, pad, p in packs] == \
            [(64, 64, 64), (36, 64, 64)]
        tail = packs[1][2]
        assert tail.labels[35:] == [first.report.labels[99]] * 29
        for args in tail.host_args().values():
            for planes in args["res"].values():
                if planes[0].shape[0] == 64:
                    assert (planes[1][35:] == planes[1][35]).all()
        traces = plan._jax_engine.trace_count
        second = svc.query_mc(mc_spec(), 100, seed=SEED + 1, timeout=600)
        assert plan._jax_engine.trace_count == traces
    assert first.n == second.n == 100

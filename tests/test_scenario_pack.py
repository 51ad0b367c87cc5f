"""Prepared scenario packs: reuse, immutability, deltas, sharding, summary.

Contracts under test (ISSUE 3 satellites):

* ``plan.sweep(plan.prepare(s))`` is BIT-identical to ``plan.sweep(s)`` on
  the numpy backend, across grid / scale_resource / override scenario kinds
  (one shared code path packs both),
* mutating the caller's scenario list (or the scenarios themselves) after
  ``prepare`` does not leak into the pack,
* ``pack.override`` delta re-packs equal a fresh ``prepare`` of the edited
  scenario list,
* ``pack.shard(n)`` pads the batch internally and returns results identical
  to single-device for B not divisible by the device count (pmap over
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` in a subprocess),
* ``Report.summary()`` surfaces the scalar-fallback rate, and the summary
  warning fires exactly once per sweep call.
"""

import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro import sweep
from repro.analysis import CompiledWorkflow, scenarios
from repro.analysis.pack import ScenarioPack
from repro.configs.paper_workflow import build_workflow, sweep_scenarios
from repro.core import PPoly

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def plan() -> CompiledWorkflow:
    return build_workflow(0.5).compile()


def _assert_bit_identical(a, b):
    np.testing.assert_array_equal(a.makespans, b.makespans)
    np.testing.assert_array_equal(a.share_seconds, b.share_seconds)
    np.testing.assert_array_equal(a.share_fractions, b.share_fractions)
    assert a.factors == b.factors
    assert a.labels == b.labels
    for n in a.order:
        np.testing.assert_array_equal(a.finish[n], b.finish[n])


SCENARIO_KINDS = {
    "grid": lambda: scenarios.grid({"dl1.link": [0.5, 1.0, 2.0],
                                    "task1.cpu": [1.0, 2.0]}),
    "scale_resource": lambda: scenarios.scale_resource(
        "task1", "cpu", [0.5, 1.0, 2.0, 4.0]),
    "override": lambda: [scenarios.override(
        {"dl1.link": PPoly.constant(2e7), "task1.cpu": 1.5}, label="x"),
        scenarios.override({"dl2.link": 0.5}, label="y")],
    "paper": lambda: sweep_scenarios([0.3, 0.6, 0.9]),
}


@pytest.mark.parametrize("kind", sorted(SCENARIO_KINDS))
def test_pack_bit_identical_to_list_numpy(plan, kind):
    scs = SCENARIO_KINDS[kind]()
    pack = plan.prepare(scs)
    _assert_bit_identical(plan.sweep(pack, backend="numpy"),
                          plan.sweep(scs, backend="numpy"))


def test_pack_bit_identical_to_list_jax(plan):
    scs = sweep_scenarios([0.3, 0.6, 0.9])
    a = plan.sweep(plan.prepare(scs), backend="jax")
    b = plan.sweep(plan.prepare(list(scs)), backend="jax")
    _assert_bit_identical(a, b)


def test_mutated_list_does_not_leak_into_pack(plan):
    scs = sweep_scenarios([0.3, 0.6, 0.9])
    pack = plan.prepare(scs)
    ref = plan.sweep(pack, backend="numpy")
    # mutate the list AND the scenario objects the caller still holds
    resolved = [s.resolve(plan.workflow) if hasattr(s, "resolve") else s
                for s in scs]
    scs.clear()
    for sc in resolved:
        for key in list(sc.resource_inputs):
            sc.resource_inputs[key] = PPoly.constant(1e-6)
    again = plan.sweep(pack, backend="numpy")
    _assert_bit_identical(ref, again)


def test_pack_override_equals_fresh_prepare(plan):
    base = sweep_scenarios([0.3, 0.6, 0.9])
    pack = plan.prepare(base)
    fast = [PPoly.constant(3e7), PPoly.constant(4e7), PPoly.constant(5e7)]
    delta = pack.override({"dl1.link": fast, ("task1", "cpu"): 2.0})
    edited = []
    for i, spec in enumerate(sweep_scenarios([0.3, 0.6, 0.9])):
        sc = spec.resolve(plan.workflow)
        sc.resource_inputs[("dl1", "link")] = fast[i]
        sc.resource_inputs[("task1", "cpu")] = \
            plan.base_res[("task1", "cpu")] * 2.0
        edited.append(sc)
    _assert_bit_identical(plan.sweep(delta, backend="numpy"),
                          plan.sweep(plan.prepare(edited), backend="numpy"))
    # the original pack is untouched
    _assert_bit_identical(plan.sweep(pack, backend="numpy"),
                          plan.sweep(base, backend="numpy"))


def test_pack_override_validates(plan):
    pack = plan.prepare(sweep_scenarios([0.5]))
    with pytest.raises(ValueError, match="unknown process"):
        pack.override({"ghost.cpu": 2.0})
    with pytest.raises(ValueError, match="no input"):
        pack.override({"task1.gpu": 2.0})
    with pytest.raises(ValueError, match="produced by"):
        pack.override({"task1.video": 2.0})
    with pytest.raises(sweep.UnsupportedScenario, match="function class"):
        pack.override({"task1.cpu": PPoly(np.array([0.0]),
                                          [np.array([1.0, 0.1, 0.01])])})
    # a piecewise-linear ramp is INSIDE the batched class now
    ramped = pack.override({"task1.cpu": PPoly.pwlinear([0.0, 5.0], [1.0, 3.0])})
    assert ramped.loop_idx == pack.loop_idx
    with pytest.raises(ValueError, match="entries"):
        pack.override({"task1.cpu": [1.0, 2.0]})  # B=1 but 2 entries


def test_pack_override_accepts_numpy_0d_scalars(plan):
    """Regression: ``np.isscalar(np.array(2.0))`` is False, so 0-d arrays
    and numpy scalar kinds — exactly what monitoring feeds hand over — were
    iterated as sequences and crashed in ``float(v)``.  Every numpy scalar
    must mean 'scale the base input', bit-identical to the plain float."""
    pack = plan.prepare(sweep_scenarios([0.3, 0.6, 0.9]))
    ref = plan.sweep(pack.override({"task1.cpu": 2.0, "dl1.link": 0.7}),
                     backend="numpy")
    for two, seven in ((np.array(2.0), np.array(0.7)),
                       (np.float64(2.0), np.float64(0.7)),
                       (np.int64(2), np.asarray(0.7))):
        got = plan.sweep(pack.override({"task1.cpu": two, "dl1.link": seven}),
                         backend="numpy")
        _assert_bit_identical(got, ref)


def test_pack_from_other_plan_rejected(plan):
    other = build_workflow(0.5).compile()
    pack = other.prepare(sweep_scenarios([0.5]))
    with pytest.raises(ValueError, match="different plan"):
        plan.sweep(pack)


def test_unknown_backend_rejected(plan):
    with pytest.raises(ValueError, match="unknown backend"):
        plan.sweep(sweep_scenarios([0.5]), backend="cuda")


def test_shard_validation(plan):
    pack = plan.prepare(sweep_scenarios([0.3, 0.6]))
    with pytest.raises(ValueError, match=">= 1"):
        pack.shard(0)
    import jax
    too_many = jax.local_device_count() + 1
    with pytest.raises(ValueError, match="device"):
        plan.sweep(pack.shard(too_many), backend="jax")


def test_sharded_sweep_identical_to_single_device_subprocess():
    """Padding correctness: B=6 over 4 forced CPU devices == single device.

    Device count is fixed at JAX init, so the pmap path runs in a fresh
    subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=4.
    """
    code = """
import numpy as np, jax
assert jax.local_device_count() == 4, jax.local_device_count()
from repro.core import DataDep, PPoly, Process, ResourceDep, Workflow
from repro import sweep
n = 1000.0
wf = Workflow()
wf.add(Process("dl", data={"file": DataDep.stream(n, n)},
               resources={"link": ResourceDep.stream(n, n)},
               total_progress=n).identity_output(),
       resources={"link": PPoly.constant(10.0)})
wf.set_data_input("dl", "file", PPoly.constant(n))
scs = [sweep.Scenario(label=f"r{r}",
                      resource_inputs={("dl", "link"): PPoly.constant(r)})
       for r in (2.0, 4.0, 5.0, 8.0, 10.0, 40.0)]   # B=6, not divisible by 4
plan = wf.compile()
pack = plan.prepare(scs)
r1 = plan.sweep(pack, backend="jax")
r4 = plan.sweep(pack.shard(4), backend="jax")
np.testing.assert_array_equal(r1.makespans, r4.makespans)
np.testing.assert_array_equal(r1.share_seconds, r4.share_seconds)
for nme in r1.order:
    np.testing.assert_array_equal(r1.finish[nme], r4.finish[nme])
rn = plan.sweep(scs, backend="numpy")
np.testing.assert_allclose(r4.makespans, rn.makespans, rtol=1e-9)
np.testing.assert_allclose(r4.makespans, [500., 250., 200., 125., 100., 25.])
print("SHARD-OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARD-OK" in out.stdout


# ------------------------------------------------- summary + warn-once ----
def _mixed_setup():
    from repro.core import DataDep, Process, ResourceDep, Workflow
    n = 1000.0
    wf = Workflow()
    wf.add(Process("dl", data={"file": DataDep.stream(n, n)},
                   resources={"link": ResourceDep.stream(n, n)},
                   total_progress=n).identity_output(),
           resources={"link": PPoly.constant(10.0)})
    wf.set_data_input("dl", "file", PPoly.constant(n))
    quad = PPoly(np.array([0.0]), [np.array([5.0, 0.1, 0.01])])  # degree 2
    scs = [sweep.Scenario(label="fast",
                          resource_inputs={("dl", "link"): PPoly.constant(20.0)}),
           sweep.Scenario(label="quad",
                          resource_inputs={("dl", "link"): quad}),
           sweep.Scenario(label="slow",
                          resource_inputs={("dl", "link"): PPoly.constant(5.0)})]
    return wf.compile(), scs


def test_pack_override_on_mixed_routing_pack():
    """Regression: ``override`` used to validate EVERY scenario's replacement
    against the batched function class, so any pack with a loop-routed row
    rejected all deltas.  Only batched rows need validating — the scalar
    solver accepts any PPoly — and the delta re-pack must equal a fresh
    prepare of the edited list."""
    plan, scs = _mixed_setup()
    quad2 = PPoly(np.array([0.0]), [np.array([4.0, 0.2, 0.02])])
    repl = [PPoly.constant(30.0), quad2, PPoly.constant(8.0)]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        pack = plan.prepare(scs)
        assert pack.loop_idx == [1]
        delta = pack.override({"dl.link": repl})  # used to raise here
        assert delta.loop_idx == [1]
        edited = [sweep.Scenario(label=sc.label,
                                 resource_inputs={("dl", "link"): fn})
                  for sc, fn in zip(scs, repl)]
        _assert_bit_identical(plan.sweep(delta, backend="auto"),
                              plan.sweep(plan.prepare(edited), backend="auto"))
        # batched rows ARE still validated: a quad aimed at row 0 must raise
        with pytest.raises(sweep.UnsupportedScenario, match="scenario 0"):
            pack.override({"dl.link": [quad2, quad2, quad2]})


def test_summary_surfaces_fallback_rate():
    plan, scs = _mixed_setup()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        rep = plan.sweep(scs, backend="auto")
    assert rep.fallback_indices == [1]
    s = rep.summary()
    assert "1/3" in s and "loop backend" in s and "[1]" in s
    assert "2 batched" in s
    # scalar + all-batched summaries
    assert "scalar analysis" in plan.solve().summary()
    clean = plan.sweep([scs[0], scs[2]], backend="batched")
    assert clean.fallback_indices == []
    assert "fallback" not in clean.summary()


def test_summary_warning_fires_exactly_once_per_sweep():
    plan, scs = _mixed_setup()
    pack = plan.prepare(scs)
    for _ in range(2):  # each sweep call warns once, including pack reuse
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            plan.sweep(pack, backend="auto")
        summary = [w for w in caught
                   if "fell back to the scalar loop" in str(w.message)]
        assert len(summary) == 1
        assert "1/3" in str(summary[0].message)

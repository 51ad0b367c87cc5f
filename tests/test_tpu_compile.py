"""The main path compiles for a TPU v5e that is described, not attached.

The TPU compiler refuses things interpret mode and the CPU backend accept
(a lane slice that lowers to a gather, a float iota inside a kernel), so
these tests compile the Pallas curve-query kernels and the fused engine at
the paper workflow's real widths for one chip of a ``v5e:2x2`` topology.
Nothing runs: a pass says the compiler accepts the program, not that it is
correct or fast on the chip (``chip_smoke.py`` checks that on a chip).
The fused sweep is also held to have no ``gather`` in its optimized HLO:
the TPU runs an element gather one element at a time, and the engine
selects by mask instead.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library.  The persistent
compilation cache is off around the compiles, because entries written for a
described chip cannot be read back without one.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import sweep
from repro.analysis import override, ramp_resource
from repro.analysis.optimize import _DiffObjective
from repro.analysis.pack import ThetaMap
from repro.configs.paper_workflow import (compile_paper_plan, fig7_space,
                                          sweep_scenarios)
from repro.core import PPoly
from repro.kernels.ppoly_eval import ops
from repro.sweep.jax_engine import DEFAULT_ITER_CAP, JaxSweepEngine

from test_fan_in import deploy, fan_in
from test_level_fused import _diamond

#: the Fig. 7 sweep width and one 128-lane query tile
B, T = 600, 128
#: widest progress / ceiling tables of the paper sweeps: 4 pieces, task3's
#: two data ceilings (F), quadratic coefficients under ramped links (K=3)
P, F = 4, 2
#: device memory of one v5e chip
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _no_gather(compiled) -> None:
    n = compiled.as_text().count(" gather(")
    assert n == 0, f"{n} element gathers in the fused sweep"


def _fits_one_chip(compiled) -> None:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 1e9:.1f} GB does not fit one chip"


# ------------------------------------------------------------ kernels ----
#: the jitted dispatchers behind ppoly_eval / ppoly_min_eval /
#: ppoly_first_crossing (padding + pallas_call), as Report's curve queries
#: call them on a chip
DISPATCH = {"ppoly_eval": ops._dispatch, "ppoly_min_eval": ops._dispatch_min,
            "ppoly_first_crossing": ops._dispatch_crossing}


@pytest.mark.parametrize("K", [2, 3])
@pytest.mark.parametrize("name", sorted(DISPATCH))
def test_kernel_compiles_for_v5e(name, K, one_chip):
    """Compiled (not interpreted) under the engine's process-wide x64."""
    assert jax.config.jax_enable_x64
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa: E731
                                              sharding=one_chip)
    fns = (f32(B, F, P), f32(B, F, P, K)) if name == "ppoly_min_eval" \
        else (f32(B, P), f32(B, P, K))
    lowered = DISPATCH[name].lower(*fns, f32(B, T), use_pallas=True,
                                   interpret=False, block_b=8, block_t=T)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


# ------------------------------------------------------- fused engine ----
def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.float64,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("ramps", [False, True], ids=["linear", "ramps"])
def test_sweep_engine_compiles_for_v5e(ramps, one_chip):
    """``make_run`` at the Fig. 7 sweep width, float64 as the engine runs."""
    assert jax.config.jax_enable_x64
    plan = compile_paper_plan(0.5)
    fracs = np.linspace(0.02, 0.98, B)
    scs = ([ramp_resource("dl2", "link", [0.0, 200.0], [4e6 * f, 0.5e6])
            for f in fracs] if ramps else sweep_scenarios(fracs))
    pack = plan.prepare(scs)
    assert pack.ramps == ramps and pack.B_batched == B
    eng = JaxSweepEngine(plan)
    largs = eng.level_args(pack.host_args(), B, ramps)
    run = jax.jit(eng._make_run(B, DEFAULT_ITER_CAP, ramps))
    compiled = run.lower(_shapes(largs, one_chip)).compile()
    _fits_one_chip(compiled)
    _no_gather(compiled)


def _diamond_pack():
    """A wide level (5 processes, up to 2 resources, a burst resource), a
    two-input join gated on a third process."""
    plan = _diamond(burst=True).compile()
    return plan, plan.prepare([
        sweep.Scenario(label=f"m{m}",
                       resource_inputs={("m1", "mem"): PPoly.constant(m)})
        for m in (0.5, 1.0, 4.0, 8.0)])


def _fan_in_pack():
    """A 23-input join, as wide as Montage's mConcatFit."""
    cfg = fan_in(23, "crossover")
    plan = deploy.build_workflow(cfg).compile()
    return plan, plan.prepare([
        deploy.program_scenario({"c.cpu": ("scale", f)},
                                deploy.data_keys(cfg))
        for f in (0.5, 1.0, 2.0, 3.0)])


@pytest.mark.parametrize("make", [_diamond_pack, _fan_in_pack],
                         ids=["diamond_burst", "fan_in_23"])
def test_fan_in_sweep_compiles_gather_free_for_v5e(make, one_chip):
    """Joins (several ceiling slots) through ``_solve_level`` and the
    in-trace composition of edge-fed ceilings."""
    plan, pack = make()
    eng = JaxSweepEngine(plan)
    assert max(ls.nC for ls in eng.spec.levels) > 1
    B = pack.B_batched
    largs = eng.level_args(pack.host_args(), B, pack.ramps)
    run = jax.jit(eng._make_run(B, DEFAULT_ITER_CAP, pack.ramps))
    _no_gather(run.lower(_shapes(largs, one_chip)).compile())


def test_diff_engine_compiles_for_v5e(one_chip):
    """``make_diff_run`` value + gradient, as ``plan.optimize`` runs the
    Fig. 7 search (one iterate per gradient step)."""
    plan = compile_paper_plan(0.5)
    pack = plan.prepare([override(label="base")])
    obj = _DiffObjective(plan, ThetaMap(plan, fig7_space().axes), pack, 1,
                         None)
    largs = obj.eng.level_args(pack.host_args(), pack.B_batched, pack.ramps)
    theta = jax.ShapeDtypeStruct((1, 1), jnp.float64, sharding=one_chip)
    lowered = obj._compiled(1, grad=True).lower(theta,
                                                _shapes(largs, one_chip))
    _fits_one_chip(lowered.compile())
